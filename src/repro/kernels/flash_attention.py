"""Pallas TPU kernel: flash-attention forward with causal block skipping.

Why it exists here: the §Roofline table shows train/prefill attention in the
XLA path computes the full S x S score grid (masked) — a 2x flops waste on
causal shapes.  This kernel implements the standard online-softmax streaming
attention with the strictly-upper-triangular blocks *skipped* (pl.when), so
prefill compute approaches the causal-optimal S^2/2.

Layout: grid (B*H, n_q_blocks, n_kv_blocks), innermost kv dimension iterates
sequentially per q block; (acc, m, l) live in VMEM scratch across kv steps
(the canonical Pallas flash pattern).  Blocks are MXU-aligned (bq, bk
multiples of 128 on real TPU; smaller allowed in interpret mode for tests).

Forward-only: serving prefill needs no backward; training keeps the XLA
blockwise path (its backward is rematerialized chunk-wise already).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  scale: float, causal: bool, bq: int, bk: int, nk: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # causal: block (qi, ki) is dead when its first k col > its last q row
    live = (ki * bk <= qi * bq + bq - 1) if causal else (ki >= 0)

    @pl.when(live)
    def _compute():
        q = q_ref[0].astype(jnp.float32)              # (bq, d)
        k = k_ref[0].astype(jnp.float32)              # (bk, d)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            rows = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            cols = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(rows >= cols, s, NEG_INF)
        m_prev = m_ref[...]                           # (bq, 1)
        l_prev = l_ref[...]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[...] = m_new
        l_ref[...] = l_new

    @pl.when(ki == nk - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                    ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "bq", "bk",
                                             "interpret"))
def flash_attention_fwd(q: jax.Array, k: jax.Array, v: jax.Array,
                        causal: bool = True, bq: int = 128, bk: int = 128,
                        *, interpret: bool) -> jax.Array:
    """q,k,v: (BH, S, D) -> (BH, S, D).  S % bq == S % bk == 0."""
    bh, s, d = q.shape
    assert s % bq == 0 and s % bk == 0, (s, bq, bk)
    nq, nk = s // bq, s // bk
    scale = 1.0 / (d ** 0.5)

    return pl.pallas_call(
        functools.partial(_flash_kernel, scale=scale, causal=causal,
                          bq=bq, bk=bk, nk=nk),
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s, d), q.dtype),
        scratch_shapes=[
            _vmem_scratch((bq, d)),
            _vmem_scratch((bq, 1)),
            _vmem_scratch((bq, 1)),
        ],
        interpret=interpret,
    )(q, k, v)


def _vmem_scratch(shape):
    """VMEM f32 scratch accumulator spec (TPU memory space)."""
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.VMEM(shape, jnp.float32)


def causal_flops_saving(s: int, bq: int, bk: int) -> float:
    """Fraction of block-pairs skipped by the causal gate."""
    nq, nk = s // bq, s // bk
    live = sum(1 for i in range(nq) for j in range(nk)
               if j * bk <= i * bq + bq - 1)
    return 1.0 - live / (nq * nk)
