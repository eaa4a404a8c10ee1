"""Pallas TPU kernel: packed ternary MAC (twin 9T bit-cell GEMM, paper C1).

TPU adaptation of the analog macro: the MSB/LSB twin-cell planes are stored as
int8 ternary tensors and *decoded on the fly* inside the kernel
(``w = ratio * msb + lsb``), so HBM traffic is 2 int8 planes instead of a
dequantized bf16/f32 weight — a 2x (vs bf16) / 4x (vs f32) memory-bandwidth
saving, which is the TPU-native analogue of the macro's in-array multi-bit
composition.  The MAC itself runs on the MXU at f32 accumulation.

Tiling: grid (M/bm, N/bn, K/bk); the K dimension is innermost so the output
block accumulates in VMEM across K steps (revisiting semantics).  Block sizes
default to MXU-aligned (128) multiples; the natural bn is the macro's own
column count, 128.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


DEFAULT_BM = 128
DEFAULT_BN = 128
DEFAULT_BK = 256  # the macro's row count: one K-tile == one physical macro


def _ternary_mac_kernel(x_ref, msb_ref, lsb_ref, o_ref, *, ratio: float,
                        n_k: int):
    """One (bm, bn) output tile; accumulates over the K grid axis."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[...].astype(jnp.float32)
    # Twin-cell decode: multi-VDD bank composition (I_MSB = ratio * I_LSB).
    w = ratio * msb_ref[...].astype(jnp.float32) + lsb_ref[...].astype(jnp.float32)
    o_ref[...] += jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "ratio",
                                             "interpret"))
def ternary_mac(x: jax.Array, msb: jax.Array, lsb: jax.Array,
                bm: int = DEFAULT_BM, bn: int = DEFAULT_BN,
                bk: int = DEFAULT_BK, ratio: float = 2.0,
                *, interpret: bool) -> jax.Array:
    """x: (M, K) int8 ternary; msb/lsb: (K, N) int8 ternary -> (M, N) f32.

    Shapes must be multiples of the block sizes (``ops.py`` pads).
    """
    m, k = x.shape
    k2, n = msb.shape
    assert k == k2 and msb.shape == lsb.shape
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (m, n, k, bm, bn, bk)
    grid = (m // bm, n // bn, k // bk)

    return pl.pallas_call(
        functools.partial(_ternary_mac_kernel, ratio=ratio, n_k=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        interpret=interpret,
    )(x, msb, lsb)
