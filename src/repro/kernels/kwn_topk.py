"""Pallas TPU kernel: KWN descending-ramp top-K with early stop (paper C3).

Implements the hardware algorithm literally: codes are swept from the highest
ramp level downward; columns whose quantized MAC equals the level "cross" and
are admitted in priority-encoder (index) order until K winners are found.  The
step index at which the K-th winner appears is the early-stop ADC cycle count
— emitted per row so the latency/energy model consumes *measured* statistics.

Block layout: rows tile over the grid; the lane dimension is the macro's 128
columns (one physical macro per block).  The level sweep is a fori_loop of
n_codes iterations (32 for the 5-bit IMA) over VREG-resident state — no HBM
traffic inside the sweep.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BM = 128


def _kwn_kernel(mac_ref, bounds_ref, mask_ref, steps_ref, *, k: int,
                n_codes: int):
    mac = mac_ref[...]                                    # (bm, N)
    bounds = bounds_ref[...]                              # (1, n_codes-1)
    bm, n = mac.shape

    # Ramp conversion: code = #boundaries below the value.
    codes = jnp.sum((mac[:, :, None] > bounds[0][None, None, :]),
                    axis=-1).astype(jnp.int32)            # (bm, N)

    def sweep(step, carry):
        n_found, mask, steps = carry
        level = n_codes - 1 - step                        # descending ramp
        crossing = (codes == level) & (mask == 0)
        order = jnp.cumsum(crossing.astype(jnp.int32), axis=-1)
        admit = crossing & ((n_found + order) <= k)       # priority encoder
        mask = mask + admit.astype(jnp.int32)
        n_found = n_found + jnp.sum(admit.astype(jnp.int32), axis=-1,
                                    keepdims=True)
        # Early stop: record the first step where K winners exist.
        done_now = (n_found >= k) & (steps < 0)
        steps = jnp.where(done_now, step, steps)
        return n_found, mask, steps

    init = (jnp.zeros((bm, 1), jnp.int32), jnp.zeros((bm, n), jnp.int32),
            jnp.full((bm, 1), -1, jnp.int32))
    n_found, mask, steps = jax.lax.fori_loop(0, n_codes, sweep, init)
    steps = jnp.where(steps < 0, n_codes - 1, steps)

    mask_ref[...] = mask.astype(jnp.float32)
    steps_ref[...] = steps


@functools.partial(jax.jit, static_argnames=("k", "bm", "interpret"))
def kwn_topk(mac: jax.Array, boundaries: jax.Array, k: int,
             bm: int = DEFAULT_BM, *, interpret: bool):
    """mac: (M, N) f32; boundaries: (n_codes-1,) -> (mask (M,N) f32,
    adc_steps (M,1) i32).  M must be a multiple of bm; N is the macro width.
    """
    m, n = mac.shape
    assert m % bm == 0, (m, bm)
    n_codes = boundaries.shape[0] + 1
    grid = (m // bm,)

    return pl.pallas_call(
        functools.partial(_kwn_kernel, k=k, n_codes=n_codes),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, n), lambda i: (i, 0)),
            pl.BlockSpec((1, n_codes - 1), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bm, n), lambda i: (i, 0)),
            pl.BlockSpec((bm, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, n), jnp.float32),
            jax.ShapeDtypeStruct((m, 1), jnp.int32),
        ],
        interpret=interpret,
    )(mac, boundaries.reshape(1, -1))
