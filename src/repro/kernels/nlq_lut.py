"""Pallas TPU kernel: NLQ ramp conversion + LUT map-back (paper C2/C6).

The IMA's nonlinear ramp is a monotone boundary set; conversion is a compare-
and-count against the (n_codes-1) boundaries, and the KWN-mode LUT map-back is
a gather from the level table.  TPU adaptation: the boundary compare is a
broadcast over the 32-entry codebook held in VMEM (VREG-resident after first
use) and the LUT gather becomes a one-hot matmul — gathers are slow on the
VPU, but a (bm, 128, 32) one-hot contraction with a (32,) table hits the MXU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BM = 256


def _nlq_kernel(x_ref, bounds_ref, levels_ref, code_ref, y_ref, *,
                n_codes: int):
    x = x_ref[...]                                     # (bm, N)
    bounds = bounds_ref[...][0]                        # (n_codes-1,)
    levels = levels_ref[...][0]                        # (n_codes,)

    # Ramp conversion: count boundaries crossed (ripple counter).
    code = jnp.sum((x[:, :, None] > bounds[None, None, :]), axis=-1
                   ).astype(jnp.int32)                 # (bm, N)

    # LUT map-back as one-hot (MXU-friendly; no VPU gather).
    iota = jax.lax.broadcasted_iota(jnp.int32, (1, 1, n_codes), 2)
    onehot = (code[:, :, None] == iota).astype(jnp.float32)
    y = jnp.sum(onehot * levels[None, None, :], axis=-1)

    code_ref[...] = code
    y_ref[...] = y


@functools.partial(jax.jit, static_argnames=("bm", "interpret"))
def nlq_convert(x: jax.Array, boundaries: jax.Array, levels: jax.Array,
                bm: int = DEFAULT_BM, *, interpret: bool):
    """x: (M, N) f32 -> (codes (M,N) i32, reconstruction (M,N) f32)."""
    m, n = x.shape
    assert m % bm == 0, (m, bm)
    n_codes = levels.shape[0]
    grid = (m // bm,)

    return pl.pallas_call(
        functools.partial(_nlq_kernel, n_codes=n_codes),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, n), lambda i: (i, 0)),
            pl.BlockSpec((1, n_codes - 1), lambda i: (0, 0)),
            pl.BlockSpec((1, n_codes), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bm, n), lambda i: (i, 0)),
            pl.BlockSpec((bm, n), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, n), jnp.int32),
            jax.ShapeDtypeStruct((m, n), jnp.float32),
        ],
        interpret=interpret,
    )(x, boundaries.reshape(1, -1), levels.reshape(1, -1))
