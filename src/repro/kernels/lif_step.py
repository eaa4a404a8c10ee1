"""Pallas TPU kernel: fused digital LIF step (paper C5, Eq. 1).

Fuses the silicon's 3-stage pipeline (leak -> update -> compare) plus the SNL
probabilistic-firing path into a single VMEM pass: one read of (v, drive,
mask, noise), one write of (v', spike).  Unfused, this chain is 4 HBM reads +
4 intermediate writes; fused it is memory-optimal (the LIF is purely
bandwidth-bound, so the fusion is the entire win).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BM = 256


def _lif_kernel(v_ref, drive_ref, mask_ref, noise_ref, v_out_ref, spike_ref,
                *, beta: float, v_th1: float, v_th2: float, v_reset: float,
                v_lim: float, use_snl: bool):
    v = v_ref[...]
    drive = drive_ref[...]
    mask = mask_ref[...]

    # Eq. (1): winners leak+integrate, non-winners hold.
    v_new = jnp.where(mask > 0, beta * v + drive, v)

    if use_snl:
        # SNL: neurons sitting in (v_th2, v_th1) get the PRBS kick.
        noise = noise_ref[...]
        snl = (v_new > v_th2) & (v_new < v_th1)
        v_new = jnp.where(snl, v_new + noise, v_new)

    v_new = jnp.clip(v_new, -v_lim, v_lim)      # 12-bit register saturation
    spike = (v_new >= v_th1).astype(jnp.float32)
    v_out_ref[...] = jnp.where(spike > 0, v_reset, v_new)
    spike_ref[...] = spike


@functools.partial(jax.jit, static_argnames=("beta", "v_th1", "v_th2",
                                             "v_reset", "v_lim", "use_snl",
                                             "bm", "interpret"))
def lif_step_fused(v: jax.Array, drive: jax.Array, mask: jax.Array,
                   noise: jax.Array, beta: float = 0.9, v_th1: float = 1.0,
                   v_th2: float = 0.6, v_reset: float = 0.0,
                   v_lim: float = 8.0, use_snl: bool = True,
                   bm: int = DEFAULT_BM, *, interpret: bool):
    """All inputs (M, N) f32; returns (v_out, spikes), both (M, N) f32."""
    m, n = v.shape
    assert m % bm == 0, (m, bm)
    grid = (m // bm,)
    spec = pl.BlockSpec((bm, n), lambda i: (i, 0))

    return pl.pallas_call(
        functools.partial(_lif_kernel, beta=beta, v_th1=v_th1, v_th2=v_th2,
                          v_reset=v_reset, v_lim=v_lim, use_snl=use_snl),
        grid=grid,
        in_specs=[spec, spec, spec, spec],
        out_specs=[spec, spec],
        out_shape=[jax.ShapeDtypeStruct((m, n), jnp.float32),
                   jax.ShapeDtypeStruct((m, n), jnp.float32)],
        interpret=interpret,
    )(v, drive, mask, noise)
