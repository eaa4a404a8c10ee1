"""Pallas TPU kernel: the surrogate backward of the fused macro sequence.

This is the first backward-capable kernel in the repo: the time-reversed
BPTT pass of ``kernels.fused_macro.fused_macro_seq`` (KWN mode), so silicon
training runs its gradient step on the same tile plans — and the same
activity gating — the serving forward uses.  The gradient *semantics* are
defined by ``kernels.ref.fused_macro_seq_vjp_ref`` (the differentiable
oracle); this kernel must match ``jax.grad`` of that oracle.

Backward dataflow
-----------------
The forward LIF recurrence couples time steps per column; the MAC couples
columns per time step.  Given the forward residuals, the backward therefore
factors into

  1. an elementwise cotangent chain per (step, row-tile, col-tile) —
     SuperSpike surrogate through the spike comparator, hard cut at the
     V_mem saturation rails, the winner/loser leak-vs-hold split — feeding
     the reverse-time membrane cotangent ``g_v`` (carried in VMEM across
     the whole reversed T axis, exactly like the forward membrane);
  2. one MXU contraction per step: ``dW += x_t^T @ g_mac_t``, where
     ``g_mac`` is the elementwise chain's output gated by the (relaxed) KWN
     winner mask and the IMA ramp's straight-through window.

Grid is ``(M/bm, T, NC/bn)`` with the *time index maps reversed*
(grid step t reads forward step T-1-t), so the cotangent recurrence walks
the sequence backwards in one launch.  ``dW`` lives as a single
full-(K, NC) output block with a constant index map — revisited at every
grid step, so accumulation is pipeline-safe — which puts the VMEM ceiling
at ``4*K*NC`` bytes (512 KB for the 512x256 bench layer; layers beyond
~2-4 MB of weight gradient should split at the model layer, same ceiling
family as the forward head's one-hot transient).

Residual-vs-recompute policy
----------------------------
The elementwise chain needs the per-step membrane trace (``vtrace``, a new
opt-in forward output) and winner masks; the ramp's straight-through window
needs the *clean analog MAC*.  Two ways to get the MAC:

  * **residual** (default): the forward saves the (T, M, NC) MAC stack
    (``mac_telemetry=True``) and the backward streams it — one extra HBM
    tensor, no extra compute;
  * **recompute** (``mac`` absent, ``msb/lsb`` given): the backward re-runs
    the ternary MAC per (step, col-tile) on the MXU — the right trade when
    the residual stack would not fit (long sequences / wide layers), and
    exactly bitwise-equal to the residual because the MAC is small exact
    integers (associativity-free in f32), so the two policies produce
    *identical* gradients, not merely close ones.

Activity gating rides along: the reverse pass always runs the (cheap)
elementwise chain — the cotangent recurrence does not stop when events do —
but skips both MAC contractions for row-tile time steps whose forward
activity map is empty (an all-zero ``x_t`` block contributes exactly zero
to ``dW``), so sparse event streams train as cheaply as they serve.

Noise needs no special handling here: the Fig. 7 draws and the SNL kicks
shape the residuals (masks, membrane trace) in the forward, and the
straight-through tangent rides the clean MAC — so one backward kernel
serves the clean and the counter-PRNG noisy forward alike, and noisy
gradients are exactly reproducible from the forward seed.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import fused_macro as _fm


def _seq_kwn_bwd_kernel(*refs, n_t, n_i, bn, ratio, drive_gain, beta, v_th1,
                        v_lim, kwn_relax, surrogate_beta, ste_lo, ste_hi,
                        has_mac, remat, gated):
    """One grid step: reversed time index ``ti`` -> forward step T-1-ti.

    Ref order is (scalar prefetch), inputs, outputs:
    ``[occ?] x scale g_vfin vtrace mask g_spk [mac?] [msb lsb?] dw dv0``.
    """
    refs = list(refs)
    occ_ref = refs.pop(0) if gated else None
    x_ref, scale_ref, g_vfin_ref, vtrace_ref, mask_ref, g_spk_ref = refs[:6]
    refs = refs[6:]
    mac_ref = refs.pop(0) if has_mac else None
    if remat:
        msb_ref, lsb_ref = refs.pop(0), refs.pop(0)
    dw_ref, dv0_ref = refs

    i, ti, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    t_fwd = (n_t - 1) - ti
    single = dv0_ref.shape[-1] == bn          # one column tile: full slice
    col = (slice(None),
           slice(None) if single else pl.ds(pl.multiple_of(j * bn, bn), bn))

    @pl.when((i == 0) & (ti == 0) & (j == 0))
    def _zero_dw():
        dw_ref[...] = jnp.zeros(dw_ref.shape, jnp.float32)

    @pl.when(ti == 0)
    def _seed_carry():                       # g_v(T) = cotangent of v_out
        dv0_ref[col] = g_vfin_ref[col]

    # --- elementwise cotangent chain (always runs: g_v must flow) --------
    g_v = dv0_ref[col]
    vt = vtrace_ref[0]                       # pre-reset saturated membrane
    m = mask_ref[0]
    spk = (vt >= v_th1).astype(jnp.float32)
    arg = surrogate_beta * (vt - v_th1)
    sg_spk = surrogate_beta / (1.0 + jnp.abs(arg)) ** 2   # SuperSpike
    g_vclip = g_v * (1.0 - spk) + g_spk_ref[0] * sg_spk
    inside = (jnp.abs(vt) < v_lim).astype(jnp.float32)    # rail cut
    g_v2 = g_vclip * inside                  # SNL add is grad-transparent
    dv0_ref[col] = g_v2 * (m * beta + (1.0 - m))  # winners leak, losers hold

    # --- dW contraction (activity-gated: empty x_t blocks contribute 0) --
    def _contract():
        xf = x_ref[0].astype(jnp.float32)    # (bm, K)
        if mac_ref is not None:
            mac_t = mac_ref[0]
        else:                                # recompute: exact-int MAC
            wt = ratio * msb_ref[...].astype(jnp.float32) \
                + lsb_ref[...].astype(jnp.float32)
            mac_t = jax.lax.dot_general(
                xf, wt, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
        in_ramp = ((mac_t >= ste_lo) & (mac_t <= ste_hi)) \
            .astype(jnp.float32)             # IMA straight-through window
        gate = m + kwn_relax * (1.0 - m)     # relaxed hard KWN gate
        g_mac = g_v2 * gate * scale_ref[...] * drive_gain * in_ramp
        part = jax.lax.dot_general(          # x_t^T @ g_mac: (K, bn)
            xf, g_mac, (((0,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,  # g_mac is not bf16-exact
            preferred_element_type=jnp.float32)
        dw_ref[col] = dw_ref[col] + part

    # The dense launch runs the contraction under an always-true predicate,
    # so both launches emit the same predicated dot: the f32 dW sums then
    # accumulate in one order and gating stays bitwise-neutral.
    pl.when(occ_ref[t_fwd * n_i + i] > 0 if gated else ti >= 0)(_contract)


@functools.partial(jax.jit, static_argnames=(
    "ratio", "drive_gain", "beta", "v_th1", "v_lim", "kwn_relax",
    "surrogate_beta", "ste_lo", "ste_hi", "bm", "bn", "interpret"))
def fused_macro_seq_grad(x: jax.Array, scale: jax.Array, g_spk: jax.Array,
                         g_vfin: jax.Array, vtrace: jax.Array,
                         mask: jax.Array, mac: jax.Array | None = None,
                         msb: jax.Array | None = None,
                         lsb: jax.Array | None = None,
                         activity: jax.Array | None = None, *,
                         ratio: float = 2.0, drive_gain: float = 1.0,
                         beta: float = 0.9, v_th1: float = 1.0,
                         v_lim: float = 8.0, kwn_relax: float = 0.0,
                         surrogate_beta: float = 4.0,
                         ste_lo: float = -24.5, ste_hi: float = 24.5,
                         bm: int = 128, bn: int | None = None,
                         interpret: bool):
    """The fused surrogate backward: padded operands, one launch.

    x:        (T, M, K) int8 ternary inputs (the forward's, padded).
    scale:    (1, NC) per-column weight scale (padded columns zero — they
              self-mask out of ``dW``).
    g_spk:    (T, M, N) f32 cotangent of the per-step spike stack.
    g_vfin:   (M, N) f32 cotangent of the final membrane.
    vtrace:   (T, M, N) f32 membrane trace (forward ``train_trace`` output).
    mask:     (T, M, N) f32 KWN winner masks (forward output).
    mac:      (T, M, NC) f32 clean integer-unit MAC residual, or None to
              recompute it from ``msb``/``lsb`` (the remat policy — exactly
              gradient-identical, see module docstring).
    activity: (T, M/bm) int32 row-tile occupancy (any K-tile active), or
              None for dense execution.  Scalar-prefetched; empty blocks
              skip both MXU contractions.

    Returns (dw (K, NC) f32, dv0 (M, N) f32): the cotangents of the
    integer-unit weight and the initial membrane.
    """
    t_steps, m, kdim = x.shape
    n = vtrace.shape[-1]
    bn = n if bn is None else bn
    assert m % bm == 0 and n % bn == 0, (m, n, bm, bn)
    assert g_spk.shape == (t_steps, m, n) and vtrace.shape == g_spk.shape
    assert mask.shape == g_spk.shape and g_vfin.shape == (m, n)
    has_mac = mac is not None
    remat = not has_mac
    if remat:
        assert msb is not None and lsb is not None
        assert msb.shape == (kdim, n) and lsb.shape == (kdim, n)
    else:
        assert mac.shape == (t_steps, m, n), (mac.shape,)
    gated = activity is not None
    n_i = m // bm
    if gated:
        assert activity.shape == (t_steps, n_i), (activity.shape,)
    grid = (n_i, t_steps, n // bn)
    rev = t_steps - 1

    in_specs = [
        pl.BlockSpec((1, bm, kdim), lambda i, t, j, *_: (rev - t, i, 0)),
        pl.BlockSpec((1, bn), lambda i, t, j, *_: (0, j)),          # scale
        pl.BlockSpec((bm, n), lambda i, t, j, *_: (i, 0)),          # g_vfin
        pl.BlockSpec((1, bm, bn), lambda i, t, j, *_: (rev - t, i, j)),
        pl.BlockSpec((1, bm, bn), lambda i, t, j, *_: (rev - t, i, j)),
        pl.BlockSpec((1, bm, bn), lambda i, t, j, *_: (rev - t, i, j)),
    ]
    inputs = [x.astype(jnp.int8), scale.astype(jnp.float32).reshape(1, -1),
              g_vfin.astype(jnp.float32), vtrace, mask, g_spk]
    if has_mac:
        in_specs.append(
            pl.BlockSpec((1, bm, bn), lambda i, t, j, *_: (rev - t, i, j)))
        inputs.append(mac)
    else:
        in_specs += [pl.BlockSpec((kdim, bn), lambda i, t, j, *_: (0, j)),
                     pl.BlockSpec((kdim, bn), lambda i, t, j, *_: (0, j))]
        inputs += [msb.astype(jnp.int8), lsb.astype(jnp.int8)]

    out_specs = [
        pl.BlockSpec((kdim, n), lambda i, t, j, *_: (0, 0)),        # dw
        pl.BlockSpec((bm, n), lambda i, t, j, *_: (i, 0)),          # dv0
    ]
    out_shape = [jax.ShapeDtypeStruct((kdim, n), jnp.float32),
                 jax.ShapeDtypeStruct((m, n), jnp.float32)]

    kernel = functools.partial(
        _seq_kwn_bwd_kernel, n_t=t_steps, n_i=n_i, bn=bn, ratio=ratio,
        drive_gain=drive_gain, beta=beta, v_th1=v_th1, v_lim=v_lim,
        kwn_relax=kwn_relax, surrogate_beta=surrogate_beta, ste_lo=ste_lo,
        ste_hi=ste_hi, has_mac=has_mac, remat=remat, gated=gated)

    if gated:
        return pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=grid, in_specs=in_specs,
                out_specs=out_specs),
            out_shape=out_shape,
            interpret=interpret,
        compiler_params=_fm.compiler_params(interpret),
        )(activity.reshape(-1).astype(jnp.int32), *inputs)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
        compiler_params=_fm.compiler_params(interpret),
    )(*inputs)
