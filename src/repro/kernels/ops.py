"""jit'd public wrappers for the Pallas kernels: padding, dtype handling, and
the interpret-mode decision (CPU validation vs TPU execution).

``interpret_mode()`` is the one place that decides how a kernel runs: Mosaic
compiles it whenever the default backend is a TPU, and the Pallas
interpreter runs it everywhere else.  Nothing falls back from the chip.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import ternary as _ternary
from repro.kernels import fused_macro as _fused
from repro.kernels import fused_macro_grad as _fused_grad


def interpret_mode() -> bool:
    """True unless the default JAX backend is a TPU.

    Every kernel wrapper here, and the plan cache's device key, asks this
    at trace time; on a TPU the kernels compile with Mosaic, elsewhere they
    run in the Pallas interpreter.
    """
    return jax.default_backend() != "tpu"


def _pad_cols(a, n: int, n_pad: int, n_branches: int):
    """Zero-pad the branch-major column axis (last) from J*n to J*n_pad."""
    if n_pad == n:
        return a
    lead = a.shape[:-1]
    branched = a.reshape(*lead, n_branches, n)
    widths = [(0, 0)] * (branched.ndim - 1) + [(0, n_pad - n)]
    return jnp.pad(branched, widths).reshape(*lead, n_branches * n_pad)


def _unpad_cols(a, n: int, n_pad: int, n_branches: int):
    """Inverse of ``_pad_cols`` for branch-major column outputs."""
    if n_pad == n:
        return a
    lead = a.shape[:-1]
    branched = a.reshape(*lead, n_branches, n_pad)
    return branched[..., :n].reshape(*lead, n_branches * n)


def event_stream_issues(events, n_in: int | None = None):
    """Host-side check of the fused kernels' event-tensor input contract.

    The kernels consume a ``(T, n_in)`` ternary tensor — finite values in
    {-1, 0, +1}, a real-number dtype, at least one time step.  Anything
    else either crashes the launch with an opaque shape error or, worse,
    flows through the MAC as silent garbage (NaNs propagate into every
    membrane the slot touches for the rest of the round).  This is the
    single source of truth the serving layer's submit-time validation
    (``serve.lifecycle.validate_events``) consults *before* any kernel
    launch is staged.

    Pure numpy (no device dispatch on the submit path).  Returns
    ``(ev, issues)``: the ``np.ndarray`` view of ``events`` (or None when
    the dtype cannot even be materialized) and a list of
    ``(code, message)`` pairs with codes ``dtype`` / ``shape`` / ``empty``
    / ``nonfinite`` / ``nonternary``; an empty list means the tensor is
    launchable as-is.
    """
    import numpy as np
    issues: list[tuple[str, str]] = []
    try:
        ev = np.asarray(events)
    except Exception as e:   # ragged lists, arbitrary objects
        return None, [("dtype", f"events not array-like ({e})")]
    if ev.dtype == object or ev.dtype.kind in "USVcM":
        return ev, [("dtype", f"events dtype {ev.dtype} is not a real "
                              f"number type")]
    if ev.ndim != 2:
        issues.append(("shape", f"events must be (T, n_in); got shape "
                                f"{ev.shape}"))
    elif n_in is not None and ev.shape[1] != n_in:
        issues.append(("shape", f"events width {ev.shape[1]} != engine "
                                f"n_in {n_in}"))
    if ev.size == 0:
        issues.append(("empty", f"zero-length event stream (shape "
                                f"{ev.shape})"))
        return ev, issues
    if ev.dtype.kind == "f" and not bool(np.isfinite(ev).all()):
        issues.append(("nonfinite", "events carry NaN/Inf values"))
        return ev, issues     # ternary test on NaNs would double-report
    if not bool(np.isin(ev, (-1.0, 0.0, 1.0)).all()):
        bad = ev[~np.isin(ev, (-1.0, 0.0, 1.0))]
        issues.append(("nonternary",
                       f"events must be ternary in {{-1, 0, +1}}; got "
                       f"{bad.flat[0]!r} (and {bad.size - 1} more)"))
    return ev, issues


def fused_activity_map(xm: jax.Array, plan) -> jax.Array:
    """Per-(step, row-tile, K-tile) occupancy of a padded time-major input.

    xm (T, m_pad, k_pad) ternary events, plan a ``fused_macro.TilePlan``;
    returns the (T, m_pad/bm, k_pad/bk) int32 map (1 = the block holds at
    least one event) the gated kernel consumes via scalar prefetch.  This
    is the whole host-side activity-planning pass: one any-reduce over the
    input, O(T*M*K) bit tests, negligible next to a single MAC step.
    """
    t = xm.shape[0]
    n_i, n_k = plan.m_pad // plan.bm, plan.k_pad // plan.bk
    occ = (xm != 0).reshape(t, n_i, plan.bm, n_k, plan.bk)
    return occ.any(axis=(2, 4)).astype(jnp.int32)


def fused_macro_seq(x, msb, lsb, boundaries, levels, scale, v, noise=None,
                    w_dend=None, *, mode: str = "kwn", k: int = 12,
                    ratio: float = 2.0, drive_gain: float = 1.0,
                    beta: float = 0.9, v_th1: float = 1.0, v_th2: float = 0.6,
                    v_reset: float = 0.0, v_lim: float = 8.0,
                    use_snl: bool = True, bm: int | None = None,
                    bk: int | None = None, bn: int | None = None,
                    ima_noise=None, snl_amp: float = 0.0,
                    gate: bool = True, activity=None,
                    mac_telemetry: bool = True, train_trace: bool = False,
                    seed=0, step_offset=0, row_ctl=None):
    """Batched time-major fused sequence; x (T, ..., K), v (..., N),
    noise (T, ..., N) or None for in-kernel counter noise.

    Pads the batch to the row tile, K to the macro row count, and — for
    layers wider than one macro — the column axis to the column tile (zero
    padding is MAC-neutral; padded columns are masked out of the KWN ramp
    and, in NLD mode, padded per branch so the branch-major layout
    survives).  Runs the whole sequence through one kernel launch with the
    LIF membrane carried in VMEM, then slices the padding back off.

    ``gate`` (default on — it cannot change any output bit) runs the
    activity-gated kernel: a per-(step, row-tile, K-tile) occupancy map is
    computed from the events (``fused_activity_map``; or pass a
    precomputed ``activity``) and scalar-prefetched into the kernel, which
    skips the plane decode + MXU contraction for all-zero blocks and
    bounds the KWN ramp sweep to the occupied code range.  ``gate=False``
    is the dense execution the pre-sparsity pipeline ran — kept as the
    benchmark baseline and for A/B parity tests.  ``mac_telemetry=False``
    keeps the raw MAC accumulator in VMEM scratch (no (T, ..., NC) HBM
    stack; the returned mac is None) — the serving default upstream.

    ``ima_noise`` (an ``ima.IMAKernelNoise``) turns on the in-kernel Fig. 7
    conversion-error model; the counter streams are keyed on *logical*
    (row, column) coordinates, so padding and tile choice cannot move a
    draw.  ``noise=None`` with ``snl_amp > 0`` generates the SNL sign noise
    in-kernel as well — the noisy path streams no per-step tensors at all.

    ``row_ctl`` (optional, (..., 3) int32 over the same batch lead dims as
    ``v``) gives every batch row its own ``[seed, step_offset, row_id]``
    noise-stream control, overriding the scalar ``seed``/``step_offset`` —
    the continuous-batching engine uses it so each slot replays the
    counter stream of an independent batch-1 run.

    ``train_trace=True`` (KWN only) appends the per-step membrane trace
    vtrace (T, ..., N) — the post-saturation, pre-reset V_mem — to the
    return tuple; it is the residual the surrogate backward kernel
    (``fused_macro_grad``) consumes.

    Returns (mac (T, ..., NC) or None, v_out (..., N), spikes (T, ..., N),
    mask (T, ..., N), adc_steps (T, ...)), plus vtrace (T, ..., N) when
    ``train_trace``.
    """
    t = x.shape[0]
    lead = x.shape[1:-1]
    kdim = x.shape[-1]
    n = v.shape[-1]
    nc = msb.shape[-1]
    n_branches = nc // n if mode == "nld" else 1
    xm = x.reshape(t, -1, kdim)
    vm = v.reshape(-1, n)
    m0 = xm.shape[1]
    plan = _fused.plan_tiles(m0, kdim, nc, n, t, mode=mode,
                             n_branches=n_branches, bm=bm, bk=bk, bn=bn)
    xm = jnp.pad(xm, ((0, 0), (0, plan.m_pad - m0), (0, plan.k_pad - kdim)))
    vm = jnp.pad(vm, ((0, plan.m_pad - m0), (0, plan.n_pad - n)))
    if not gate:
        activity = None
    elif activity is None:
        activity = fused_activity_map(xm, plan)
    nm = None
    if noise is not None:
        nm = noise.reshape(t, -1, n)
        nm = jnp.pad(nm, ((0, 0), (0, plan.m_pad - m0), (0, plan.n_pad - n)))
    msb_p = _pad_cols(jnp.pad(msb, ((0, plan.k_pad - kdim), (0, 0))),
                      n, plan.n_pad, n_branches)
    lsb_p = _pad_cols(jnp.pad(lsb, ((0, plan.k_pad - kdim), (0, 0))),
                      n, plan.n_pad, n_branches)
    scale_p = _pad_cols(scale.reshape(-1), n, plan.n_pad, n_branches)
    w_dend_p = w_dend
    if w_dend is not None and plan.n_pad != n:
        w_dend_p = jnp.pad(w_dend, ((0, 0), (0, plan.n_pad - n)))
    rc = None
    if row_ctl is not None:
        rc = jnp.pad(row_ctl.reshape(-1, 3), ((0, plan.m_pad - m0), (0, 0)))
    outs = _fused.fused_macro_seq(
        xm, msb_p, lsb_p, boundaries, levels, scale_p, vm, nm, w_dend_p,
        activity, rc,
        mode=mode, k=k, ratio=ratio, drive_gain=drive_gain, beta=beta,
        v_th1=v_th1, v_th2=v_th2, v_reset=v_reset, v_lim=v_lim,
        use_snl=use_snl, bm=plan.bm, bk=plan.bk, bn=plan.bn,
        n_valid=plan.n_valid, ima_noise=ima_noise, snl_amp=snl_amp,
        logical_n=n, mac_telemetry=mac_telemetry, train_trace=train_trace,
        seed=seed, step_offset=step_offset, interpret=interpret_mode())
    mac, v_out, spikes, mask, steps = outs[:5]
    if mac is not None:
        mac = _unpad_cols(mac[:, :m0], n, plan.n_pad, n_branches)
        mac = mac.reshape(t, *lead, nc)
    ret = (mac,
           v_out[:m0, :n].reshape(*lead, n),
           spikes[:, :m0, :n].reshape(t, *lead, n),
           mask[:, :m0, :n].reshape(t, *lead, n),
           steps[:, :m0, 0].reshape(t, *lead))
    if train_trace:
        ret += (outs[5][:, :m0, :n].reshape(t, *lead, n),)
    return ret


class MultiSeqOut(NamedTuple):
    """Outputs of the stacked multi-layer fused sequence.

    ``spikes``/``mask`` are the FINAL layer's per-step stacks — the only
    spike tensors that exist in HBM.  Hidden-layer activity surfaces as
    telemetry instead: ``spike_counts`` (per-layer (T, ...) row-wise spike
    totals — what the SOP energy accounting needs from the inter-layer
    tensors that never leave the kernel) and ``occupancy`` (per-layer
    (T, row-tiles) occupied-K-tile counts from the in-kernel occupancy
    map; ``total_blocks`` is the denominator for the skipped-block ratio,
    summed over layers).
    """

    v_outs: tuple
    spikes: jax.Array
    mask: jax.Array
    steps: tuple
    spike_counts: tuple
    occupancy: tuple
    total_blocks: int


def fused_macro_multi_seq(x, stack, vs, noises=None, *, ks,
                          ratio: float = 2.0, drive_gain: float = 1.0,
                          beta: float = 0.9, v_th1: float = 1.0,
                          v_th2: float = 0.6, v_reset: float = 0.0,
                          v_lim: float = 8.0, use_snl: bool = True,
                          bm: int | None = None, tile_shapes=None,
                          ima_noise=None, snl_amp: float = 0.0,
                          gate: bool = True, seeds=None):
    """L stacked KWN macro layers, batched: x (T, ..., K0), one launch.

    stack:  per-layer (msb, lsb, boundaries, levels, scale) operand tuples
            (``core.macro.FusedMacroWeights`` fields; KWN mode only — the
            planes are (k_dim_l, n_l) with k_dim_l == n_{l-1} for l > 0).
    vs:     per-layer (..., n_l) initial membranes.
    noises: per-layer (T, ..., n_l) pre-drawn SNL noise (clean-path PRBS
            parity), or None for the in-kernel counter streams.
    ks:     per-layer KWN winner counts.
    tile_shapes: per-layer (bk, bn) in-kernel MAC tile sizes, or None for
            defaults (bk = min(k_dim, 256) aligned via the layer-0 tile
            planner, bn = min(n, 128)); this is the "tile plan" of the
            stacked kernel — ``bk`` doubles as the occupancy-gating
            granularity.
    seeds:  per-layer int32 counter seeds (distinct per layer so the
            per-layer noise streams never collide), or None for zeros.

    Only layer 0 is padded (rows to the row tile, K to the layer-0 K
    tiling, both sliced back off); inter-layer widths stay exact because
    the spike hand-off happens in registers inside the kernel.  Returns a
    ``MultiSeqOut``.
    """
    t = x.shape[0]
    lead = x.shape[1:-1]
    kdim = x.shape[-1]
    n_layers = len(stack)
    widths = [s[0].shape[-1] for s in stack]
    assert len(ks) == n_layers
    if tile_shapes is None:
        tile_shapes = [(None, None)] * n_layers
    xm = x.reshape(t, -1, kdim)
    m0 = xm.shape[1]
    plan0 = _fused.plan_tiles(m0, kdim, widths[0], widths[0], t,
                              bm=bm, bk=tile_shapes[0][0])
    specs = []
    for li in range(n_layers):
        k_dim = plan0.k_pad if li == 0 else widths[li - 1]
        bk_l, bn_l = tile_shapes[li]
        if li == 0:
            bk_l = plan0.bk               # matches the host activity map
        elif bk_l is None and bn_l is None:
            # deep layers reuse any tuned single-layer plan for their
            # shape, capped to the layer (LayerSpec allows ragged tails)
            cb = _fused.cached_plan_blocks(
                m0, k_dim, widths[li], widths[li], t, mode="kwn")
            if cb is not None:
                bk_l, bn_l = min(cb.bk, k_dim), min(cb.bn, widths[li])
        specs.append(_fused.LayerSpec(
            k_dim=k_dim, n=widths[li], k=int(ks[li]),
            bk=int(bk_l or min(k_dim, _fused.DEFAULT_BK)),
            bn=int(bn_l or min(widths[li], _fused.DEFAULT_BN))))
    specs = tuple(specs)
    budget = _fused.plan_vmem_budget()
    if budget is not None and _fused.stack_vmem_bytes(specs,
                                                      plan0.bm) > budget:
        # On a TPU the whole stack shares one launch's VMEM: halve the row
        # tile (the layer widths are fixed) until it fits.
        bm_fit = plan0.bm
        while bm_fit > 8 and _fused.stack_vmem_bytes(specs, bm_fit) > budget:
            bm_fit = max(8, bm_fit // 16 * 8)
        if _fused.stack_vmem_bytes(specs, bm_fit) > budget:
            raise ValueError(
                f"stack {widths} needs more than the {budget}-byte VMEM "
                f"limit even at 8 rows; it needs a layer-streaming schedule")
        plan0 = _fused.plan_tiles(m0, kdim, widths[0], widths[0], t,
                                  bm=bm_fit, bk=plan0.bk)
    xm = jnp.pad(xm, ((0, 0), (0, plan0.m_pad - m0),
                      (0, plan0.k_pad - kdim)))
    activity = fused_activity_map(xm, plan0) if gate else None
    vs_p = tuple(jnp.pad(v.reshape(-1, w), ((0, plan0.m_pad - m0), (0, 0)))
                 for v, w in zip(vs, widths))
    noises_p = None
    if noises is not None:
        noises_p = tuple(
            jnp.pad(nz.reshape(t, -1, w), ((0, 0), (0, plan0.m_pad - m0),
                                           (0, 0)))
            for nz, w in zip(noises, widths))
    if seeds is None:
        seeds = jnp.zeros((n_layers,), jnp.int32)
    ctl = jnp.asarray(seeds, jnp.int32).reshape(1, -1)
    planes = [tuple(s[:5]) for s in stack]
    if plan0.k_pad != kdim:              # zero K rows are MAC-neutral
        msb0, lsb0 = planes[0][0], planes[0][1]
        pad_k = ((0, plan0.k_pad - kdim), (0, 0))
        planes[0] = (jnp.pad(msb0, pad_k), jnp.pad(lsb0, pad_k),
                     *planes[0][2:])
    planes = tuple(planes)
    v_outs, spikes, mask, steps, counts, occ = _fused.fused_macro_multi_seq(
        xm, planes, vs_p, noises_p, activity, ctl,
        specs=specs, ratio=ratio, drive_gain=drive_gain, beta=beta,
        v_th1=v_th1, v_th2=v_th2, v_reset=v_reset, v_lim=v_lim,
        use_snl=use_snl, bm=plan0.bm, ima_noise=ima_noise, snl_amp=snl_amp,
        has_noise=noises is not None, gated=gate,
        interpret=interpret_mode())
    n_i = plan0.m_pad // plan0.bm
    return MultiSeqOut(
        v_outs=tuple(v[:m0].reshape(*lead, w)
                     for v, w in zip(v_outs, widths)),
        spikes=spikes[:, :m0].reshape(t, *lead, widths[-1]),
        mask=mask[:, :m0].reshape(t, *lead, widths[-1]),
        steps=tuple(s[:, :m0, 0].reshape(t, *lead) for s in steps),
        spike_counts=tuple(c[:, :m0, 0].reshape(t, *lead) for c in counts),
        occupancy=tuple(o[:, :, 0] for o in occ),
        total_blocks=t * n_i * sum(spec.n_k for spec in specs))


# ---------------------------------------------------------------------------
# Differentiable fused sequence: the silicon-in-the-loop training primitive
# ---------------------------------------------------------------------------

class SeqVJPSpec(NamedTuple):
    """Static (hashable) configuration of ``fused_macro_seq_vjp``.

    Mirrors the fused forward's static kwargs plus the surrogate-backward
    knobs: ``kwn_relax`` (loser gradient leak through the hard winner gate),
    ``surrogate_beta`` (SuperSpike sharpness), ``ste_lo``/``ste_hi`` (the
    IMA ramp's straight-through window, in the ramp's input units), and
    ``remat`` (recompute the MAC in the backward instead of saving the
    (T, M, NC) residual stack — bit-identical gradients, see
    ``fused_macro_grad``).  ``has_noise`` says whether the streamed noise
    operand is live (clean-path PRBS SNL) or a dummy (in-kernel counter
    noise / SNL off).
    """

    k: int = 12
    ratio: float = 2.0
    drive_gain: float = 1.0
    beta: float = 0.9
    v_th1: float = 1.0
    v_th2: float = 0.6
    v_reset: float = 0.0
    v_lim: float = 8.0
    use_snl: bool = True
    ima_noise: object = None          # ima.IMAKernelNoise | None (hashable)
    snl_amp: float = 0.0
    kwn_relax: float = 0.0
    surrogate_beta: float = 4.0
    ste_lo: float = -24.5
    ste_hi: float = 24.5
    remat: bool = False
    gate: bool = True
    has_noise: bool = False
    bm: int | None = None
    bk: int | None = None
    bn: int | None = None


def _seq_vjp_forward(spec: SeqVJPSpec, w, x, boundaries, levels, scale, v,
                     noise, seed_f):
    """Silicon-exact forward: quantize ``w`` onto the twin-cell planes and
    run the fused kernel with the training residual outputs enabled."""
    msb, lsb = _ternary.weight_decompose(w)
    seed = seed_f.astype(jnp.int32)
    noise_arr = noise if spec.has_noise else None
    mac, v_out, spikes, mask, _, vtrace = fused_macro_seq(
        x, _ternary.pack_ternary(msb), _ternary.pack_ternary(lsb),
        boundaries, levels, scale, v, noise_arr, None,
        mode="kwn", k=spec.k, ratio=spec.ratio, drive_gain=spec.drive_gain,
        beta=spec.beta, v_th1=spec.v_th1, v_th2=spec.v_th2,
        v_reset=spec.v_reset, v_lim=spec.v_lim, use_snl=spec.use_snl,
        bm=spec.bm, bk=spec.bk, bn=spec.bn, ima_noise=spec.ima_noise,
        snl_amp=spec.snl_amp, gate=spec.gate,
        mac_telemetry=not spec.remat, train_trace=True, seed=seed)
    return (spikes, v_out), (w, x, scale, mask, vtrace, mac, noise)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def fused_macro_seq_vjp(spec: SeqVJPSpec, w, x, boundaries, levels, scale,
                        v, noise, seed_f):
    """``fused_macro_seq`` with a surrogate backward (KWN mode).

    The forward is the silicon-exact fused kernel — clean or counter-PRNG
    noisy, activity-gated — bitwise-equal to ``ref.fused_macro_seq_ref``;
    the backward is the time-reversed BPTT Pallas kernel
    (``fused_macro_grad.fused_macro_seq_grad``), whose gradient semantics
    are pinned by ``jax.grad`` of ``ref.fused_macro_seq_vjp_ref``.

    w:      (K, N) f32 weight in *integer MAC units* (values on the twin-
            cell [-3, 3] grid; non-grid values are rounded in the primal
            and straight-through in the tangent — callers put their own
            ternary-STE clip at the model layer).
    x:      (T, ..., K) f32 ternary events (no gradient; zero cotangent).
    v:      (..., N) f32 initial membrane (dv0 is returned).
    noise:  (T, ..., N) f32 streamed SNL noise when ``spec.has_noise``,
            else a dummy array (any shape).
    seed_f: f32 scalar counter-PRNG seed (< 2^24; kept float so the
            cotangent machinery never meets an integer primal).

    Returns (spikes (T, ..., N), v_out (..., N)).
    """
    out, _ = _seq_vjp_forward(spec, w, x, boundaries, levels, scale, v,
                              noise, seed_f)
    return out


def _seq_vjp_fwd(spec, w, x, boundaries, levels, scale, v, noise, seed_f):
    out, res = _seq_vjp_forward(spec, w, x, boundaries, levels, scale, v,
                                noise, seed_f)
    return out, res + (boundaries, levels)


def _seq_vjp_bwd(spec, res, cts):
    w, x, scale, mask, vtrace, mac, noise, boundaries, levels = res
    g_spk, g_vout = cts
    t = x.shape[0]
    lead = x.shape[1:-1]
    kdim = x.shape[-1]
    n = vtrace.shape[-1]
    xm = x.reshape(t, -1, kdim)
    m0 = xm.shape[1]
    plan = _fused.plan_tiles(m0, kdim, n, n, t, mode="kwn",
                             bm=spec.bm, bk=spec.bk, bn=spec.bn)
    xm = jnp.pad(xm, ((0, 0), (0, plan.m_pad - m0),
                      (0, plan.k_pad - kdim)))
    pad_n = [(0, 0), (0, plan.m_pad - m0), (0, plan.n_pad - n)]
    stack = lambda a: jnp.pad(a.reshape(t, m0, n), pad_n)
    g_spk_p = stack(g_spk)
    vtrace_p = stack(vtrace)
    mask_p = stack(mask)
    g_vfin_p = jnp.pad(g_vout.reshape(m0, n), pad_n[1:])
    scale_p = jnp.pad(scale.reshape(-1), (0, plan.n_pad - n)).reshape(1, -1)
    activity = None
    if spec.gate:
        activity = fused_activity_map(xm, plan).any(axis=2).astype(jnp.int32)
    if spec.remat:
        msb, lsb = _ternary.weight_decompose(w)
        msb_p = jnp.pad(msb, ((0, plan.k_pad - kdim), (0, plan.n_pad - n)))
        lsb_p = jnp.pad(lsb, ((0, plan.k_pad - kdim), (0, plan.n_pad - n)))
        mac_p = None
    else:
        msb_p = lsb_p = None
        mac_p = stack(mac)
    dw_p, dv0_p = _fused_grad.fused_macro_seq_grad(
        xm, scale_p, g_spk_p, g_vfin_p, vtrace_p, mask_p, mac_p,
        None if msb_p is None else _ternary.pack_ternary(msb_p),
        None if lsb_p is None else _ternary.pack_ternary(lsb_p),
        activity,
        ratio=spec.ratio, drive_gain=spec.drive_gain, beta=spec.beta,
        v_th1=spec.v_th1, v_lim=spec.v_lim, kwn_relax=spec.kwn_relax,
        surrogate_beta=spec.surrogate_beta, ste_lo=spec.ste_lo,
        ste_hi=spec.ste_hi, bm=plan.bm, bn=plan.bn,
        interpret=interpret_mode())
    dw = dw_p[:kdim, :n]
    dv0 = dv0_p[:m0, :n].reshape(*lead, n)
    return (dw, jnp.zeros_like(x), jnp.zeros_like(boundaries),
            jnp.zeros_like(levels), jnp.zeros_like(scale), dv0,
            jnp.zeros_like(noise), jnp.zeros((), jnp.float32))


fused_macro_seq_vjp.defvjp(_seq_vjp_fwd, _seq_vjp_bwd)
