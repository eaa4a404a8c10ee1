"""Pure-jnp oracles for every Pallas kernel (the correctness references).

These are deliberately written against the *core* library semantics so kernel
tests check kernels against the same code the SNN models execute.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import ctrprng
from repro.core import ima as ima_lib
from repro.core import kwn as kwn_lib
from repro.core import ternary as ternary_lib


def _noise_ids(shape):
    """Global (row, column) counter words for a 2-D *unpadded* operand.

    The kernel's logical-column mapping collapses to the plain column index
    on unpadded layouts (KWN: branch 0 only; NLD branch-major: branch j of
    column p sits at ``j * n + p`` — exactly ``j * logical_n + p``), so the
    oracle's stream is the kernel's stream by construction.
    """
    rows = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    return rows, cols


def counter_snl_noise(shape, seed, step, amp: float) -> jax.Array:
    """The in-kernel SNL sign-noise stream (noisy-silicon path oracle)."""
    rows, cols = _noise_ids(shape)
    sign = ctrprng.counter_sign(seed, step, rows, cols, ctrprng.TAG_SNL)
    return jnp.float32(amp) * sign


def ternary_mac_ref(x: jax.Array, msb: jax.Array, lsb: jax.Array,
                    ratio: float = 2.0) -> jax.Array:
    """f32 GEMM against the decoded twin-cell weights."""
    w = ratio * msb.astype(jnp.float32) + lsb.astype(jnp.float32)
    return x.astype(jnp.float32) @ w


def kwn_topk_ref(mac: jax.Array, boundaries: jax.Array, k: int):
    """(mask, adc_steps) via the core ramp-scan semantics."""
    cb = ima_lib.RampCodebook(levels=jnp.zeros(boundaries.shape[0] + 1),
                              boundaries=boundaries,
                              in_lo=float(boundaries[0]),
                              in_hi=float(boundaries[-1]))
    res = kwn_lib.kwn_select(mac, k, cb)
    return res.mask, res.adc_steps[..., None].astype(jnp.int32)


def lif_step_ref(v, drive, mask, noise, beta=0.9, v_th1=1.0, v_th2=0.6,
                 v_reset=0.0, v_lim=8.0, use_snl=True):
    v_new = jnp.where(mask > 0, beta * v + drive, v)
    if use_snl:
        snl = (v_new > v_th2) & (v_new < v_th1)
        v_new = jnp.where(snl, v_new + noise, v_new)
    v_new = jnp.clip(v_new, -v_lim, v_lim)
    spike = (v_new >= v_th1).astype(jnp.float32)
    return jnp.where(spike > 0, v_reset, v_new), spike


def nlq_convert_ref(x, boundaries, levels):
    code = jnp.searchsorted(boundaries, x, side="left").astype(jnp.int32)
    # kernel uses strict '>' compare: match searchsorted side for exact ties
    code = jnp.sum(x[..., None] > boundaries, axis=-1).astype(jnp.int32)
    return code, jnp.take(levels, code)


def fused_head_ref(mac, boundaries, levels, scale, v, noise=None,
                   w_dend=None, *, mode: str = "kwn", k: int = 12,
                   drive_gain: float = 1.0, beta: float = 0.9,
                   v_th1: float = 1.0, v_th2: float = 0.6,
                   v_reset: float = 0.0, v_lim: float = 8.0,
                   use_snl: bool = True, ima_noise=None,
                   snl_amp: float = 0.0, seed=0, step=0):
    """The post-MAC stages of the fused step: IMA ramp conversion, mode head
    (KWN descending-ramp top-K / NLD branch activation + soma combine), and
    the LIF update.  Split out so tiled MAC oracles can reuse it verbatim.

    With ``ima_noise`` (an ``ima.IMAKernelNoise``), the Fig. 7 conversion
    error is injected through the *same* counter-PRNG function the kernel
    calls (``ctrprng.noisy_ima_codes``) keyed on ``(seed, step, row, col)``
    — the noisy oracle, bitwise-equal to the noisy kernel.  ``noise=None``
    selects the in-kernel SNL stream (``counter_snl_noise`` at ``snl_amp``)
    instead of a pre-drawn tensor; noisy mode requires 2-D operands (rows
    are counter words).
    """
    # in_lo/in_hi are only consumed by the noise model, not by
    # convert/reconstruct/select — keep the oracle jit-friendly.  (The
    # counter noise model carries its own range inside ``ima_noise``.)
    cb = ima_lib.RampCodebook(
        levels=jnp.asarray(levels, jnp.float32),
        boundaries=jnp.asarray(boundaries, jnp.float32),
        in_lo=0.0, in_hi=0.0)
    if ima_noise is not None:
        assert mac.ndim == 2, "noisy oracle needs (rows, cols) operands"
    if mode == "kwn":
        codes = ima_lib.ima_convert(mac, cb)
        if ima_noise is not None:
            rows, cols = _noise_ids(mac.shape)
            codes = ctrprng.noisy_ima_codes(codes, mac, rows, cols, seed,
                                            step, ima_noise, cb.n_codes)
            # Selection, early stop, and drive all read the *noisy* code —
            # rank on its reconstruction (convert∘reconstruct is identity
            # on codes, so kwn_select sees exactly the noisy ramp order).
            mac_rank = ima_lib.ima_reconstruct(codes, cb)
        else:
            mac_rank = mac
        res = kwn_lib.kwn_select(mac_rank, k, cb)
        mask, steps = res.mask, res.adc_steps[..., None]
        recon = ima_lib.ima_reconstruct(codes, cb)
        drive = recon * scale * mask * drive_gain
    elif mode == "nld":
        n_branches, n = w_dend.shape
        mac_f = mac * scale
        codes = ima_lib.ima_convert(mac_f, cb)
        if ima_noise is not None:
            rows, cols = _noise_ids(mac_f.shape)
            codes = ctrprng.noisy_ima_codes(codes, mac_f, rows, cols, seed,
                                            step, ima_noise, cb.n_codes)
        act = ima_lib.ima_reconstruct(codes, cb)
        act3 = act.reshape(act.shape[:-1] + (n_branches, n))
        # soma combine summed in branch order from zero, as the kernel does
        drive = jnp.zeros(act3.shape[:-2] + (n,), jnp.float32)
        for jb in range(n_branches):
            drive = drive + act3[..., jb, :] * w_dend[jb]
        drive = drive * drive_gain
        mask = jnp.ones(v.shape, jnp.float32)
        steps = jnp.full(v.shape[:-1] + (1,), cb.n_codes - 1, jnp.int32)
        use_snl = False
    else:
        raise ValueError(f"unknown mode {mode!r}")
    if noise is None:
        if use_snl and snl_amp != 0.0:
            noise = counter_snl_noise(v.shape, seed, step, snl_amp)
        else:
            noise = jnp.zeros(v.shape, jnp.float32)
    v_out, spikes = lif_step_ref(v, drive, mask, noise, beta=beta,
                                 v_th1=v_th1, v_th2=v_th2, v_reset=v_reset,
                                 v_lim=v_lim, use_snl=use_snl)
    return v_out, spikes, mask, steps


def fused_macro_step_ref(x, msb, lsb, boundaries, levels, scale, v,
                         noise=None, w_dend=None, *, mode: str = "kwn",
                         k: int = 12, ratio: float = 2.0,
                         drive_gain: float = 1.0, beta: float = 0.9,
                         v_th1: float = 1.0, v_th2: float = 0.6,
                         v_reset: float = 0.0, v_lim: float = 8.0,
                         use_snl: bool = True, ima_noise=None,
                         snl_amp: float = 0.0, seed=0, step=0):
    """Composed jnp oracle for the fused macro step (kernels/fused_macro.py).

    Same stage sequence — twin-cell MAC, IMA ramp conversion (optionally
    through the counter-PRNG Fig. 7 error model), mode head (KWN
    descending-ramp top-K / NLD branch activation + soma combine), LIF
    update — expressed through the core-library semantics, with every
    arithmetic step mirrored so the fused kernel matches *bitwise* at f32:
    the MAC partials are small integers (exact in f32, associativity-free),
    the head is compare/select/LUT arithmetic, and the noise draws come
    from the identical ``ctrprng`` counter functions.

    Returns (mac, v_out, spikes, mask, adc_steps) like the kernel, with
    adc_steps shaped (..., 1).
    """
    mac = ternary_mac_ref(x, msb, lsb, ratio=ratio)
    v_out, spikes, mask, steps = fused_head_ref(
        mac, boundaries, levels, scale, v, noise, w_dend, mode=mode, k=k,
        drive_gain=drive_gain, beta=beta, v_th1=v_th1, v_th2=v_th2,
        v_reset=v_reset, v_lim=v_lim, use_snl=use_snl, ima_noise=ima_noise,
        snl_amp=snl_amp, seed=seed, step=step)
    return mac, v_out, spikes, mask, steps


def tiled_ternary_mac_ref(x, msb, lsb, ratio: float = 2.0, *,
                          bk: int = 256, bn: int = 128) -> jax.Array:
    """Tiled-oracle MAC: explicit digital partial-sum accumulation.

    Computes the twin-cell GEMM the way the tiled kernel does — one
    ``(bk, bn)`` weight-plane tile per step, f32 partial sums added across
    the K tiles in order — to pin down that row/col tiling cannot move the
    result: every partial is a small exact integer, so f32 accumulation is
    associativity-free and any tiling equals the untiled ``ternary_mac_ref``
    bitwise.
    """
    kdim, nc = msb.shape
    w = ratio * msb.astype(jnp.float32) + lsb.astype(jnp.float32)
    xf = x.astype(jnp.float32)
    cols = []
    for j0 in range(0, nc, bn):
        acc = None
        for k0 in range(0, kdim, bk):
            part = xf[..., k0:k0 + bk] @ w[k0:k0 + bk, j0:j0 + bn]
            acc = part if acc is None else acc + part
        cols.append(acc)
    return jnp.concatenate(cols, axis=-1)


def fused_macro_tiled_ref(x, msb, lsb, boundaries, levels, scale, v,
                          noise=None, w_dend=None, *, bk: int = 256,
                          bn: int = 128, mode: str = "kwn", k: int = 12,
                          ratio: float = 2.0, drive_gain: float = 1.0,
                          beta: float = 0.9, v_th1: float = 1.0,
                          v_th2: float = 0.6, v_reset: float = 0.0,
                          v_lim: float = 8.0, use_snl: bool = True,
                          ima_noise=None, snl_amp: float = 0.0, seed=0,
                          step=0):
    """Tiled oracle: ``tiled_ternary_mac_ref`` + the shared fused head.

    Must equal ``fused_macro_step_ref`` bitwise for any (bk, bn) — the
    property suite sweeps tilings against it.  The noise streams are
    counter-indexed on global element coordinates, so they are tiling
    oblivious by construction (same kwargs as the step oracle).
    """
    mac = tiled_ternary_mac_ref(x, msb, lsb, ratio=ratio, bk=bk, bn=bn)
    v_out, spikes, mask, steps = fused_head_ref(
        mac, boundaries, levels, scale, v, noise, w_dend, mode=mode, k=k,
        drive_gain=drive_gain, beta=beta, v_th1=v_th1, v_th2=v_th2,
        v_reset=v_reset, v_lim=v_lim, use_snl=use_snl, ima_noise=ima_noise,
        snl_amp=snl_amp, seed=seed, step=step)
    return mac, v_out, spikes, mask, steps


def fused_macro_seq_ref(x, msb, lsb, boundaries, levels, scale, v,
                        noise=None, w_dend=None, *, mode: str = "kwn",
                        k: int = 12, ratio: float = 2.0,
                        drive_gain: float = 1.0, beta: float = 0.9,
                        v_th1: float = 1.0, v_th2: float = 0.6,
                        v_reset: float = 0.0, v_lim: float = 8.0,
                        use_snl: bool = True, ima_noise=None,
                        snl_amp: float = 0.0, seed=0, step_offset=0):
    """Time-major oracle: left-fold of ``fused_macro_step_ref`` over T.

    x (T, ..., K) time-major, v (..., N) initial membrane, noise
    (T, ..., N) pre-drawn per-step noise — or None for the counter-based
    in-kernel streams (IMA conversion error via ``ima_noise``, SNL sign
    noise at ``snl_amp``), in which case the per-step counter word is
    ``step_offset + t``.  Returns per-step stacks (mac (T, ..., NC),
    spikes, mask, adc_steps (T, ..., 1)) plus the final membrane (..., N)
    — exactly the contract of the time-major kernel.
    """
    def step(v_carry, inp):
        t, xt, nt = inp[0], inp[1], (inp[2] if noise is not None else None)
        mac, v_out, spikes, mask, steps = fused_macro_step_ref(
            xt, msb, lsb, boundaries, levels, scale, v_carry, nt, w_dend,
            mode=mode, k=k, ratio=ratio, drive_gain=drive_gain, beta=beta,
            v_th1=v_th1, v_th2=v_th2, v_reset=v_reset, v_lim=v_lim,
            use_snl=use_snl, ima_noise=ima_noise, snl_amp=snl_amp,
            seed=seed, step=step_offset + t)
        return v_out, (mac, spikes, mask, steps)

    t_ix = jnp.arange(x.shape[0], dtype=jnp.int32)
    xs = (t_ix, x) if noise is None else (t_ix, x, noise)
    v_fin, (mac_t, spk_t, mask_t, steps_t) = jax.lax.scan(step, v, xs)
    return mac_t, v_fin, spk_t, mask_t, steps_t


def fused_macro_multi_seq_ref(x, stack, vs, noises=None, *, ks, seeds=None,
                              ratio: float = 2.0, drive_gain: float = 1.0,
                              beta: float = 0.9, v_th1: float = 1.0,
                              v_th2: float = 0.6, v_reset: float = 0.0,
                              v_lim: float = 8.0, use_snl: bool = True,
                              ima_noise=None, snl_amp: float = 0.0,
                              step_offset=0):
    """Composed per-layer oracle for the stacked fused kernel (KWN only).

    Chains ``fused_macro_seq_ref`` layer by layer: layer l's full spike
    stack becomes layer l+1's input sequence.  This layer-major order is
    *exactly* the stacked kernel's step-major order, because layer l+1 at
    step t depends only on (its own membrane after step t-1, layer l's
    step-t spikes) — the two schedules compute identical dataflow DAGs, so
    the comparison is bitwise, not approximate.  KWN spikes are {0, 1},
    which is its own ternary encoding, so spike stacks feed the next
    layer's MAC unmodified.

    stack:  per-layer (msb, lsb, boundaries, levels, scale) tuples.
    vs:     per-layer initial membranes; ks: per-layer winner counts.
    seeds:  per-layer counter seeds (must match the kernel's per-layer
            ctl words); noises: per-layer pre-drawn SNL tensors or None
            for the counter streams.

    Returns (v_fins (per-layer), spikes (T, ..., n_L) — final layer,
    mask (T, ..., n_L), steps (per-layer (T, ..., 1)),
    spike_counts (per-layer (T, ...) row-wise |spike| totals)).
    """
    cur = x.astype(jnp.float32)
    v_fins, steps_list, cnt_list = [], [], []
    spk_t = mask_t = None
    for li, (msb, lsb, bounds, levels, scale) in enumerate(stack):
        _, v_fin, spk_t, mask_t, steps_t = fused_macro_seq_ref(
            cur, msb, lsb, bounds, levels, scale, vs[li],
            None if noises is None else noises[li],
            mode="kwn", k=ks[li], ratio=ratio, drive_gain=drive_gain,
            beta=beta, v_th1=v_th1, v_th2=v_th2, v_reset=v_reset,
            v_lim=v_lim, use_snl=use_snl, ima_noise=ima_noise,
            snl_amp=snl_amp, seed=0 if seeds is None else seeds[li],
            step_offset=step_offset)
        v_fins.append(v_fin)
        steps_list.append(steps_t)
        cnt_list.append(jnp.sum(jnp.abs(spk_t), axis=-1))
        cur = spk_t
    return v_fins, spk_t, mask_t, steps_list, cnt_list


# ---------------------------------------------------------------------------
# Differentiable oracle: the surrogate-backward reference (silicon training)
# ---------------------------------------------------------------------------
#
# ``fused_macro_seq_vjp_ref`` is the *gradient semantics* oracle for the
# silicon-in-the-loop training subsystem: a pure-JAX function whose primal
# outputs are bitwise-equal to ``fused_macro_seq_ref`` (and therefore to the
# fused Pallas kernel) and whose ``jax.grad`` defines the reference surrogate
# gradient the Pallas backward kernel (``kernels.fused_macro_grad``) must
# reproduce.  The surrogate chain, expressed through STE-identity terms
# (``primal_exact + (surrogate - stop_grad(surrogate))`` — exactly zero in
# the primal, the surrogate's derivative in the tangent):
#
#   * **ternary MAC**: the tangent of the integer-unit MAC is ``x @ w`` (the
#     caller's float weight, straight through the round-to-ternary);
#   * **IMA ramp + LUT**: straight-through inside the ramp's representable
#     range (``[ste_lo, ste_hi]`` = levels span +-0.5 LSB, the same
#     saturation window ``ima._ima_ste_bwd`` uses); the Fig. 7 noise draws
#     perturb the primal codes only — the tangent passes through the clean
#     analog MAC;
#   * **KWN winner mask**: a hard gate with a *relaxed* STE — winners pass
#     gradient at weight 1, losers leak it at ``kwn_relax`` (the gradient a
#     loser would have received had it won, scaled down; ``kwn_relax=0`` is
#     the pure hard gate);
#   * **LIF spike**: the SuperSpike fast-sigmoid surrogate at
#     ``surrogate_beta`` (the same ``core.lif.spike_fn`` derivative);
#   * **V_mem saturation**: gradient passes strictly inside the register
#     range (``|v_clip| < v_lim``), and is cut at the rails — defined here
#     (not via ``jnp.clip``, whose tie-splitting at an exact-rail membrane
#     has no silicon meaning);
#   * **SNL noise / reset**: additive noise and the reset branch selection
#     are gradient-transparent and gradient-opaque respectively, exactly as
#     in the software BPTT path.


def _ste(exact: jax.Array, surrogate: jax.Array) -> jax.Array:
    """Primal = ``exact`` (bitwise); tangent = the surrogate's."""
    return jax.lax.stop_gradient(exact) + (
        surrogate - jax.lax.stop_gradient(surrogate))


@jax.custom_vjp
def _spike_surrogate(v: jax.Array, v_th: jax.Array,
                     sbeta: jax.Array) -> jax.Array:
    return (v >= v_th).astype(jnp.float32)


def _spike_surrogate_fwd(v, v_th, sbeta):
    return _spike_surrogate(v, v_th, sbeta), (v, v_th, sbeta)


def _spike_surrogate_bwd(res, g):
    v, v_th, sbeta = res
    x = sbeta * (v - v_th)
    sg = sbeta / (1.0 + jnp.abs(x)) ** 2          # SuperSpike fast sigmoid
    return g * sg, jnp.zeros_like(v_th), jnp.zeros_like(sbeta)


_spike_surrogate.defvjp(_spike_surrogate_fwd, _spike_surrogate_bwd)


@jax.custom_vjp
def _sat_clip(v: jax.Array, lim: jax.Array) -> jax.Array:
    """V_mem register saturation with a hard gradient cut at the rails.

    ``jnp.clip`` splits the cotangent 50/50 when the membrane lands exactly
    on a rail (lax.min/max balanced-tie JVP); the register has no such
    half-gradient state, so the backward here passes iff strictly inside."""
    return jnp.clip(v, -lim, lim)


def _sat_clip_fwd(v, lim):
    out = _sat_clip(v, lim)
    return out, (out, lim)


def _sat_clip_bwd(res, g):
    v_clip, lim = res
    inside = (jnp.abs(v_clip) < lim).astype(g.dtype)
    return g * inside, jnp.zeros_like(lim)


_sat_clip.defvjp(_sat_clip_fwd, _sat_clip_bwd)


def fused_macro_seq_vjp_ref(w, x, boundaries, levels, scale, v,
                            noise=None, *, k: int = 12, ratio: float = 2.0,
                            drive_gain: float = 1.0, beta: float = 0.9,
                            v_th1: float = 1.0, v_th2: float = 0.6,
                            v_reset: float = 0.0, v_lim: float = 8.0,
                            use_snl: bool = True, ima_noise=None,
                            snl_amp: float = 0.0, seed=0, step_offset=0,
                            kwn_relax: float = 0.0,
                            surrogate_beta: float = 4.0,
                            ste_lo: float | None = None,
                            ste_hi: float | None = None):
    """Differentiable time-major oracle for the fused KWN sequence.

    ``w`` is the *float* weight in integer MAC units (the primal rounds it
    onto the twin-cell [-3, 3] grid exactly like the packers, so passing an
    already-integer ``w`` reproduces ``fused_macro_seq_ref(x, msb, lsb, ...)``
    bitwise); gradients flow to ``w`` and ``v`` through the surrogate chain
    documented above.  ``x`` is the (T, M, K) ternary input as f32 (events
    carry no gradient).  ``ste_lo``/``ste_hi`` bound the straight-through
    window of the IMA ramp (default: levels span +-0.5 LSB).

    Returns (v_fin, spikes (T, M, N), mask (T, M, N), adc_steps (T, M, 1),
    vtrace (T, M, N)) — the same per-step stacks the training forward saves,
    with vtrace the pre-reset saturated membrane.
    """
    sg = jax.lax.stop_gradient
    w_int = ternary_lib.weight_decompose(sg(w))
    w_exact = ternary_lib.weight_compose(*w_int, ratio=ratio)
    cb = ima_lib.RampCodebook(
        levels=jnp.asarray(levels, jnp.float32),
        boundaries=jnp.asarray(boundaries, jnp.float32),
        in_lo=0.0, in_hi=0.0)
    if ste_lo is None:
        ste_lo = float(jnp.min(cb.levels)) - 0.5
    if ste_hi is None:
        ste_hi = float(jnp.max(cb.levels)) + 0.5
    sbeta = jnp.float32(surrogate_beta)
    lim = jnp.float32(v_lim)

    def step(v_carry, inp):
        t, xt = inp[0], inp[1]
        nzt = inp[2] if noise is not None else None
        mac_e = xt @ w_exact                       # exact integer-unit MAC
        mac = _ste(mac_e, xt @ w)
        codes = ima_lib.ima_convert(sg(mac_e), cb)
        if ima_noise is not None:
            rows, cols = _noise_ids(mac_e.shape)
            codes = ctrprng.noisy_ima_codes(codes, sg(mac_e), rows, cols,
                                            seed, step_offset + t, ima_noise,
                                            cb.n_codes)
            mac_rank = ima_lib.ima_reconstruct(codes, cb)
        else:
            mac_rank = sg(mac_e)
        res = kwn_lib.kwn_select(mac_rank, k, cb)
        maskf, steps = sg(res.mask), res.adc_steps[..., None]
        recon = ima_lib.ima_reconstruct(codes, cb)
        drive_exact = recon * scale * maskf * drive_gain
        rng = sg(((mac_e >= ste_lo) & (mac_e <= ste_hi))
                 .astype(jnp.float32))             # ramp saturation window
        drive_sur = mac * sg(scale) * drive_gain * rng
        drive_w = _ste(drive_exact, drive_sur)
        if kwn_relax != 0.0:
            leak = kwn_relax * drive_sur
            v_lose = v_carry + (leak - sg(leak))   # exactly v in the primal
        else:
            v_lose = v_carry
        v2 = jnp.where(maskf > 0, beta * v_carry + drive_w, v_lose)
        if use_snl:
            if nzt is None:
                nz = counter_snl_noise(v2.shape, seed, step_offset + t,
                                       snl_amp)
            else:
                nz = nzt
            snl = (sg(v2) > v_th2) & (sg(v2) < v_th1)
            v2 = jnp.where(snl, v2 + sg(nz), v2)
        v_clip = _sat_clip(v2, lim)
        s = _spike_surrogate(v_clip, jnp.float32(v_th1), sbeta)
        v_next = jnp.where(sg(s) > 0, v_reset, v_clip)
        return v_next, (s, maskf, steps, v_clip)

    t_ix = jnp.arange(x.shape[0], dtype=jnp.int32)
    xs = (t_ix, x) if noise is None else (t_ix, x, noise)
    v_fin, (spk_t, mask_t, steps_t, vtrace_t) = jax.lax.scan(step, v, xs)
    return v_fin, spk_t, mask_t, steps_t, vtrace_t
