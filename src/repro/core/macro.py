"""CIM macro simulator: 256x128 MAC array + 46x128 NL-IMA (paper Fig. 2).

Combines the pieces: ternary inputs hit twin-cell MSB/LSB weight planes, the
analog MAC (with optional variation model) lands on the RBLs, the IMA converts
(linear, NLQ, or NL-activation ramp), and the mode logic (KWN / NLD) produces
the LIF drive.

Layers larger than the physical 256(rows) x 128(cols) array are tiled onto a
*virtual macro grid*: row tiles accumulate in the digital domain (partial-sum
adds after conversion are what the silicon would do across macro instances),
column tiles are independent.  ``MacroGeometry`` tracks how many physical
macro invocations a layer costs — the energy model consumes that.

Two execution paths:

  * **composed** (``cim_mac`` / ``kwn_forward`` / ``nld_forward``): each
    pipeline stage is a separate jnp/kernel call with HBM-visible
    intermediates — use it when you need those intermediates (codebook
    studies, training STE paths);
  * **fused** (``pack_kwn_weights``/``pack_nld_weights`` + ``fused_seq``):
    the whole MAC -> IMA -> mode-head -> LIF step runs
    inside one Pallas kernel (``repro.kernels.fused_macro``), the way the
    silicon never leaves the macro.  Layers wider than one 256x128 macro
    are tiled onto the virtual macro grid *inside* the kernel (column tiles
    + K tiles with digital partial-sum accumulation), and ``fused_seq``
    folds the whole event sequence into one launch with the LIF membrane
    carried in VMEM across time steps.  This is the inference hot path; it
    is bitwise-equal to the composed reference at f32 accumulation, and it
    carries the Fig. 7 IMA error model *inside* the kernel
    (``fused_kernel_noise`` + the counter PRNG in ``core.ctrprng``), so
    noisy silicon evaluation no longer leaves the fused path.
    ``plan_fused_tiles`` exposes the tile planner (padding, grid, VMEM
    footprint, macro-invocation count for the energy model).

    The fused path is also *activity-gated* by default: ``plan_activity``
    computes the per-(step, row-tile, K-tile) occupancy map of an event
    sequence (the host-side pass the KWN controller's row-activity logic
    performs in silicon), and the kernel skips the plane decode + MXU
    contraction for all-zero blocks and bounds the KWN ramp sweep to the
    occupied code range — bit-identical outputs, event-proportional work.
    Raw-MAC telemetry is opt-in on this path (``mac_telemetry``): serving
    never pays the (T, ..., NC) HBM stack.

Stacked-layer API (multi-layer fused networks)
----------------------------------------------
Deep KWN networks chain macro layers *on chip*: ``pack_kwn_stack`` packs a
list of per-layer integer weights into one ``FusedMacroWeights`` list, and
``fused_multi_seq`` runs the whole stack — every layer, every time step —
in a single Pallas launch (``kernels.fused_macro.fused_macro_multi_seq``).
Per-layer weight planes are layer-stationary (const-indexed, staged once
per launch), per-layer LIF membranes are carried in VMEM across the T
axis, and the inter-layer ternary spike tensor is a register value handed
from layer l's KWN head straight into layer l+1's MAC — it never touches
HBM.  Only the *final* layer's spike/mask stacks are materialized.

Because each KWN layer emits exactly K winners of N columns, layer l's
winner set IS layer l+1's activity plan: the stacked kernel computes the
multi-layer occupancy map *in kernel* (``jnp.any`` over each register-
resident K tile of the previous layer's spikes) instead of host-side —
only layer 0, whose events are host-visible, uses the scalar-prefetched
host map.  All-zero tiles skip the plane decode + MXU contraction exactly
like the single-layer gating (bitwise-neutral), and the per-layer
occupied-block counters leave the kernel as telemetry
(``MultiSeqOut.occupancy`` / ``total_blocks`` -> the serving
skipped-block ratio), so depth costs no HBM spike traffic even for the
energy accounting: hidden-layer SOP counts come from the per-step
row-wise ``spike_counts`` reduction, not from spike tensors.

``plan_fused_stack`` exposes the per-layer tile plans (layer 0 follows
the single-layer planner; deeper layers tile in kernel with ragged tails
— no column padding exists past layer 0).  The oracle is the composed
per-layer chain ``kernels.ref.fused_macro_multi_seq_ref`` — layer-major
and step-major schedules compute the same dataflow DAG, so parity is
bitwise, clean and noisy (per-layer counter seeds keep the noise streams
collision-free).  The stacked path is KWN-only; NLD stacks and the
multi-layer surrogate backward are roadmap follow-ups.
"""

from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import ima as ima_lib
from repro.core import kwn as kwn_lib
from repro.core import ternary as ternary_lib

MACRO_ROWS = 256   # MAC array word-lines (inputs)
MACRO_COLS = 128   # columns (neurons)
IMA_ROWS = 46      # ramp array rows


class MacroGeometry(NamedTuple):
    n_in: int
    n_out: int
    row_tiles: int
    col_tiles: int

    @property
    def n_macros(self) -> int:
        return self.row_tiles * self.col_tiles


def geometry(n_in: int, n_out: int) -> MacroGeometry:
    return MacroGeometry(
        n_in, n_out,
        row_tiles=math.ceil(n_in / MACRO_ROWS),
        col_tiles=math.ceil(n_out / MACRO_COLS),
    )


class CIMMacroConfig(NamedTuple):
    code_bits: int = 5                 # IMA resolution (5-bit over 8-bit range w/ NLQ)
    mac_range: float = 64.0            # full-scale analog MAC range (in weight LSBs)
    nlq_gamma: float = 2.0
    ratio_sigma: float = 0.0           # MC current-ratio spread (0 = ideal)
    ima_noise: ima_lib.IMANoiseModel | None = None  # None = ideal conversion


def _codebooks(cfg: CIMMacroConfig):
    lin = ima_lib.linear_codebook(cfg.code_bits, -cfg.mac_range, cfg.mac_range)
    nlq = ima_lib.nlq_codebook(cfg.code_bits, -cfg.mac_range, cfg.mac_range,
                               cfg.nlq_gamma)
    return lin, nlq


def cim_mac(spikes: jax.Array, w_int: jax.Array, cfg: CIMMacroConfig,
            key: jax.Array | None = None) -> jax.Array:
    """Analog ternary MAC: spikes (..., I) x int weights (I, N) in [-3, 3].

    The MSB/LSB twin-cell split and (optional) current-ratio variation are
    applied per column, exactly as the multi-VDD banks realize the weight.
    """
    msb, lsb = ternary_lib.weight_decompose(w_int)
    if cfg.ratio_sigma > 0.0 and key is not None:
        w_eff = ternary_lib.effective_weights(msb, lsb, key, cfg.ratio_sigma)
    else:
        w_eff = ternary_lib.weight_compose(msb, lsb)
    s = ternary_lib.ternary_input_encode(spikes)
    return jnp.einsum("...i,in->...n", s, w_eff)


def kwn_forward(spikes: jax.Array, w_int: jax.Array, k: int,
                cfg: CIMMacroConfig, key: jax.Array | None = None):
    """KWN mode: MAC -> NLQ ramp (descending) -> top-K early stop.

    Returns (drive, mask, result): drive is the LUT-mapped Z_j for winners and
    exactly 0 for the rest (what the LIF receives), result carries indices /
    codes / adc_steps for the latency model.
    """
    _, nlq = _codebooks(cfg)
    mac = cim_mac(spikes, w_int, cfg, key)
    if cfg.ima_noise is not None and key is not None:
        k_noise = jax.random.fold_in(key, 1)
        codes = ima_lib.ima_convert_noisy(mac, nlq, k_noise, cfg.ima_noise)
        mac_eff = ima_lib.ima_reconstruct(codes, nlq)
    else:
        mac_eff = mac
    res = kwn_lib.kwn_select(mac_eff, k, nlq)
    drive = ima_lib.ima_reconstruct(
        ima_lib.ima_convert(mac_eff, nlq), nlq) * res.mask
    return drive, res.mask, res


def nld_forward(spikes: jax.Array, dendrite_params, cfg: CIMMacroConfig,
                activation: str = "quadratic", quantize: bool = True):
    """NLD mode: branch MACs through the NL-activation ramp, soma combine."""
    f = ima_lib.DENDRITE_ACTIVATIONS[activation]
    cb = ima_lib.activation_codebook(cfg.code_bits, f, -cfg.mac_range,
                                     cfg.mac_range)
    from repro.core import dendrite as dendrite_lib
    return dendrite_lib.dendrite_mac(
        dendrite_params, spikes, f=f, nl_cb=cb, quantize=quantize)


# ---------------------------------------------------------------------------
# Fused macro-step path (single Pallas kernel per time step)
# ---------------------------------------------------------------------------

class FusedMacroWeights(NamedTuple):
    """Device-ready operands for the fused macro-step kernel.

    msb/lsb:     (I, NC) int8 twin-cell planes (NC = N for KWN, J*N branch-
                 major for NLD).
    scale:       (NC,) per-column weight quantization scale.
    boundaries:  (n_codes-1,) ramp thresholds.
    levels:      (n_codes,) LUT / activation samples.
    w_dend:      (J, N) soma combine weights, or None in KWN mode.
    mode:        "kwn" | "nld".
    """

    msb: jax.Array
    lsb: jax.Array
    scale: jax.Array
    boundaries: jax.Array
    levels: jax.Array
    w_dend: jax.Array | None
    mode: str


def pack_kwn_weights(w_int: jax.Array, scale: jax.Array,
                     cfg: CIMMacroConfig) -> FusedMacroWeights:
    """KWN-mode packing: int weights in [-3, 3] + per-column scale.

    The NLQ ramp operates in integer MAC units (``cfg.mac_range``); the
    per-column float scale is applied to the winner drive after the LUT
    map-back, exactly like ``kwn_forward`` + the SNN silicon path.
    """
    msb, lsb = ternary_lib.weight_decompose(w_int)
    _, nlq = _codebooks(cfg)
    return FusedMacroWeights(
        msb=ternary_lib.pack_ternary(msb), lsb=ternary_lib.pack_ternary(lsb),
        scale=scale.reshape(-1).astype(jnp.float32),
        boundaries=nlq.boundaries, levels=nlq.levels, w_dend=None, mode="kwn")


def pack_nld_weights(dendrite_params, cfg: CIMMacroConfig,
                     activation: str = "quadratic") -> FusedMacroWeights:
    """NLD-mode packing: branch weights onto the twin-cell grid.

    The fused NLD path stores the branch synapses the way the silicon does —
    as 3-bit twin-cell ternary pairs with a per-(branch, column) scale — so
    branch MACs accumulate in integer units and are rescaled to float units
    just before the NL-activation ramp.  (The composed ``nld_forward`` keeps
    float weights; the fused path is the more silicon-faithful of the two.)
    Column packing is branch-major: column j*N + p is branch j of neuron p.
    """
    w_syn = dendrite_params.w_syn * dendrite_params.mask   # (J, I, N)
    n_branches, n_in, n_out = w_syn.shape
    scale = jnp.maximum(jnp.max(jnp.abs(w_syn), axis=1) / 3.0, 1e-8)  # (J, N)
    w_int = jnp.round(jnp.clip(w_syn / scale[:, None, :], -3, 3))
    msb, lsb = ternary_lib.weight_decompose(w_int)
    # (J, I, NC) -> (I, J*N) branch-major flat columns
    flat = lambda t: jnp.transpose(t, (1, 0, 2)).reshape(n_in,
                                                         n_branches * n_out)
    f = ima_lib.DENDRITE_ACTIVATIONS[activation]
    cb = ima_lib.activation_codebook(cfg.code_bits, f, -cfg.mac_range,
                                     cfg.mac_range)
    return FusedMacroWeights(
        msb=ternary_lib.pack_ternary(flat(msb)),
        lsb=ternary_lib.pack_ternary(flat(lsb)),
        scale=scale.reshape(-1).astype(jnp.float32),
        boundaries=cb.boundaries, levels=cb.levels,
        w_dend=dendrite_params.w_dend, mode="nld")


def plan_fused_tiles(batch: int, fw: FusedMacroWeights, n_out: int,
                     n_steps: int = 1):
    """Tile plan + macro accounting for one fused launch.

    Parameters
    ----------
    batch : flattened batch rows the launch will carry (the leading dims
        of the event tensor collapsed to one axis).
    fw : the packed ``FusedMacroWeights`` — supplies the layer geometry
        (n_in x nc weight planes) and the mode (kwn/nld).
    n_out : per-neuron output width (== nc in KWN mode; nc / n_branches
        in NLD mode).
    n_steps : time steps folded into the kernel grid (1 = single step).

    Returns (plan, geometry): the kernel-facing ``TilePlan`` (block sizes,
    padded shapes, grid, resident VMEM bytes) and the ``MacroGeometry`` the
    energy model consumes (physical macro invocations for the layer).

    Delegates to ``kernels.fused_macro.plan_tiles`` with no overrides, so
    tuned plans from the persistent cache (``docs/TILE_PLANS.md``) apply
    transparently; with no cache entry this is the PR 4 heuristic.  Every
    caller that pairs a plan with a separately built activity map must
    plan through here (or through ``plan_activity``, which does) so both
    sides resolve the same cache entry.
    """
    from repro.kernels import fused_macro as fused_kernel
    n_in, nc = fw.msb.shape
    n_branches = nc // n_out if fw.mode == "nld" else 1
    plan = fused_kernel.plan_tiles(batch, n_in, nc, n_out, n_steps,
                                   mode=fw.mode, n_branches=n_branches)
    return plan, geometry(n_in, nc)


def plan_activity(spikes: jax.Array, fw: FusedMacroWeights,
                  n_out: int) -> jax.Array:
    """Occupancy map for a time-major event sequence: the activity plan.

    Parameters
    ----------
    spikes : (T, ..., I) event tensor in {-1, 0, +1}.
    fw : the packed ``FusedMacroWeights`` for the layer the events drive.
    n_out : per-neuron output width (as in ``plan_fused_tiles``).

    Returns the (T, row-tiles, K-tiles) int32 map (1 = the block holds at
    least one event) matching the tile plan ``plan_fused_tiles`` would
    pick for this launch — the same map ``fused_seq`` computes internally
    when none is passed.  Built once per sequence; ``1 - map.mean()`` is
    the skipped-block ratio the serving telemetry reports next to the KWN
    early-stop statistics.

    The map's row-tile/K-tile granularity IS the plan's (bm, bk): both
    sides plan through ``plan_tiles`` with identical arguments (and no
    density refinement), so a tuned cache entry (``docs/TILE_PLANS.md``)
    moves the map and the kernel grid together.  Handing this map to a
    launch planned with *different* block overrides is a shape error by
    construction — pass no overrides, or none of the map.
    """
    from repro.kernels import ops as kernel_ops
    s = ternary_lib.ternary_input_encode(spikes)
    t = s.shape[0]
    xm = s.reshape(t, -1, s.shape[-1])
    plan, _ = plan_fused_tiles(xm.shape[1], fw, n_out, n_steps=t)
    xm = jnp.pad(xm, ((0, 0), (0, plan.m_pad - xm.shape[1]),
                      (0, plan.k_pad - xm.shape[-1])))
    return kernel_ops.fused_activity_map(xm, plan)


def fused_kernel_noise(fw: FusedMacroWeights,
                       cfg: CIMMacroConfig) -> "ima_lib.IMAKernelNoise | None":
    """The kernel-consumable Fig. 7 noise struct for a packed weight set.

    Binds ``cfg.ima_noise`` to the full-scale range of the ramp the packed
    weights actually sweep (integer MAC units in KWN mode, float units in
    NLD mode — both are ``±cfg.mac_range`` by construction of the packers).
    Returns None when the config is ideal, so callers can pass the result
    straight to ``fused_seq``.
    """
    if cfg.ima_noise is None:
        return None
    cb = ima_lib.RampCodebook(fw.levels, fw.boundaries,
                              -cfg.mac_range, cfg.mac_range)
    return ima_lib.kernel_noise_params(cfg.ima_noise, cb)


def stream_row_ctl(seeds: jax.Array, step_offsets: jax.Array,
                   row_ids: jax.Array | None = None) -> jax.Array:
    """Per-slot noise-stream control lane for resumable serving.

    Builds the ``(S, 3) = [seed, step_offset, row_id]`` int32 tensor the
    fused kernel's ``row_ctl`` path consumes: slot ``s`` replays the
    counter-PRNG stream of an independent batch-1 run keyed on its own
    request ``seeds[s]``, positioned at absolute stream step
    ``step_offsets[s]``.  ``row_ids`` defaults to all-zero — every slot
    claims batch row 0 of its virtual batch-1 run, which is precisely what
    makes slot state *relocatable*: a checkpointed slot can be restored
    into ANY free slot (``snn.silicon_stream_restore``) and the replayed
    stream is unchanged, because nothing in the noise keying ever sees the
    physical slot index.
    """
    seeds = jnp.asarray(seeds, jnp.int32)
    rows = (jnp.zeros_like(seeds) if row_ids is None
            else jnp.asarray(row_ids, jnp.int32))
    return jnp.stack(
        [seeds, jnp.asarray(step_offsets, jnp.int32), rows], axis=-1)


def fused_seq(spikes: jax.Array, fw: FusedMacroWeights, v: jax.Array,
              noise: jax.Array | None = None, *, k: int = 12,
              drive_gain: float = 1.0, beta: float = 0.9,
              v_th1: float = 1.0, v_th2: float = 0.6,
              v_reset: float = 0.0, v_lim: float = 8.0,
              use_snl: bool = True, ima_noise=None, snl_amp: float = 0.0,
              gate: bool = True, activity: jax.Array | None = None,
              mac_telemetry: bool = True, seed=0,
              row_ctl: jax.Array | None = None):
    """A whole fused event sequence: spikes (T, ..., I), v (..., N),
    noise (T, ..., N) — or None for the in-kernel counter noise streams
    (this is the noisy-silicon serving path, with no pre-drawn noise tensor
    and no composed-path fallback).  ``ima_noise`` (``ima.IMAKernelNoise``,
    see ``fused_kernel_noise``) enables the in-kernel Fig. 7
    conversion-error model; with ``noise=None`` the SNL stream is generated
    in-kernel too (counter PRNG at ``snl_amp``), keyed on
    ``(seed, t)``.

    ``row_ctl`` ((..., 3) int32, batch lead dims) carries per-row
    ``[seed, step_offset, row_id]`` noise-stream control for the
    continuous-batching engine — each slot replays the counter stream of
    an independent batch-1 run (see ``kernels.ops.fused_macro_seq``).

    One kernel launch covers all T time steps (time-major grid axis, LIF
    membrane carried in VMEM) and any virtual-macro tiling the layer needs.
    ``gate`` selects activity-gated execution (default; pass the
    ``plan_activity`` map as ``activity`` to build the plan once per
    sequence and reuse it for telemetry); ``mac_telemetry=False`` skips
    the raw-MAC HBM stack (mac is None).
    Returns (v_out (..., N), spikes_out (T, ..., N), mask (T, ..., N),
    adc_steps (T, ...), mac (T, ..., NC) or None).
    """
    from repro.kernels import ops as kernel_ops
    s = ternary_lib.ternary_input_encode(spikes)
    mac, v_out, spk, mask, steps = kernel_ops.fused_macro_seq(
        s, fw.msb, fw.lsb, fw.boundaries, fw.levels, fw.scale, v, noise,
        fw.w_dend, mode=fw.mode, k=k, drive_gain=drive_gain, beta=beta,
        v_th1=v_th1, v_th2=v_th2, v_reset=v_reset, v_lim=v_lim,
        use_snl=use_snl, ima_noise=ima_noise, snl_amp=snl_amp, gate=gate,
        activity=activity, mac_telemetry=mac_telemetry, seed=seed,
        row_ctl=row_ctl)
    return v_out, spk, mask, steps, mac


def pack_kwn_stack(w_ints, scales, cfg: CIMMacroConfig):
    """Pack a KWN layer stack: per-layer int weights -> fused operands.

    ``w_ints``/``scales`` are parallel per-layer lists ((I_l, N_l) integer
    weights in [-3, 3] with their per-column scales; I_l must equal
    N_{l-1} for l > 0 — the layers chain).  All layers share the macro
    config (one NLQ ramp codebook); only widths differ.  Returns the
    ``FusedMacroWeights`` list ``fused_multi_seq`` consumes.
    """
    stack = [pack_kwn_weights(w, s, cfg) for w, s in zip(w_ints, scales)]
    for prev, nxt in zip(stack, stack[1:]):
        assert nxt.msb.shape[0] == prev.msb.shape[1], \
            (nxt.msb.shape, prev.msb.shape)
    return stack


def plan_fused_stack(batch: int, stack, n_steps: int = 1):
    """Per-layer (TilePlan, MacroGeometry) for a stacked fused launch.

    Parameters
    ----------
    batch : flattened batch rows (shared by every layer of the stack).
    stack : a ``pack_kwn_stack`` result — per-layer packed weights whose
        widths chain (layer l's n_in == layer l-1's n_out).
    n_steps : time steps folded into the one stacked launch.

    Returns a list of ``(TilePlan, MacroGeometry)`` pairs, one per layer,
    via ``plan_fused_tiles`` (so tuned cache entries apply per layer —
    see ``docs/TILE_PLANS.md``).  Layer 0's plan is authoritative for the
    launch (row tiling + the host activity-map granularity); deeper
    layers' plans describe the in-kernel MAC tiling and the per-layer
    macro-invocation count the energy model charges.  Column padding in
    deep plans is advisory only — the stacked kernel keeps inter-layer
    widths exact (spikes never leave registers).
    """
    return [plan_fused_tiles(batch, fw, fw.msb.shape[1], n_steps)
            for fw in stack]


def fused_multi_seq(spikes: jax.Array, stack, vs, noises=None, *, ks,
                    drive_gain: float = 1.0, beta: float = 0.9,
                    v_th1: float = 1.0, v_th2: float = 0.6,
                    v_reset: float = 0.0, v_lim: float = 8.0,
                    use_snl: bool = True, ima_noise=None,
                    snl_amp: float = 0.0, gate: bool = True,
                    tile_shapes=None, seeds=None):
    """A whole event sequence through L stacked KWN macro layers, fused.

    spikes (T, ..., I), stack a ``pack_kwn_stack`` result, vs/noises
    per-layer membranes / pre-drawn SNL tensors (noises=None selects the
    in-kernel counter streams), ks the per-layer winner counts, seeds the
    per-layer counter seeds (keep them distinct — the oracle chain uses
    the same ones).  One Pallas launch covers every layer and every time
    step; the inter-layer spike tensors never reach HBM (see the module
    docstring).  Returns ``kernels.ops.MultiSeqOut``.
    """
    from repro.kernels import ops as kernel_ops
    for fw in stack:
        assert fw.mode == "kwn", "the stacked fused path is KWN-only"
    s = ternary_lib.ternary_input_encode(spikes)
    return kernel_ops.fused_macro_multi_seq(
        s, [(fw.msb, fw.lsb, fw.boundaries, fw.levels, fw.scale)
            for fw in stack],
        vs, noises, ks=ks, drive_gain=drive_gain, beta=beta, v_th1=v_th1,
        v_th2=v_th2, v_reset=v_reset, v_lim=v_lim, use_snl=use_snl,
        ima_noise=ima_noise, snl_amp=snl_amp, gate=gate,
        tile_shapes=tile_shapes, seeds=seeds)


def fused_seq_vjp(spikes: jax.Array, w: jax.Array, scale: jax.Array,
                  cfg: CIMMacroConfig, v: jax.Array, *, k: int = 12,
                  drive_gain: float = 1.0, beta: float = 0.9,
                  v_th1: float = 1.0, v_th2: float = 0.6,
                  v_reset: float = 0.0, v_lim: float = 8.0,
                  use_snl: bool = True, noise: jax.Array | None = None,
                  snl_amp: float = 0.0, kwn_relax: float = 0.0,
                  surrogate_beta: float = 4.0, remat: bool = False,
                  gate: bool = True, seed=0.0):
    """Differentiable fused KWN sequence: the silicon-in-the-loop training
    forward, with the surrogate backward running as a Pallas kernel.

    spikes: (T, ..., I) event stream in {-1, 0, +1} (no gradient).
    w:      (I, N) f32 weight in *integer MAC units* — gradients flow to it
            straight through the twin-cell rounding; callers apply their
            own ternary-STE clip at the model layer (``repro.train.silicon``
            does, mirroring ``ternary.quantize_weights_ste``).
    scale:  (N,) per-column weight scale (stop-gradient semantics — the
            tangent treats it as a constant, like the software QAT path).
    cfg:    the macro config; ``cfg.ima_noise`` turns on the in-kernel
            Fig. 7 error model (noise-aware QAT) keyed on ``seed`` — pass a
            fresh ``seed`` per optimization step so every step sees a fresh
            silicon-noise draw.
    noise:  (T, ..., N) pre-drawn SNL noise for the *clean* path (PRBS
            parity with serving); None selects the in-kernel counter SNL
            stream at ``snl_amp`` on the noisy path (or no noise at all
            when ``use_snl`` is off).
    kwn_relax / surrogate_beta / remat: surrogate-backward knobs — loser
            gradient leak through the hard winner gate, SuperSpike
            sharpness, and the MAC residual-vs-recompute memory policy
            (see ``kernels.fused_macro_grad``).
    seed:   f32 scalar (traced) keying both counter noise streams.

    Returns (spikes_out (T, ..., N), v_out (..., N)), both differentiable.
    """
    from repro.kernels import ops as kernel_ops
    _, nlq = _codebooks(cfg)
    ima_kn = None
    if cfg.ima_noise is not None:
        ima_kn = ima_lib.kernel_noise_params(cfg.ima_noise, nlq)
    spec = kernel_ops.SeqVJPSpec(
        k=k, drive_gain=drive_gain, beta=beta, v_th1=v_th1, v_th2=v_th2,
        v_reset=v_reset, v_lim=v_lim, use_snl=use_snl, ima_noise=ima_kn,
        snl_amp=snl_amp, kwn_relax=kwn_relax, surrogate_beta=surrogate_beta,
        ste_lo=float(-cfg.mac_range - 0.5), ste_hi=float(cfg.mac_range + 0.5),
        remat=remat, gate=gate, has_noise=noise is not None)
    s = ternary_lib.ternary_input_encode(spikes)
    noise_arr = jnp.zeros((1,), jnp.float32) if noise is None else noise
    return kernel_ops.fused_macro_seq_vjp(
        spec, w, s, nlq.boundaries, nlq.levels,
        scale.reshape(-1).astype(jnp.float32), v, noise_arr,
        jnp.asarray(seed, jnp.float32))


def tiled_cim_mac(spikes: jax.Array, w_int: jax.Array,
                  cfg: CIMMacroConfig) -> tuple[jax.Array, MacroGeometry]:
    """Large-layer path: tile (I, N) onto the 256x128 macro grid.

    Row-tile partial sums are converted per tile then digitally accumulated —
    this loses precision exactly like the silicon does, so we model it: each
    row tile's analog MAC is IMA-quantized before the add.
    """
    n_in, n_out = w_int.shape
    geo = geometry(n_in, n_out)
    lin, _ = _codebooks(cfg)
    pad_i = geo.row_tiles * MACRO_ROWS - n_in
    pad_n = geo.col_tiles * MACRO_COLS - n_out
    s = jnp.pad(spikes, [(0, 0)] * (spikes.ndim - 1) + [(0, pad_i)])
    w = jnp.pad(w_int, [(0, pad_i), (0, pad_n)])
    s_t = s.reshape(s.shape[:-1] + (geo.row_tiles, MACRO_ROWS))
    w_t = w.reshape(geo.row_tiles, MACRO_ROWS, geo.col_tiles * MACRO_COLS)
    msb, lsb = ternary_lib.weight_decompose(w_t)
    w_eff = ternary_lib.weight_compose(msb, lsb)
    partial = jnp.einsum("...tr,trn->...tn", s_t, w_eff)
    partial_q = ima_lib.ima_quantize(partial, lin)
    out = jnp.sum(partial_q, axis=-2)
    return out[..., :n_out], geo
