"""The paper's SNNs: event input -> CIM hidden layer (KWN or NLD mode) ->
LIF -> spike-count readout, with surrogate-gradient training (BPTT through
lax.scan) and quantization-aware training for the twin-cell weight grid and
the NLQ ramp.

Inference runs through the macro simulator with the silicon noise models, so
the accuracy benchmarks (Figs. 5b / 6c / 8) exercise the same mechanisms the
chip measures: KWN top-K sparse V_mem updates + SNL/PRBS rescue + NLQ LUT,
vs NLD dendritic nonlinearities.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import dendrite as dendrite_lib
from repro.core import ima as ima_lib
from repro.core import kwn as kwn_lib
from repro.core import lif as lif_lib
from repro.core import macro as macro_lib
from repro.core import prbs as prbs_lib
from repro.core import ternary as ternary_lib
from repro.obs import trace as obs_trace


@dataclasses.dataclass(frozen=True)
class SNNConfig:
    n_in: int
    n_hidden: int = 128           # the macro's 128 columns
    n_classes: int = 10
    n_steps: int = 20
    mode: str = "kwn"             # kwn | nld
    k: int = 12                   # KWN winners
    n_branches: int = 2           # NLD dendritic branches
    activation: str = "quadratic" # NLD activation f()
    code_bits: int = 5
    mac_range: float = 24.0      # NLQ full scale, in *integer MAC* units
    dend_range: float = 4.0      # NLD branch-MAC full scale (float units)
    drive_gain: float = 0.25     # V_mem LSBs per unit drive
    beta: float = 0.9
    v_th1: float = 1.0
    v_th2: float = 0.6
    noise_amp: float = 0.05
    use_snl: bool = True
    train_nlq: bool = True        # NLQ-aware training (Fig. 6c)
    weight_qat: bool = True       # twin-cell 3-bit QAT
    # Layer stack (multi-layer fused networks, KWN only).  None keeps the
    # single-layer network the paper measures; a tuple of widths chains L
    # macro layers (n_hidden is forced to the last width — the readout
    # reads the final layer).  k_layers optionally sets per-layer winner
    # counts (default: cfg.k for every layer).  The config stays hashable
    # (jit-static), so the fields are coerced to tuples.
    hidden_layers: tuple[int, ...] | None = None
    k_layers: tuple[int, ...] | None = None

    def __post_init__(self):
        if self.hidden_layers is not None:
            hl = tuple(int(h) for h in self.hidden_layers)
            if not hl:
                raise ValueError("hidden_layers must be a non-empty tuple")
            if self.mode == "nld" and len(hl) > 1:
                raise ValueError("multi-layer stacks are KWN-only; the NLD "
                                 "stack is a roadmap follow-up")
            object.__setattr__(self, "hidden_layers", hl)
            object.__setattr__(self, "n_hidden", hl[-1])
        if self.k_layers is not None:
            kl = tuple(int(x) for x in self.k_layers)
            if len(kl) != len(self.layer_widths):
                raise ValueError(f"k_layers has {len(kl)} entries for "
                                 f"{len(self.layer_widths)} layers")
            object.__setattr__(self, "k_layers", kl)

    @property
    def layer_widths(self) -> tuple:
        """Hidden-layer widths, last one feeding the readout."""
        return self.hidden_layers or (self.n_hidden,)

    @property
    def layer_k(self) -> tuple:
        """Per-layer KWN winner counts."""
        return self.k_layers or (self.k,) * len(self.layer_widths)


def init_params(cfg: SNNConfig, key: jax.Array) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    p: dict[str, Any] = {
        "w_out": jax.random.normal(k3, (cfg.n_hidden, cfg.n_classes))
        / jnp.sqrt(cfg.n_hidden),
    }
    widths = cfg.layer_widths
    if cfg.mode == "nld":
        p["dend"] = dendrite_lib.dendrite_init(k1, cfg.n_in, cfg.n_hidden,
                                               cfg.n_branches)
    elif len(widths) == 1:
        # single layer: the historical RNG stream (cached models depend
        # on it byte-for-byte), w_hid a bare array
        p["w_hid"] = jax.random.normal(k1, (cfg.n_in, cfg.n_hidden)) \
            / jnp.sqrt(cfg.n_in) * 3.0
    else:
        fan_ins = (cfg.n_in,) + widths[:-1]
        keys = jax.random.split(k1, len(widths))
        p["w_hid"] = [
            jax.random.normal(kk, (f_in, w)) / jnp.sqrt(f_in) * 3.0
            for kk, f_in, w in zip(keys, fan_ins, widths)]
    return p


def _nlq_cb(cfg: SNNConfig):
    return ima_lib.nlq_codebook(cfg.code_bits, -cfg.mac_range, cfg.mac_range)


def _act_cb(cfg: SNNConfig):
    f = ima_lib.DENDRITE_ACTIVATIONS[cfg.activation]
    return ima_lib.activation_codebook(cfg.code_bits, f, -cfg.dend_range,
                                       cfg.dend_range)


def _hidden_drive_train(p, spikes, cfg: SNNConfig):
    """Differentiable (QAT/STE) hidden-layer drive for one time step.

    The NLQ ramp digitizes the *integer* MAC (twin-cell units), so the float
    MAC is divided by the per-column quantization scale before the STE ramp
    and multiplied back after — the exact silicon dataflow."""
    if cfg.mode == "nld":
        f = ima_lib.DENDRITE_ACTIVATIONS[cfg.activation]
        if cfg.train_nlq:
            return dendrite_lib.dendrite_mac(p["dend"], spikes, f=f,
                                             nl_cb=_act_cb(cfg), quantize=True)
        return dendrite_lib.dendrite_mac(p["dend"], spikes, f=f)
    return _kwn_drive_train(p["w_hid"], spikes, cfg)


def _kwn_drive_train(w_full, spikes, cfg: SNNConfig):
    """One KWN layer's QAT/STE MAC drive, for any layer of a stack."""
    w = w_full
    if cfg.weight_qat:
        w = ternary_lib.quantize_weights_ste(w)
    mac = spikes @ w
    if cfg.train_nlq:
        scale = jax.lax.stop_gradient(
            ternary_lib.quantize_weights_3bit(w_full)[1][0])  # (N,)
        mac = ima_lib.ima_quantize_ste(mac / scale, _nlq_cb(cfg)) * scale
    return mac


def forward_train(p, events, cfg: SNNConfig):
    """BPTT forward: events (B, T, N_in) -> logits (B, classes).

    Training uses dense LIF updates (top-K masking is applied at inference;
    training with the dense objective + QAT is how the silicon was trained).
    With a ``cfg.hidden_layers`` stack, each step chains the layer drives
    spike->MAC->LIF->spike; the readout reads the last layer's counts.
    Spike counts are normalized by the *actual* sequence length
    ``events.shape[1]`` (not ``cfg.n_steps``), so logits are invariant to
    the configured step count when callers pass shorter/longer sequences."""
    b, t_steps = events.shape[0], events.shape[1]
    widths = cfg.layer_widths
    multi = cfg.mode != "nld" and len(widths) > 1

    def step(carry, ev):
        vs, spk_acc = carry
        if not multi:
            drive = _hidden_drive_train(p, ev, cfg) * cfg.drive_gain
            v = cfg.beta * vs[0] + drive
            s = lif_lib.spike_fn(v, jnp.asarray(cfg.v_th1))
            v = jnp.where(s > 0, 0.0, v)
            return ((v,), spk_acc + s), None
        cur, new_vs = ev, []
        for li in range(len(widths)):
            drive = _kwn_drive_train(p["w_hid"][li], cur, cfg) * cfg.drive_gain
            v = cfg.beta * vs[li] + drive
            cur = lif_lib.spike_fn(v, jnp.asarray(cfg.v_th1))
            new_vs.append(jnp.where(cur > 0, 0.0, v))
        return (tuple(new_vs), spk_acc + cur), None

    init = (tuple(jnp.zeros((b, w)) for w in widths)
            if multi else (jnp.zeros((b, cfg.n_hidden)),),
            jnp.zeros((b, cfg.n_hidden)))
    (_, counts), _ = jax.lax.scan(step, init, jnp.moveaxis(events, 1, 0))
    return (counts / t_steps) @ p["w_out"]


def _quantized_weights(p, cfg: SNNConfig):
    w_int, scale = ternary_lib.quantize_weights_3bit(p["w_hid"])
    return w_int, scale


def forward_silicon(p, events, cfg: SNNConfig, key: jax.Array,
                    mode: str | None = None, k: int | None = None,
                    use_snl: bool | None = None,
                    noise: ima_lib.IMANoiseModel | None = None,
                    fused: bool | str = False,
                    mac_telemetry: bool = False):
    """Inference through the macro simulator (KWN Eq. 1 / NLD Eq. 2).

    ``fused`` selects the execution path:

    * ``False`` — the composed stage chain (HBM-visible intermediates);
    * ``True`` / ``"seq"`` — the time-major fused kernel: the *whole* event
      sequence runs in one Pallas launch (MAC -> IMA -> mode head -> LIF in
      one VMEM pass per step, LIF membrane carried in VMEM across T), with
      any virtual-macro tiling the layer shape needs picked automatically
      by the kernel-side tile planner;
    * ``"step"`` — the PR 1 behaviour: one fused kernel launch per scan
      step (kept for launch-overhead benchmarking).

    All fused variants are bitwise-equal to the composed path at f32 in KWN
    mode; in NLD mode they additionally quantize the branch weights onto
    the twin-cell grid (the silicon storage format), so accuracies can
    differ slightly from the float-weight composed path.

    With ``noise`` (the Fig. 7 ``IMANoiseModel``), the fused paths stay
    fused: the per-step per-column conversion-error draws — and the SNL
    sign noise — are generated *inside* the kernel by the counter PRNG,
    keyed on a seed derived from ``key``, with no pre-drawn noise tensor
    and no composed-path fallback.  Noisy ``"step"`` and ``"seq"`` draw the
    identical stream (the scan index is the counter's step word), and both
    are bitwise-equal to ``kernels.ref.fused_macro_seq_ref`` with the same
    parameters.  The noisy *composed* path keeps its historical
    ``jax.random``/PRBS draws, so noisy composed and noisy fused are
    statistically — not bitwise — equivalent.

    The fused paths are *activity-gated*: the occupancy plan of the event
    sequence is built once per sequence (``macro.plan_activity``) and the
    kernel skips MAC work for all-zero (step, row-tile, K-tile) blocks and
    bounds the KWN ramp sweep — output bits are unchanged, so gating has
    no off switch here (benchmarks A/B it at the ops layer).  Raw-MAC
    telemetry is *opt-in* (``mac_telemetry=True``): by default the fused
    kernel keeps the accumulator in VMEM scratch and never writes the
    (T, B, NC) MAC stack to HBM — inference consumes spikes and masks,
    not raw MACs, and that write was the fused step's largest dead output.

    Stacked configs (``cfg.hidden_layers`` with more than one width) route
    every ``fused`` choice through the multi-layer machinery: ``"seq"`` /
    ``"step"`` use the stacked kernel (one launch chains all layers, the
    inter-layer ternary spike tensor never leaves the chip, layer l's KWN
    winner set is layer l+1's activity plan), ``False`` composes the stage
    chain per layer.  All three agree bitwise in KWN mode; NLD stacks and
    ``mac_telemetry=True`` on stacks are unsupported (ValueError).

    Returns (logits, telemetry) where telemetry carries adc_steps per time
    step (early-stop latency), LIF update counts, SOP counts for the
    energy model, and — on the fused paths — the skipped-block ratio of
    the activity plan (the fraction of MAC blocks gating elided).  All
    rates normalize by the *actual* sequence length ``events.shape[1]``,
    never ``cfg.n_steps``.
    """
    mode = mode or cfg.mode
    k = k or cfg.k
    use_snl = cfg.use_snl if use_snl is None else use_snl
    if fused is True:
        fused = "seq"
    b, t_steps = events.shape[0], events.shape[1]
    multi = len(cfg.layer_widths) > 1
    if multi and mode != "kwn":
        raise ValueError("multi-layer stacks are KWN-only")
    mcfg = macro_lib.CIMMacroConfig(
        code_bits=cfg.code_bits,
        mac_range=cfg.mac_range if mode == "kwn" else cfg.dend_range,
        ima_noise=noise)
    lif_p = lif_lib.LIFParams(beta=cfg.beta, v_th1=cfg.v_th1, v_th2=cfg.v_th2,
                              noise_amp=cfg.noise_amp if use_snl else 0.0)
    if multi:
        ks = cfg.k_layers or (k,) * len(cfg.layer_widths)
        if fused in ("seq", "step"):
            if mac_telemetry:
                raise ValueError("mac_telemetry is single-layer only: the "
                                 "stacked kernel never writes MACs to HBM")
            return _forward_silicon_fused_multi(p, events, cfg, ks, use_snl,
                                                mcfg, lif_p, key, fused)
        if fused is not False:
            raise ValueError(f"unknown fused={fused!r}; expected False, "
                             f"True, 'step', or 'seq'")
        return _forward_silicon_composed_multi(p, events, cfg, ks, use_snl,
                                               mcfg, lif_p, key, noise)
    if fused == "seq":
        return _forward_silicon_fused_seq(p, events, cfg, mode, k, use_snl,
                                          mcfg, lif_p, key, mac_telemetry)
    if fused == "step":
        return _forward_silicon_fused(p, events, cfg, mode, k, use_snl, mcfg,
                                      lif_p, key, mac_telemetry)
    if fused is not False:
        raise ValueError(f"unknown fused={fused!r}; expected False, True, "
                         f"'step', or 'seq'")
    if mode == "kwn":
        w_int, scale = _quantized_weights(p, cfg)
        nlq = _nlq_cb(cfg)

    def step(carry, inp):
        state, spk_acc, tele = carry
        ev, kk = inp
        if mode == "nld":
            drive = macro_lib.nld_forward(ev, p["dend"], mcfg,
                                          activation=cfg.activation,
                                          quantize=True)
            mask = None
            adc_steps = jnp.full((b,), nlq_steps_full(cfg), jnp.int32)
            n_upd = jnp.full((b,), cfg.n_hidden, jnp.int32)
        else:
            mac_int = macro_lib.cim_mac(ev, w_int, mcfg, key=kk)  # int units
            if noise is not None:
                codes = ima_lib.ima_convert_noisy(mac_int, nlq, kk, noise)
                mac_q = ima_lib.ima_reconstruct(codes, nlq)
            else:
                mac_q = ima_lib.ima_quantize(mac_int, nlq)
            res = kwn_lib.kwn_select(mac_q, k, nlq)
            drive = (mac_q * scale[0]) * res.mask                 # LUT x scale
            mask = res.mask
            adc_steps = res.adc_steps
            n_upd = jnp.full((b,), k, jnp.int32)
        state, s = lif_lib.lif_step(
            state, drive * cfg.drive_gain, lif_p,
            update_mask=mask, use_snl=use_snl and mode == "kwn")
        sops = jnp.sum(jnp.abs(ev), axis=-1) * cfg.n_hidden
        tele = {
            "adc_steps": tele["adc_steps"] + adc_steps.astype(jnp.float32),
            "lif_updates": tele["lif_updates"] + n_upd.astype(jnp.float32),
            "sops": tele["sops"] + sops,
        }
        return (state, spk_acc + s, tele), None

    tele0 = {"adc_steps": jnp.zeros((b,)), "lif_updates": jnp.zeros((b,)),
             "sops": jnp.zeros((b,))}
    init = (lif_lib.lif_init((b, cfg.n_hidden)), jnp.zeros((b, cfg.n_hidden)),
            tele0)
    keys = jax.random.split(key, t_steps)
    (state, counts, tele), _ = jax.lax.scan(
        step, init, (jnp.moveaxis(events, 1, 0), keys))
    logits = (counts / t_steps) @ p["w_out"]
    tele = jax.tree.map(lambda x: x / t_steps, tele)  # per-step means
    return logits, tele


def _quantized_weight_stack(p, cfg: SNNConfig):
    """Per-layer (w_int, scale) for a ``hidden_layers`` stack."""
    return [ternary_lib.quantize_weights_3bit(w) for w in p["w_hid"]]


def _forward_silicon_composed_multi(p, events, cfg: SNNConfig, ks, use_snl,
                                    mcfg, lif_p, key, noise):
    """Composed multi-layer inference: the per-layer HBM round-trip path.

    Each time step runs the layer chain through the composed stage
    pipeline (cim_mac -> IMA -> KWN -> LIF per layer), with every
    inter-layer spike tensor materialized — the baseline the stacked fused
    kernel is benchmarked against, and (clean) its bitwise oracle at the
    model level.  Per-layer noise keys are ``fold_in(step_key, layer)``.
    """
    b, t_steps = events.shape[0], events.shape[1]
    widths = cfg.layer_widths
    w_stack = _quantized_weight_stack(p, cfg)
    nlq = _nlq_cb(cfg)

    def step(carry, inp):
        states, spk_acc, tele = carry
        ev, kk = inp
        cur, new_states = ev, []
        adc = jnp.zeros((b,), jnp.float32)
        sops = jnp.zeros((b,), jnp.float32)
        for li, (w_int, scale) in enumerate(w_stack):
            kl = jax.random.fold_in(kk, li)
            mac_int = macro_lib.cim_mac(cur, w_int, mcfg, key=kl)
            if noise is not None:
                codes = ima_lib.ima_convert_noisy(mac_int, nlq, kl, noise)
                mac_q = ima_lib.ima_reconstruct(codes, nlq)
            else:
                mac_q = ima_lib.ima_quantize(mac_int, nlq)
            res = kwn_lib.kwn_select(mac_q, ks[li], nlq)
            drive = (mac_q * scale[0]) * res.mask
            state, s = lif_lib.lif_step(
                states[li], drive * cfg.drive_gain, lif_p,
                update_mask=res.mask, use_snl=use_snl)
            new_states.append(state)
            adc = adc + res.adc_steps.astype(jnp.float32)
            sops = sops + jnp.sum(jnp.abs(cur), axis=-1) * widths[li]
            cur = s
        tele = {
            "adc_steps": tele["adc_steps"] + adc,
            "lif_updates": tele["lif_updates"] + float(sum(ks)),
            "sops": tele["sops"] + sops,
        }
        return (tuple(new_states), spk_acc + cur, tele), None

    tele0 = {"adc_steps": jnp.zeros((b,)), "lif_updates": jnp.zeros((b,)),
             "sops": jnp.zeros((b,))}
    init = (tuple(lif_lib.lif_init((b, w)) for w in widths),
            jnp.zeros((b, cfg.n_hidden)), tele0)
    keys = jax.random.split(key, t_steps)
    (_, counts, tele), _ = jax.lax.scan(
        step, init, (jnp.moveaxis(events, 1, 0), keys))
    logits = (counts / t_steps) @ p["w_out"]
    tele = jax.tree.map(lambda x: x / t_steps, tele)
    return logits, tele


def _pack_fused(p, cfg: SNNConfig, mode: str, mcfg):
    if mode == "kwn":
        w_int, scale = _quantized_weights(p, cfg)
        return macro_lib.pack_kwn_weights(w_int, scale.reshape(-1), mcfg)
    return macro_lib.pack_nld_weights(p["dend"], mcfg,
                                      activation=cfg.activation)


def _noise_seed(key: jax.Array) -> jax.Array:
    """Counter-PRNG seed word derived from the caller's JAX key."""
    return jax.random.randint(key, (), 0, jnp.iinfo(jnp.int32).max,
                              dtype=jnp.int32)


def _forward_silicon_fused(p, events, cfg: SNNConfig, mode: str, k: int,
                           use_snl: bool, mcfg, lif_p, key,
                           mac_telemetry: bool = False):
    """Per-step fused inference scan body.

    Mirrors the composed ``forward_silicon`` step exactly in the clean case
    (same PRBS state threading, same telemetry), one fused Pallas kernel
    per time step.  With ``mcfg.ima_noise`` the per-step launches pass the
    scan index as the counter step word, so the stream — and therefore
    every spike — is bitwise-identical to the one-launch ``seq`` path.
    Kept for launch-overhead benchmarking; the serving default is the
    time-major ``_forward_silicon_fused_seq``.  Each per-step launch gates
    on its own step's activity map (the T=1 slice of the sequence plan),
    so the reported skipped-block ratio matches the seq path exactly.
    """
    b = events.shape[0]
    fw = _pack_fused(p, cfg, mode, mcfg)
    snl_active = use_snl and mode == "kwn"
    noisy = mcfg.ima_noise is not None
    ima_kn = macro_lib.fused_kernel_noise(fw, mcfg)
    seed = _noise_seed(key) if noisy else jnp.int32(0)

    def step(carry, inp):
        v, prbs_state, spk_acc, tele = carry
        ev, t = inp
        if noisy:
            nz = None           # SNL noise comes from the in-kernel counter
        elif snl_active:
            prbs_state, nz = prbs_lib.prbs_noise(prbs_state, v.shape,
                                                 lif_p.noise_amp)
        else:
            nz = jnp.zeros_like(v)
        v, s, mask, steps, _ = macro_lib.fused_step(
            ev, fw, v, nz, k=k, drive_gain=cfg.drive_gain, beta=cfg.beta,
            v_th1=cfg.v_th1, v_th2=cfg.v_th2, v_reset=lif_p.v_reset,
            v_lim=lif_lib.vmem_limit(lif_p.vmem_bits),
            use_snl=snl_active, ima_noise=ima_kn,
            snl_amp=lif_p.noise_amp if (noisy and snl_active) else 0.0,
            mac_telemetry=mac_telemetry, seed=seed, step_offset=t)
        n_upd = float(k if mode == "kwn" else cfg.n_hidden)
        tele = {
            "adc_steps": tele["adc_steps"] + steps.astype(jnp.float32),
            "lif_updates": tele["lif_updates"] + n_upd,
            "sops": tele["sops"] + jnp.sum(jnp.abs(ev), -1) * cfg.n_hidden,
        }
        return (v, prbs_state, spk_acc + s, tele), None

    tele0 = {"adc_steps": jnp.zeros((b,)), "lif_updates": jnp.zeros((b,)),
             "sops": jnp.zeros((b,))}
    st0 = lif_lib.lif_init((b, cfg.n_hidden))
    init = (st0.v_mem, st0.prbs_state, jnp.zeros((b, cfg.n_hidden)), tele0)
    t_steps = events.shape[1]
    (_, _, counts, tele), _ = jax.lax.scan(
        step, init, (jnp.moveaxis(events, 1, 0),
                     jnp.arange(t_steps, dtype=jnp.int32)))
    logits = (counts / t_steps) @ p["w_out"]
    tele = jax.tree.map(lambda x: x / t_steps, tele)
    tele["skipped_block_ratio"] = _skipped_block_ratio(events, fw, cfg)
    return logits, tele


def _skipped_block_ratio(events, fw, cfg: SNNConfig) -> jax.Array:
    """Fraction of (step, row-tile, K-tile) MAC blocks gating elides,
    broadcast per request (the plan is a batch-level property — requests
    share row tiles)."""
    act = macro_lib.plan_activity(jnp.moveaxis(events, 1, 0), fw,
                                  cfg.n_hidden)
    # clip: f32 mean of an all-ones map can land a ULP past 1.0
    ratio = jnp.clip(1.0 - jnp.mean(act.astype(jnp.float32)), 0.0, 1.0)
    return jnp.full((events.shape[0],), ratio)


def _forward_silicon_fused_seq(p, events, cfg: SNNConfig, mode: str, k: int,
                               use_snl: bool, mcfg, lif_p, key,
                               mac_telemetry: bool = False):
    """Time-major fused inference: the whole event sequence in one launch.

    The T axis is folded into the Pallas grid (``macro.fused_seq``), so the
    LIF membrane never leaves VMEM between steps and the weight planes are
    staged once per sequence instead of once per step — the serving
    engine's dominant launch overhead.  In the clean case PRBS noise is
    pre-drawn with the exact LFSR sequence the per-step path threads
    through its scan, and the per-step output stacks are left-folded in
    scan order, so logits and telemetry stay bitwise-equal to the composed
    and per-step paths.  In the noisy case (``mcfg.ima_noise``) *nothing*
    is pre-drawn: both the IMA conversion error and the SNL sign noise
    come from the in-kernel counter PRNG, and the launch streams only the
    events themselves.

    The activity plan is built once per sequence here and shared between
    the kernel (scalar-prefetched occupancy gating) and the telemetry
    (skipped-block ratio) — one host-side pass over the events per batch.
    """
    b, t_steps = events.shape[0], events.shape[1]
    fw = _pack_fused(p, cfg, mode, mcfg)
    snl_active = use_snl and mode == "kwn"
    noisy = mcfg.ima_noise is not None
    ima_kn = macro_lib.fused_kernel_noise(fw, mcfg)
    seed = _noise_seed(key) if noisy else jnp.int32(0)
    ev_t = jnp.moveaxis(events, 1, 0)                      # (T, B, N_in)
    activity = macro_lib.plan_activity(ev_t, fw, cfg.n_hidden)
    st0 = lif_lib.lif_init((b, cfg.n_hidden))
    if noisy:
        noise_t = None          # all noise is generated inside the kernel
    elif snl_active:
        def draw(s, _):
            s, nz = prbs_lib.prbs_noise(s, (b, cfg.n_hidden), lif_p.noise_amp)
            return s, nz
        _, noise_t = jax.lax.scan(draw, st0.prbs_state, None, length=t_steps)
    else:
        noise_t = jnp.zeros((t_steps, b, cfg.n_hidden))
    _, spk_t, _, steps_t, _ = macro_lib.fused_seq(
        ev_t, fw, st0.v_mem, noise_t, k=k, drive_gain=cfg.drive_gain,
        beta=cfg.beta, v_th1=cfg.v_th1, v_th2=cfg.v_th2,
        v_reset=lif_p.v_reset,
        v_lim=lif_lib.vmem_limit(lif_p.vmem_bits),
        use_snl=snl_active, ima_noise=ima_kn,
        snl_amp=lif_p.noise_amp if (noisy and snl_active) else 0.0,
        activity=activity, mac_telemetry=mac_telemetry, seed=seed)
    n_upd = float(k if mode == "kwn" else cfg.n_hidden)
    sops_t = jnp.sum(jnp.abs(ev_t), axis=-1) * cfg.n_hidden   # (T, B)

    def fold(acc, xs):
        counts, tele = acc
        spk, steps, sops = xs
        tele = {
            "adc_steps": tele["adc_steps"] + steps.astype(jnp.float32),
            "lif_updates": tele["lif_updates"] + n_upd,
            "sops": tele["sops"] + sops,
        }
        return (counts + spk, tele), None

    tele0 = {"adc_steps": jnp.zeros((b,)), "lif_updates": jnp.zeros((b,)),
             "sops": jnp.zeros((b,))}
    (counts, tele), _ = jax.lax.scan(
        fold, (jnp.zeros((b, cfg.n_hidden)), tele0),
        (spk_t, steps_t, sops_t))
    logits = (counts / t_steps) @ p["w_out"]
    tele = jax.tree.map(lambda x: x / t_steps, tele)
    tele["skipped_block_ratio"] = jnp.full(
        (b,), jnp.clip(1.0 - jnp.mean(activity.astype(jnp.float32)),
                       0.0, 1.0))
    return logits, tele


class SiliconStreamState(NamedTuple):
    """Device-resident per-slot state for step-resumable fused inference.

    One row per serving slot; this is the SNN analog of an LM engine's
    KV cache.  ``v`` is the LIF membrane the fused kernel carries in VMEM
    within a round and this struct carries *across* rounds; the remaining
    fields are the per-request accumulators and noise-stream bookkeeping
    that let a request's results come out bitwise-identical to a one-shot
    batch-1 ``forward_silicon(fused="seq")`` run no matter how many rounds
    its sequence was split over or which requests shared the batch.
    """

    v: jax.Array           # (S, N) f32 LIF membrane
    prbs: jax.Array        # (S,) uint32 per-slot PRBS LFSR state (clean SNL)
    counts: jax.Array      # (S, N) f32 spike-count accumulator
    adc: jax.Array         # (S,) f32 summed early-stop ADC ramp steps
    sops: jax.Array        # (S,) f32 summed synaptic operations
    skip_acc: jax.Array    # (S,) f32 summed per-step skipped-block ratio
    steps_done: jax.Array  # (S,) i32 time steps completed
    length: jax.Array      # (S,) i32 request sequence length
    seed: jax.Array        # (S,) i32 per-request counter-PRNG seed word


def silicon_stream_init(cfg: SNNConfig, slots: int) -> SiliconStreamState:
    """Fresh all-idle slot state for ``forward_silicon_stream``."""
    n = cfg.n_hidden
    zf = jnp.zeros((slots,), jnp.float32)
    return SiliconStreamState(
        v=jnp.zeros((slots, n), jnp.float32),
        prbs=jnp.full((slots,), prbs_lib.lfsr_init(1)),
        counts=jnp.zeros((slots, n), jnp.float32),
        adc=zf, sops=zf, skip_acc=zf,
        steps_done=jnp.zeros((slots,), jnp.int32),
        length=jnp.zeros((slots,), jnp.int32),
        seed=jnp.zeros((slots,), jnp.int32))


@jax.jit
def silicon_stream_admit(state: SiliconStreamState, mask, lengths,
                         seeds) -> SiliconStreamState:
    """Reset the masked slots for newly admitted requests.

    ``mask`` (S,) bool selects the slots being (re)admitted; their
    membrane, accumulators, and PRBS state return to the exact
    ``lif_init`` starting point a one-shot run begins from.  ``lengths``
    and ``seeds`` are full (S,) vectors (non-admitted slots just carry
    their previous values through).
    """
    mask = jnp.asarray(mask)
    m1 = mask[:, None]
    zf = jnp.float32(0.0)
    return SiliconStreamState(
        v=jnp.where(m1, zf, state.v),
        prbs=jnp.where(mask, prbs_lib.lfsr_init(1), state.prbs),
        counts=jnp.where(m1, zf, state.counts),
        adc=jnp.where(mask, zf, state.adc),
        sops=jnp.where(mask, zf, state.sops),
        skip_acc=jnp.where(mask, zf, state.skip_acc),
        steps_done=jnp.where(mask, 0, state.steps_done),
        length=jnp.asarray(lengths, jnp.int32),
        seed=jnp.asarray(seeds, jnp.int32))


@jax.jit
def silicon_stream_keys(base_key, orders, given, derive):
    """Every fresh admission's key and seed word in one device program.

    ``orders`` (S,) uint32 are the requests' submission indices, ``given``
    (S, 2) uint32 the keys their callers set, and ``derive`` (S,) bool
    marks the slots whose key is folded from ``base_key``.  All shapes are
    fixed at the slot count, so the program compiles once however many
    requests a pass admits.  Returns the (S, 2) keys,
    ``fold_in(base_key, order)`` where ``derive`` is set and ``given``
    elsewhere, and their (S,) counter-PRNG seed words (``_noise_seed``),
    each bitwise equal to the per-request call.  Rows of slots that admit
    nothing mean nothing.
    """
    derived = jax.vmap(lambda o: jax.random.fold_in(base_key, o))(orders)
    keys = jnp.where(derive[:, None], derived, given)
    return keys, jax.vmap(_noise_seed)(keys)


@jax.jit
def silicon_stream_readout(state: SiliconStreamState, w_out, mask):
    """Every finished slot's answer in one device program.

    ``mask`` (S,) bool marks the slots whose streams ended.  All shapes
    are fixed at the slot count, so the readout compiles once however
    many slots finish together.  Returns per slot the logits
    ``(counts / length) @ w_out``, their argmax, and the ``adc``, ``sops``
    and ``skip_acc`` accumulators divided by the length.  The product runs
    slot by slot at batch 1 (``lax.map``), the shape of a one-shot
    ``forward_silicon`` readout, so the logits match it bit for bit: one
    (S, N) @ (N, C) product may round a row differently.  The telemetry
    is divided here, on the device, because the one-shot path divides its
    telemetry by T on the device too, and a TPU's f32 division rounds
    differently from the host's (30-step means read one ulp apart).
    Unmasked rows divide by 1 and mean nothing.
    """
    length = jnp.where(mask, state.length, 1).astype(jnp.float32)

    def one(xs):
        counts, n = xs
        return ((counts[None] / n) @ w_out)[0]

    logits = jax.lax.map(one, (state.counts, length))
    return (logits, jnp.argmax(logits, axis=-1), state.adc / length,
            state.sops / length, state.skip_acc / length)


class SlotCheckpoint(NamedTuple):
    """Host-side snapshot of one serving slot's mid-flight stream state.

    Everything a preempted request needs to resume bitwise-exactly, pulled
    off device with ``silicon_stream_save`` and pushed back with
    ``silicon_stream_restore`` — into *any* free slot, not necessarily the
    one it left.  Relocatability holds because nothing in the stream's
    noise keying sees the physical slot index: the noisy counter-PRNG
    stream is keyed on ``(seed, absolute step, row 0)`` through the
    kernel's ``row_ctl`` lane (``macro.stream_row_ctl``), and the clean
    SNL stream is the per-slot PRBS LFSR word captured here.  The
    membrane ``v`` and the accumulators are exact f32/i32 copies, so a
    restore followed by the remaining rounds reproduces the uninterrupted
    run bit for bit.
    """

    v: np.ndarray          # (N,) f32 LIF membrane at the preemption point
    prbs: int              # uint32 PRBS LFSR word (clean-path SNL stream)
    counts: np.ndarray     # (N,) f32 spike-count accumulator
    adc: float             # summed early-stop ADC ramp steps so far
    sops: float            # summed synaptic operations so far
    skip_acc: float        # summed per-step skipped-block ratio so far
    steps_done: int        # absolute stream offset to resume at
    length: int            # request sequence length
    seed: int              # per-request counter-PRNG seed word


def silicon_stream_save(state: SiliconStreamState,
                        slot: int) -> SlotCheckpoint:
    """Checkpoint slot ``slot`` to host memory (one device->host pull).

    The slot's rows are copied out as-is; the device state is left
    untouched (the engine re-admits over the stale rows, which
    ``silicon_stream_admit`` / ``silicon_stream_restore`` fully reset).

    The pull is wrapped in a ``checkpoint_save`` span on the
    ``transfer`` track (with the payload byte count) — host<->device
    checkpoint traffic is the ROADMAP's named TPU bottleneck candidate,
    so it gets a first-class lane in every exported trace.
    """
    tr = obs_trace.get_tracer()
    span = tr.begin("checkpoint_save", track="transfer")
    ckpt = SlotCheckpoint(
        v=np.asarray(state.v[slot]),
        prbs=int(np.asarray(state.prbs[slot])),
        counts=np.asarray(state.counts[slot]),
        adc=float(np.asarray(state.adc[slot])),
        sops=float(np.asarray(state.sops[slot])),
        skip_acc=float(np.asarray(state.skip_acc[slot])),
        steps_done=int(np.asarray(state.steps_done[slot])),
        length=int(np.asarray(state.length[slot])),
        seed=int(np.asarray(state.seed[slot])))
    if span is not None:
        tr.end(span, args={"slot": int(slot),
                           "bytes": checkpoint_nbytes(ckpt),
                           "direction": "device_to_host"})
    return ckpt


def checkpoint_nbytes(ckpt: SlotCheckpoint) -> int:
    """Payload size of one slot checkpoint in bytes (arrays + scalars).

    Scalars travel as one machine word each; this is the quantity the
    transfer spans report and the engine's bandwidth math would use on a
    real part, so it lives next to the checkpoint type rather than being
    re-derived in tooling.
    """
    scalar_bytes = 8 * (len(ckpt) - 2)   # all fields except the two arrays
    return int(ckpt.v.nbytes + ckpt.counts.nbytes + scalar_bytes)


@jax.jit
def _stream_restore(state: SiliconStreamState, slot, v, prbs, counts, adc,
                    sops, skip_acc, steps_done, length,
                    seed) -> SiliconStreamState:
    return SiliconStreamState(
        v=state.v.at[slot].set(v),
        prbs=state.prbs.at[slot].set(prbs),
        counts=state.counts.at[slot].set(counts),
        adc=state.adc.at[slot].set(adc),
        sops=state.sops.at[slot].set(sops),
        skip_acc=state.skip_acc.at[slot].set(skip_acc),
        steps_done=state.steps_done.at[slot].set(steps_done),
        length=state.length.at[slot].set(length),
        seed=state.seed.at[slot].set(seed))


def silicon_stream_restore(state: SiliconStreamState, slot: int,
                           ckpt: SlotCheckpoint) -> SiliconStreamState:
    """Restore a ``SlotCheckpoint`` into slot ``slot`` (any free slot).

    The inverse of ``silicon_stream_save``: one jitted scatter writes the
    checkpoint's membrane, PRBS word, accumulators, and stream position
    into the slot's rows.  The next ``forward_silicon_stream`` round picks
    the stream up at ``ckpt.steps_done`` — the ``row_ctl`` lane replays
    the noisy counter stream from exactly that offset and the restored
    LFSR word continues the clean SNL stream, so the request's final
    results are bitwise-identical to never having been preempted
    (pinned by tests/test_serve_preempt.py across slots, co-residents,
    and non-round-aligned offsets).

    Wrapped in a ``checkpoint_restore`` span on the ``transfer`` track,
    mirroring ``silicon_stream_save`` — note the span covers the
    host->device *dispatch* (the scatter is jitted and asynchronous), so
    on real hardware the device-side cost shows up in the XLA trace the
    optional ``jax.profiler`` passthrough lines spans up with.
    """
    tr = obs_trace.get_tracer()
    span = tr.begin("checkpoint_restore", track="transfer")
    state = _stream_restore(
        state, jnp.int32(slot), jnp.asarray(ckpt.v, jnp.float32),
        jnp.uint32(ckpt.prbs), jnp.asarray(ckpt.counts, jnp.float32),
        jnp.float32(ckpt.adc), jnp.float32(ckpt.sops),
        jnp.float32(ckpt.skip_acc), jnp.int32(ckpt.steps_done),
        jnp.int32(ckpt.length), jnp.int32(ckpt.seed))
    if span is not None:
        tr.end(span, args={"slot": int(slot),
                           "bytes": checkpoint_nbytes(ckpt),
                           "direction": "host_to_device"})
    return state


@functools.partial(jax.jit, static_argnames=("cfg", "noise"))
def forward_silicon_stream(p, events, cfg: SNNConfig,
                           state: SiliconStreamState,
                           noise: ima_lib.IMANoiseModel | None = None
                           ) -> SiliconStreamState:
    """One continuous-batching round: advance every slot by R time steps.

    ``events`` is the *time-major* (R, S, N_in) round block the engine
    staged — slot s carries steps ``[steps_done[s], steps_done[s] + R)``
    of its request's event stream, zero-padded past the request's end.
    R is whatever leading extent the caller staged: the engine's regular
    cadence uses ``round_steps``, and *partial* rounds (R <
    ``round_steps``, the preemption path that stops a stream at a
    non-round-aligned offset) are the same launch at a shorter extent —
    each distinct R compiles one jit entry, bounded by ``round_steps``.
    Runs one fused time-major kernel launch (LIF membrane in VMEM within
    the round, carried across rounds through ``state.v``) and folds this
    round's spikes/ADC-steps/SOPs into the per-slot accumulators, masking
    out steps beyond each request's true length so every statistic
    normalizes by the request's own sequence — never the round count.

    Bitwise parity with one-shot ``forward_silicon(..., fused="seq")`` on
    a batch of one, clean and noisy, is by construction:

    * noise streams are per-slot — the counter PRNG is keyed through the
      kernel's ``row_ctl`` path on ``(state.seed, absolute step, row 0)``
      and the clean-path SNL PRBS is a per-slot LFSR drawing
      ``cfg.n_hidden`` bits per step from the ``lif_init`` seed — so each
      slot consumes exactly the stream a batch-1 run would;
    * every accumulated quantity (spike counts, ADC steps, SOPs) is an
      integer-valued f32 well under 2^24, so fold order cannot change a
      bit.

    The per-round activity plan spans all co-resident slots (gating is
    output-invariant; only the work changes), and ``skip_acc`` integrates
    the plan's skipped-block ratio over each request's active steps.
    Single-layer configs only — the engine serves multi-layer stacks
    through the legacy drain path.
    """
    if len(cfg.layer_widths) > 1:
        raise ValueError("forward_silicon_stream is single-layer only; "
                         "serve stacks through the legacy drain path")
    mode, k = cfg.mode, cfg.k
    mcfg = macro_lib.CIMMacroConfig(
        code_bits=cfg.code_bits,
        mac_range=cfg.mac_range if mode == "kwn" else cfg.dend_range,
        ima_noise=noise)
    lif_p = lif_lib.LIFParams(
        beta=cfg.beta, v_th1=cfg.v_th1, v_th2=cfg.v_th2,
        noise_amp=cfg.noise_amp if cfg.use_snl else 0.0)
    fw = _pack_fused(p, cfg, mode, mcfg)
    snl_active = cfg.use_snl and mode == "kwn"
    noisy = noise is not None
    ima_kn = macro_lib.fused_kernel_noise(fw, mcfg)
    r, slots = events.shape[0], events.shape[1]
    activity = macro_lib.plan_activity(events, fw, cfg.n_hidden)
    new_prbs = state.prbs
    if noisy:
        noise_t = None          # all noise is generated inside the kernel
    elif snl_active:
        def slot_draw(s0):
            def draw(s, _):
                s, nz = prbs_lib.prbs_noise(s, (cfg.n_hidden,),
                                            lif_p.noise_amp)
                return s, nz
            return jax.lax.scan(draw, s0, None, length=r)
        new_prbs, nz = jax.vmap(slot_draw)(state.prbs)
        noise_t = jnp.moveaxis(nz, 0, 1)                   # (R, S, N)
    else:
        noise_t = jnp.zeros((r, slots, cfg.n_hidden))
    # Per-slot noise-stream control: each slot replays the stream of its
    # own batch-1 run — its request seed, its absolute step, row id 0.
    row_ctl = macro_lib.stream_row_ctl(state.seed, state.steps_done)
    v_out, spk_t, _, steps_t, _ = macro_lib.fused_seq(
        events, fw, state.v, noise_t, k=k, drive_gain=cfg.drive_gain,
        beta=cfg.beta, v_th1=cfg.v_th1, v_th2=cfg.v_th2,
        v_reset=lif_p.v_reset,
        v_lim=lif_lib.vmem_limit(lif_p.vmem_bits),
        use_snl=snl_active, ima_noise=ima_kn,
        snl_amp=lif_p.noise_amp if (noisy and snl_active) else 0.0,
        activity=activity, mac_telemetry=False, row_ctl=row_ctl)
    iota = jnp.arange(r, dtype=jnp.int32)[:, None]
    active = (state.steps_done[None, :] + iota) < state.length[None, :]
    af = active.astype(jnp.float32)                        # (R, S)
    counts = state.counts + jnp.sum(spk_t * af[:, :, None], axis=0)
    adc = state.adc + jnp.sum(steps_t.astype(jnp.float32) * af, axis=0)
    sops = state.sops + jnp.sum(
        jnp.sum(jnp.abs(events), -1) * af, axis=0) * cfg.n_hidden
    ratio = jnp.clip(1.0 - jnp.mean(activity.astype(jnp.float32)), 0.0, 1.0)
    skip_acc = state.skip_acc + ratio * jnp.sum(af, axis=0)
    steps_done = jnp.minimum(state.steps_done + r, state.length)
    return SiliconStreamState(v=v_out, prbs=new_prbs, counts=counts,
                              adc=adc, sops=sops, skip_acc=skip_acc,
                              steps_done=steps_done, length=state.length,
                              seed=state.seed)


def _pack_fused_stack(p, cfg: SNNConfig, mcfg):
    w_ints, scales = [], []
    for w_int, scale in _quantized_weight_stack(p, cfg):
        w_ints.append(w_int)
        scales.append(scale.reshape(-1))
    return macro_lib.pack_kwn_stack(w_ints, scales, mcfg)


def _noise_seeds(key: jax.Array, n_layers: int) -> jax.Array:
    """Per-layer counter seeds: distinct words so layer noise streams
    never collide (the stacked kernel's ctl row)."""
    return jax.random.randint(key, (n_layers,), 0,
                              jnp.iinfo(jnp.int32).max, dtype=jnp.int32)


def _forward_silicon_fused_multi(p, events, cfg: SNNConfig, ks, use_snl,
                                 mcfg, lif_p, key, cadence: str):
    """Stacked fused inference: L macro layers chained on-chip.

    ``cadence="seq"`` runs the whole sequence and the whole stack in ONE
    Pallas launch (``macro.fused_multi_seq``): per-layer membranes live in
    VMEM across time steps and the inter-layer ternary spike tensors never
    reach HBM — layer l's KWN winner set IS layer l+1's activity plan,
    evaluated in-kernel (only layer 0 gates on the host occupancy map).
    ``cadence="step"`` launches the stack once per time step (launch-
    overhead benchmarking); both draw identical noise streams and are
    bitwise-equal.

    Hidden-layer activity is reported through telemetry only (per-layer
    spike counts for SOPs, per-layer occupancy counters for the
    skipped-block ratio) — the spike planes themselves stay on-chip.
    """
    b, t_steps = events.shape[0], events.shape[1]
    widths = cfg.layer_widths
    n_layers = len(widths)
    stack = _pack_fused_stack(p, cfg, mcfg)
    snl_active = use_snl
    noisy = mcfg.ima_noise is not None
    ima_kn = macro_lib.fused_kernel_noise(stack[0], mcfg)
    seeds = (_noise_seeds(key, n_layers) if noisy
             else jnp.zeros((n_layers,), jnp.int32))
    ev_t = jnp.moveaxis(events, 1, 0)                     # (T, B, N_in)
    v0s = [lif_lib.lif_init((b, w)).v_mem for w in widths]
    if noisy or not snl_active:
        noises = None if noisy else [jnp.zeros((t_steps, b, w))
                                     for w in widths]
        prbs0 = None
    else:
        # pre-draw each layer's PRBS stream exactly as the composed path's
        # per-layer LIF states thread it (bitwise parity in the clean case)
        noises, prbs0 = [], []
        for w in widths:
            st = lif_lib.lif_init((b, w))
            prbs0.append(st.prbs_state)

            def draw(s, _, w=w):
                s, nz = prbs_lib.prbs_noise(s, (b, w), lif_p.noise_amp)
                return s, nz

            _, nz_t = jax.lax.scan(draw, st.prbs_state, None, length=t_steps)
            noises.append(nz_t)
    kw = dict(ks=tuple(ks), drive_gain=cfg.drive_gain, beta=cfg.beta,
              v_th1=cfg.v_th1, v_th2=cfg.v_th2, v_reset=lif_p.v_reset,
              v_lim=lif_lib.vmem_limit(lif_p.vmem_bits), use_snl=snl_active,
              ima_noise=ima_kn,
              snl_amp=lif_p.noise_amp if (noisy and snl_active) else 0.0,
              seeds=seeds)
    if cadence == "seq":
        out = macro_lib.fused_multi_seq(ev_t, stack, v0s, noises, **kw)
        spk_t = out.spikes                                  # (T, B, N_last)
        steps_t = [s for s in out.steps]                    # L x (T, B)
        cnts_t = [c for c in out.spike_counts]              # L x (T, B)
        occ_total = sum(jnp.sum(o) for o in out.occupancy)
        total_blocks = out.total_blocks
    else:
        spk_steps, steps_steps, cnts_steps = [], [], []
        occ_total, total_blocks = jnp.int32(0), 0
        vs, prbs = v0s, prbs0
        for t in range(t_steps):
            if noises is None:
                nz = None
            elif prbs is None:
                nz = [n[t:t + 1] for n in noises]
            else:
                nz, new_prbs = [], []
                for li, w in enumerate(widths):
                    s, nz_l = prbs_lib.prbs_noise(prbs[li], (b, w),
                                                  lif_p.noise_amp)
                    new_prbs.append(s)
                    nz.append(nz_l[None])
                prbs = new_prbs
            out = macro_lib.fused_multi_seq(ev_t[t:t + 1], stack, vs, nz,
                                            step_offset=t, **kw)
            vs = list(out.v_outs)
            spk_steps.append(out.spikes[0])
            steps_steps.append([s[0] for s in out.steps])
            cnts_steps.append([c[0] for c in out.spike_counts])
            occ_total = occ_total + sum(jnp.sum(o) for o in out.occupancy)
            total_blocks += out.total_blocks
        spk_t = jnp.stack(spk_steps)
        steps_t = [jnp.stack([s[li] for s in steps_steps])
                   for li in range(n_layers)]
        cnts_t = [jnp.stack([c[li] for c in cnts_steps])
                  for li in range(n_layers)]
    counts = jnp.sum(spk_t, axis=0)
    logits = (counts / t_steps) @ p["w_out"]
    adc = sum(jnp.sum(s.astype(jnp.float32), axis=0) for s in steps_t)
    sops = jnp.sum(jnp.sum(jnp.abs(ev_t), axis=-1).astype(jnp.float32),
                   axis=0) * widths[0]
    for li in range(1, n_layers):
        sops = sops + jnp.sum(cnts_t[li - 1], axis=0) * widths[li]
    tele = {
        "adc_steps": adc / t_steps,
        "lif_updates": jnp.full((b,), float(sum(ks))),
        "sops": sops / t_steps,
        "skipped_block_ratio": jnp.full(
            (b,), jnp.clip(1.0 - occ_total.astype(jnp.float32)
                           / total_blocks, 0.0, 1.0)),
    }
    return logits, tele


def nlq_steps_full(cfg: SNNConfig) -> int:
    return 2 ** cfg.code_bits - 1


# ---------------------------------------------------------------------------
# Training
# ---------------------------------------------------------------------------

def loss_fn(p, events, labels, cfg: SNNConfig, seed=None, *,
            silicon: bool = False, noise: ima_lib.IMANoiseModel | None = None,
            kwn_relax: float | None = None, remat: bool = False):
    """Cross-entropy loss; ``silicon=True`` differentiates *through* the
    fused macro kernel (surrogate backward) instead of the dense-f32
    software path — see ``repro.train.silicon``.  ``seed`` (f32 scalar)
    keys the in-kernel counter noise on the silicon path; ``noise`` (the
    Fig. 7 model) makes it noise-aware QAT."""
    if silicon:
        from repro.train import silicon as silicon_lib
        if kwn_relax is None:
            kwn_relax = silicon_lib.DEFAULT_KWN_RELAX
        logits = silicon_lib.forward_logits(
            p, events, cfg,
            jnp.float32(0.0) if seed is None else seed,
            noise=noise, kwn_relax=kwn_relax, remat=remat)
    else:
        logits = forward_train(p, events, cfg)
    lse = jax.nn.log_softmax(logits)
    return -jnp.mean(jnp.take_along_axis(lse, labels[:, None], 1))


@functools.partial(jax.jit, static_argnames=(
    "cfg", "silicon", "noise", "kwn_relax", "remat"), donate_argnums=(0, 1))
def train_step(p, opt_m, events, labels, cfg: SNNConfig, lr, seed=None, *,
               silicon: bool = False, noise=None, kwn_relax=None,
               remat: bool = False):
    """One SGD-momentum step.  ``p``/``opt_m`` are donated: the optimizer
    state updates in place instead of copying every buffer per step (the
    donation engages on TPU/GPU; the CPU test container aliases where it
    can).  Callers must rebind both, as ``train`` does."""
    loss, g = jax.value_and_grad(loss_fn)(
        p, events, labels, cfg, seed, silicon=silicon, noise=noise,
        kwn_relax=kwn_relax, remat=remat)
    opt_m = jax.tree.map(lambda m, gg: 0.9 * m + gg, opt_m, g)
    p = jax.tree.map(lambda pp, m: pp - lr * m, p, opt_m)
    return p, opt_m, loss


def train(cfg: SNNConfig, dataset, n_steps: int = 300, batch: int = 64,
          seed: int = 0, lr: float = 0.05, *, silicon: bool = False,
          noise: ima_lib.IMANoiseModel | None = None,
          kwn_relax: float | None = None, remat: bool = False,
          params=None):
    """Plain SGD-momentum.  NOTE: the quadratic-NLD cell degrades if trained
    far past convergence (ramp-knee gradient spikes), so callers use per-cell
    step budgets (benchmarks/_snn_cache.py) instead of decay/clipping — both
    were tried and slowed the well-behaved cells more than they helped
    (recorded in EXPERIMENTS.md).

    ``silicon=True`` trains through the fused macro kernel with the
    surrogate backward (KWN mode only); with ``noise`` it is noise-aware
    QAT — every optimization step draws a fresh counter seed, so the model
    sees a fresh silicon-noise instance per step.  ``params`` warm-starts
    from an existing parameter tree (the software pre-train -> silicon
    fine-tune recipe of ``examples/train_snn_events.py``); the tree is
    copied first because ``train_step`` donates its arguments.

    Losses are accumulated as device arrays and converted once at the end —
    the old per-step ``float(loss)`` forced a host sync on every iteration,
    serializing dispatch against compute.
    """
    key = jax.random.PRNGKey(seed)
    if params is None:
        p = init_params(cfg, key)
    else:
        p = jax.tree.map(jnp.asarray, params)
        p = jax.tree.map(lambda x: x + 0, p)   # fresh buffers (donation-safe)
    opt_m = jax.tree.map(jnp.zeros_like, p)
    losses = []
    for i in range(n_steps):
        key, sub = jax.random.split(key)
        step_seed = None
        if silicon:
            # Split the *batch* key further rather than consuming more of
            # the main stream: the legacy (software-path) batch sequence
            # for a given seed must stay byte-identical to pre-silicon
            # runs (cached models, recorded accuracies).
            from repro.train import silicon as silicon_lib
            sub, kseed = jax.random.split(sub)
            step_seed = silicon_lib.step_seed(kseed)
        ev, lab = dataset.sample(sub, batch)
        p, opt_m, loss = train_step(p, opt_m, ev, lab, cfg,
                                    jnp.float32(lr), step_seed,
                                    silicon=silicon, noise=noise,
                                    kwn_relax=kwn_relax, remat=remat)
        losses.append(loss)                    # device array: no host sync
    return p, [float(x) for x in jnp.stack(losses)]


def evaluate(p, cfg: SNNConfig, dataset, key: jax.Array, n_batches: int = 10,
             batch: int = 128, **silicon_kwargs):
    accs, teles = [], []
    for i in range(n_batches):
        key, k1, k2 = jax.random.split(key, 3)
        ev, lab = dataset.sample(k1, batch)
        logits, tele = forward_silicon(p, ev, cfg, k2, **silicon_kwargs)
        accs.append(float(jnp.mean(jnp.argmax(logits, -1) == lab)))
        teles.append(tele)
    tele = jax.tree.map(lambda *xs: float(jnp.mean(jnp.stack(xs))), *teles)
    return sum(accs) / len(accs), tele
