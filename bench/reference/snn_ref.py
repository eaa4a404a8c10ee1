"""Plain ``jax.numpy`` reference of the served and trained SNN.

Independent of the program: nothing here imports it, and every input is
made here or by the benchmark's own generator — the weights from the seed
by the published initialisation, the ramp codebook, the PRBS and counter
noise streams.  The semantics are those of the NeuDW-CIM macro as the
program documents them: a twin-cell ternary MAC (w = 2 msb + lsb on a
[-3, 3] grid with a per-column scale), a 5-bit NLQ ramp with LUT map-back,
K-winners-take-all by code with ties to the lower column, a leaky
integrate-and-fire membrane with stochastic near-threshold lift (SNL),
12-bit membrane saturation and reset, and a spike-count readout.  Training
differentiates the same forward with the surrogate chain (SuperSpike
spike, straight-through ramp window, relaxed KWN gate, hard rail cut).

Every function takes ``dt``: float32 is the configuration's precision, and
the correctness control runs the same code in bfloat16.  Matrix products
run at ``highest`` precision in float32.
"""

from __future__ import annotations

import contextlib
import math

import jax
import jax.numpy as jnp
import numpy as np

CODE_BITS = 5
MAC_RANGE = 24.0           # NLQ full scale, in integer MAC units
NLQ_GAMMA = 2.0
DRIVE_GAIN = 0.25
BETA = 0.9
V_TH1 = 1.0
V_TH2 = 0.6
V_RESET = 0.0
V_LIM = float(2 ** 11) / 256.0   # 12-bit signed membrane register
SNL_AMP = 0.05
RATIO = 2.0                # I_MSB / I_LSB
KWN_RELAX = 0.1            # loser-gradient leak through the winner gate
SURROGATE_BETA = 4.0
IMA_NOISE = {"offset_lsb": 0.45, "sigma_lsb": 1.35, "inl_lsb": 0.56}
TAG_IMA = 0x494D4101
TAG_SNL = 0x534E4C01


def precision(dt):
    """The matmul precision the reference runs at for ``dt``."""
    if dt == jnp.float32:
        return jax.default_matmul_precision("highest")
    return contextlib.nullcontext()


# ---------------------------------------------------------------------------
# Weights and codebook
# ---------------------------------------------------------------------------

def init_params(cfg: dict, key: jax.Array) -> dict:
    """Gaussian fan-in-scaled hidden weights (x 3) and a 1/sqrt(n) readout,
    drawn from ``key`` op by op, as the program's published init does."""
    widths = list(cfg["hidden_layers"])
    k1, _, k3 = jax.random.split(key, 3)
    w_out = jax.random.normal(k3, (widths[-1], cfg["n_classes"])) \
        / jnp.sqrt(widths[-1])
    if len(widths) == 1:
        w_hid = [jax.random.normal(k1, (cfg["n_in"], widths[0]))
                 / jnp.sqrt(cfg["n_in"]) * 3.0]
    else:
        fan_ins = [cfg["n_in"]] + widths[:-1]
        keys = jax.random.split(k1, len(widths))
        w_hid = [jax.random.normal(kk, (f, w)) / jnp.sqrt(f) * 3.0
                 for kk, f, w in zip(keys, fan_ins, widths)]
    return {"w_hid": w_hid, "w_out": w_out}


def quantize_3bit(w):
    """Per-column symmetric quantization onto the twin-cell [-3, 3] grid."""
    scale = jnp.maximum(jnp.max(jnp.abs(w), axis=0, keepdims=True) / 3.0,
                        1e-8)
    return jnp.round(jnp.clip(w / scale, -3, 3)), scale


def twin_cell(w_int):
    """The weight the array realises: 2 msb + lsb, balanced-ternary digits."""
    w = jnp.round(jnp.clip(w_int, -3, 3))
    msb = jnp.clip(jnp.round(w / 2.0), -1.0, 1.0)
    return RATIO * msb + (w - 2.0 * msb)


def nlq_codebook():
    """(levels (32,), boundaries (31,)) of the square-law companding ramp."""
    n = 2 ** CODE_BITS
    u = jnp.linspace(-1.0, 1.0, n)
    comp = jnp.sign(u) * (jnp.abs(u) ** NLQ_GAMMA)
    levels = 0.0 + MAC_RANGE * comp
    return levels, 0.5 * (levels[1:] + levels[:-1])


# ---------------------------------------------------------------------------
# Noise streams
# ---------------------------------------------------------------------------

_PRBS_PERIOD = (1 << 15) - 1


def _prbs15_period() -> np.ndarray:
    """One period of the PRBS-15 (x^15 + x^14 + 1) LFSR output from the
    state a freshly initialised membrane register holds (2)."""
    s, out = 2, np.empty(_PRBS_PERIOD, np.float32)
    for i in range(_PRBS_PERIOD):
        fb = ((s >> 14) ^ (s >> 13)) & 1
        s = ((s << 1) | fb) & 0x7FFF
        out[i] = fb
    return out


_PRBS_BITS: np.ndarray | None = None


def prbs_noise(n_steps: int, rows: int, width: int) -> np.ndarray:
    """SNL noise (T, rows, width) in {-amp, +amp}: one LFSR drawing
    ``rows * width`` bits per step, row-major."""
    global _PRBS_BITS
    if _PRBS_BITS is None:
        _PRBS_BITS = _prbs15_period()
    n = n_steps * rows * width
    idx = np.arange(n) % _PRBS_PERIOD
    bits = _PRBS_BITS[idx].reshape(n_steps, rows, width)
    return (2.0 * bits - 1.0) * np.float32(SNL_AMP)


def _rotl(x, r):
    return (x << jnp.uint32(r)) | (x >> jnp.uint32(32 - r))


def threefry2x32(k0, k1, c0, c1):
    """Threefry-2x32, 20 rounds."""
    k0 = jnp.asarray(k0).astype(jnp.uint32)
    k1 = jnp.asarray(k1).astype(jnp.uint32)
    ks = (k0, k1, k0 ^ k1 ^ jnp.uint32(0x1BD11BDA))
    x0 = jnp.asarray(c0).astype(jnp.uint32) + k0
    x1 = jnp.asarray(c1).astype(jnp.uint32) + k1
    for i in range(5):
        for r in ((13, 15, 26, 6) if i % 2 == 0 else (17, 29, 16, 24)):
            x0 = x0 + x1
            x1 = _rotl(x1, r) ^ x0
        x0 = x0 + ks[(i + 1) % 3]
        x1 = x1 + ks[(i + 2) % 3] + jnp.uint32(i + 1)
    return x0, x1


def _unit_open(bits):
    hi24 = (bits >> jnp.uint32(8)).astype(jnp.int32).astype(jnp.float32)
    return (hi24 + jnp.float32(0.5)) * jnp.float32(2.0 ** -24)


def _ids(shape):
    return (jax.lax.broadcasted_iota(jnp.int32, shape, 0),
            jax.lax.broadcasted_iota(jnp.int32, shape, 1))


def counter_normal(seed, step, shape, tag):
    """Box-Muller Gaussian keyed on (seed, step, row, column)."""
    rows, cols = _ids(shape)
    k1 = jnp.uint32(tag) ^ jnp.asarray(step).astype(jnp.uint32)
    b0, b1 = threefry2x32(seed, k1, rows, cols)
    r = jnp.sqrt(jnp.float32(-2.0) * jnp.log(_unit_open(b0)))
    return r * jnp.cos(jnp.float32(2.0 * math.pi) * _unit_open(b1))


def counter_sign(seed, step, shape, tag):
    rows, cols = _ids(shape)
    k1 = jnp.uint32(tag) ^ jnp.asarray(step).astype(jnp.uint32)
    b0, _ = threefry2x32(seed, k1, rows, cols)
    return (b0 & jnp.uint32(1)).astype(jnp.int32).astype(jnp.float32) \
        * 2.0 - 1.0


def noisy_codes(codes, mac, seed, step, n_codes):
    """Fig. 7 conversion error in code space: INL sinusoid over the input
    range, comparator offset and thermal noise, rounded and clipped."""
    p = IMA_NOISE
    u = (mac - jnp.float32(-MAC_RANGE)) / jnp.float32(2 * MAC_RANGE + 1e-9)
    inl = jnp.float32(p["inl_lsb"]) * jnp.sin(jnp.float32(2.0 * math.pi) * u)
    g = counter_normal(seed, step, mac.shape, TAG_IMA)
    eps = jnp.float32(p["offset_lsb"]) + jnp.float32(p["sigma_lsb"]) * g
    code = jnp.round(codes.astype(jnp.float32) + inl + eps)
    return jnp.clip(code.astype(jnp.int32), 0, n_codes - 1)


# ---------------------------------------------------------------------------
# Forward (serving)
# ---------------------------------------------------------------------------

def kwn_winners(codes, k):
    """Top-k columns by code, ties to the lower column; (mask, ramp steps)."""
    n = codes.shape[-1]
    tie = jnp.arange(n, dtype=jnp.float32) * (0.5 / n)
    _, idx = jax.lax.top_k(codes.astype(jnp.float32) - tie, k)
    kth = jnp.take_along_axis(codes, idx, axis=-1)[..., -1]
    mask = jnp.clip(jnp.sum(jax.nn.one_hot(idx, n, dtype=jnp.float32), -2),
                    0.0, 1.0)
    return mask, (2 ** CODE_BITS - 1 - kth).astype(jnp.int32)


def layer_seq(x, w_int, scale, v, noise, k, dt):
    """One KWN layer over a time-major sequence x (T, B, K) with pre-drawn
    SNL noise (T, B, N); returns (spikes (T, B, N), ramp steps (T, B))."""
    levels, bounds = nlq_codebook()
    w = twin_cell(w_int).astype(dt)
    lv, sc = levels.astype(dt), scale.astype(dt)

    def step(v, inp):
        xt, nt = inp
        mac = (xt.astype(dt) @ w).astype(jnp.float32)
        codes = jnp.searchsorted(bounds, mac).astype(jnp.int32)
        mask, steps = kwn_winners(codes, k)
        drive = jnp.take(lv, codes) * sc * mask.astype(dt) * dt(DRIVE_GAIN)
        v2 = jnp.where(mask > 0, dt(BETA) * v + drive, v)
        snl = (v2 > dt(V_TH2)) & (v2 < dt(V_TH1))
        v2 = jnp.where(snl, v2 + nt.astype(dt), v2)
        v2 = jnp.clip(v2, dt(-V_LIM), dt(V_LIM))
        spk = v2 >= dt(V_TH1)
        return jnp.where(spk, dt(V_RESET), v2), (spk.astype(dt), steps)

    _, (spk, steps) = jax.lax.scan(step, v.astype(dt), (x, noise))
    return spk, steps


def serve(params, events, noises, k_layers, dt=jnp.float32):
    """Served answers for a batch of requests.

    events (B, T, N_in); ``noises`` per layer (T, B, width) SNL streams.
    Returns (logits (B, C) f32, mean ramp steps per time step (B,) f32).
    """
    with precision(dt):
        x = jnp.moveaxis(events, 1, 0)
        t = x.shape[0]
        adc = jnp.zeros(x.shape[1], jnp.float32)
        for w_hid, noise, k in zip(params["w_hid"], noises, k_layers):
            w_int, scale = quantize_3bit(w_hid)
            v0 = jnp.zeros((x.shape[1], w_hid.shape[1]), dt)
            x, steps = layer_seq(x, w_int, scale.reshape(-1), v0, noise, k,
                                 dt)
            adc = adc + jnp.sum(steps.astype(jnp.float32), axis=0)
        counts = jnp.sum(x, axis=0)
        logits = (counts / dt(t)) @ params["w_out"].astype(dt)
        return logits.astype(jnp.float32), adc / jnp.float32(t)


# ---------------------------------------------------------------------------
# Training: the differentiable forward and three steps of SGD momentum
# ---------------------------------------------------------------------------

def _ste(exact, surrogate):
    sg = jax.lax.stop_gradient
    return sg(exact) + (surrogate - sg(surrogate))


@jax.custom_vjp
def _spike(v, v_th):
    return (v >= v_th).astype(v.dtype)


def _spike_fwd(v, v_th):
    return _spike(v, v_th), (v, v_th)


def _spike_bwd(res, g):
    v, v_th = res
    x = SURROGATE_BETA * (v - v_th)
    return g * (SURROGATE_BETA / (1.0 + jnp.abs(x)) ** 2), \
        jnp.zeros_like(v_th)


_spike.defvjp(_spike_fwd, _spike_bwd)


@jax.custom_vjp
def _rail(v, lim):
    return jnp.clip(v, -lim, lim)


def _rail_fwd(v, lim):
    out = _rail(v, lim)
    return out, (out, lim)


def _rail_bwd(res, g):
    out, lim = res
    return g * (jnp.abs(out) < lim).astype(g.dtype), jnp.zeros_like(lim)


_rail.defvjp(_rail_fwd, _rail_bwd)


def train_logits(params, events, seed_f, k, dt=jnp.float32):
    """Differentiable silicon forward with the Fig. 7 error model and
    counter SNL noise keyed on ``seed_f``: events (B, T, N) -> logits."""
    sg = jax.lax.stop_gradient
    w_hid = params["w_hid"][0].astype(dt)
    w_int, scale2 = quantize_3bit(w_hid)
    scale2 = sg(scale2)
    w_sur = w_hid / scale2
    clip_mask = (jnp.abs(w_sur) <= 3.5).astype(dt)
    w = sg(w_int) + (w_sur - sg(w_sur)) * clip_mask
    scale = scale2.reshape(-1)
    w_exact = twin_cell(sg(w))
    levels, bounds = nlq_codebook()
    lv = levels.astype(dt)
    seed = seed_f.astype(jnp.int32)
    x = jnp.moveaxis(events, 1, 0).astype(dt)
    t_steps, b = x.shape[0], x.shape[1]
    n_codes = 2 ** CODE_BITS
    lo, hi = float(-MAC_RANGE - 0.5), float(MAC_RANGE + 0.5)

    def step(v, inp):
        t, xt = inp
        mac_e = xt @ w_exact
        mac = _ste(mac_e, xt @ w)
        mac_f = sg(mac_e).astype(jnp.float32)
        codes = jnp.searchsorted(bounds, mac_f).astype(jnp.int32)
        codes = noisy_codes(codes, mac_f, seed, t, n_codes)
        maskf, _ = kwn_winners(codes, k)
        maskf = sg(maskf).astype(dt)
        drive_exact = jnp.take(lv, codes) * sg(scale) * maskf * dt(DRIVE_GAIN)
        window = sg(((mac_e >= lo) & (mac_e <= hi)).astype(dt))
        drive_sur = mac * sg(scale) * dt(DRIVE_GAIN) * window
        drive = _ste(drive_exact, drive_sur)
        leak = dt(KWN_RELAX) * drive_sur
        v_lose = v + (leak - sg(leak))
        v2 = jnp.where(maskf > 0, dt(BETA) * v + drive, v_lose)
        nz = (jnp.float32(SNL_AMP)
              * counter_sign(seed, t, v2.shape, TAG_SNL)).astype(dt)
        snl = (sg(v2) > dt(V_TH2)) & (sg(v2) < dt(V_TH1))
        v2 = jnp.where(snl, v2 + nz, v2)
        v_clip = _rail(v2, dt(V_LIM))
        s = _spike(v_clip, dt(V_TH1))
        return jnp.where(sg(s) > 0, dt(V_RESET), v_clip), s

    v0 = jnp.zeros((b, w.shape[1]), dt)
    _, spk = jax.lax.scan(step, v0, (jnp.arange(t_steps, dtype=jnp.int32), x))
    counts = jnp.sum(spk, axis=0)
    return ((counts / dt(t_steps)) @ params["w_out"].astype(dt)) \
        .astype(jnp.float32)


def train_loss(params, events, labels, seed_f, k, dt=jnp.float32):
    lse = jax.nn.log_softmax(train_logits(params, events, seed_f, k, dt))
    return -jnp.mean(jnp.take_along_axis(lse, labels[:, None], 1))


def train_steps(params, batches, k, lr, dt=jnp.float32):
    """SGD with momentum 0.9 over ``batches`` [(events, labels, seed_f)].

    Returns (losses, first gradient, parameters after the last step), each
    on the host.  Parameters are ``{"w_hid": [...], "w_out": ...}``.
    """
    with precision(dt):
        grad = jax.jit(jax.value_and_grad(train_loss),
                       static_argnames=("k", "dt"))
        m = jax.tree.map(jnp.zeros_like, params)
        losses, g1 = [], None
        for ev, lab, seed_f in batches:
            loss, g = grad(params, ev, lab, seed_f, k=k, dt=dt)
            if g1 is None:
                g1 = jax.device_get(g)
            m = jax.tree.map(lambda mm, gg: 0.9 * mm + gg, m, g)
            params = jax.tree.map(lambda p, mm: p - lr * mm, params, m)
            losses.append(float(loss))
        return losses, g1, jax.device_get(params)
