"""Plain ``jax.numpy`` reference of the served NLD network (paper Eq. 2).

Independent of the program: nothing here imports it.  The weights are
drawn from the run's key by the program's published initialisation (the
random stream of ``snn.init_params`` and ``dendrite.dendrite_init``, op by
op), so the reference and the program hold the same float weights.  The
semantics are those of the NeuDW-CIM macro's dendritic mode as the program
documents them: J branches per soma, each a sparse ternary MAC on the
twin-cell grid (w = 2 msb + lsb in [-3, 3], one scale per branch and soma),
converted by a 5-bit activation ramp (uniform decisions over +-dend_range,
each level read back as f of it), combined at the soma by the dendritic
weights, into a leaky integrate-and-fire membrane with 12-bit saturation and
reset, and no stochastic lift; the ramp always runs its full 31 steps.  The
readout is the spike-count rate times ``w_out``.

Every function takes ``dt``: float32 is the configuration's precision, and
the correctness control runs the same code in bfloat16.  Matrix products
run at ``highest`` precision in float32.
"""

from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp

DRIVE_GAIN = 0.25
BETA = 0.9
V_TH1 = 1.0
V_RESET = 0.0
V_LIM = float(2 ** 11) / 256.0   # 12-bit signed membrane register
RATIO = 2.0                      # I_MSB / I_LSB

ACTIVATIONS = {
    "relu": lambda x: jnp.maximum(x, 0.0),
    "quadratic": lambda x: 0.5 * x * x,
    "sigmoid4": lambda x: 4.0 * jax.nn.sigmoid(x),
}


def precision(dt):
    """The matmul precision the reference runs at for ``dt``."""
    if dt == jnp.float32:
        return jax.default_matmul_precision("highest")
    return contextlib.nullcontext()


def init_params(cfg: dict, key: jax.Array) -> dict:
    """Branch synapses (masked to a fan-in of ``branch_fanin_frac``, times
    ``dendrite_gain`` over the square root of the fan-in), dendritic
    weights 1/sqrt(J) and a 1/sqrt(n) readout, drawn from ``key``."""
    n_in, n = cfg["n_in"], cfg["hidden_layers"][-1]
    j, frac = cfg["n_branches"], cfg["branch_fanin_frac"]
    k1, _, k3 = jax.random.split(key, 3)
    w_out = jax.random.normal(k3, (n, cfg["n_classes"])) / jnp.sqrt(n)
    km, ks, kd = jax.random.split(k1, 3)
    mask = (jax.random.uniform(km, (j, n_in, n)) < frac).astype(jnp.float32)
    fan_in = max(1.0, n_in * frac)
    w_syn = cfg["dendrite_gain"] * jax.random.normal(ks, (j, n_in, n)) \
        / jnp.sqrt(fan_in)
    w_dend = jax.random.normal(kd, (j, n)) / jnp.sqrt(float(j))
    return {"w_syn": w_syn * mask, "w_dend": w_dend, "w_out": w_out}


def codebook(cfg: dict):
    """(levels (2**bits,), boundaries) of the activation ramp."""
    r = cfg["dend_range"]
    grid = jnp.linspace(-r, r, 2 ** cfg["code_bits"])
    return ACTIVATIONS[cfg["activation"]](grid), 0.5 * (grid[1:] + grid[:-1])


def serve(params, events, cfg: dict, dt=jnp.float32):
    """Served answers for a batch of requests, events (B, T, N_in).

    Returns (logits (B, C) f32, mean ramp steps per time step (B,) f32).
    """
    with precision(dt):
        w_syn = params["w_syn"]                                # (J, I, N)
        scale = jnp.maximum(jnp.max(jnp.abs(w_syn), axis=1) / 3.0, 1e-8)
        w_int = jnp.round(jnp.clip(w_syn / scale[:, None, :], -3, 3))
        msb = jnp.clip(jnp.round(w_int / 2.0), -1.0, 1.0)
        w_cell = (RATIO * msb + (w_int - 2.0 * msb)).astype(dt)
        levels, bounds = codebook(cfg)
        lv, sc = levels.astype(dt), scale.astype(dt)
        w_dend = params["w_dend"].astype(dt)

        def step(v, x):
            drive = jnp.zeros(v.shape, dt)
            for j in range(w_cell.shape[0]):
                mac = (x @ w_cell[j]) * sc[j]
                act = lv[jnp.searchsorted(bounds, mac.astype(jnp.float32))]
                drive = drive + act * w_dend[j]
            v = jnp.clip(dt(BETA) * v + drive * dt(DRIVE_GAIN),
                         dt(-V_LIM), dt(V_LIM))
            spike = v >= dt(V_TH1)
            return jnp.where(spike, dt(V_RESET), v), spike.astype(dt)

        x = jnp.moveaxis(events, 1, 0).astype(dt)
        t, b = x.shape[0], x.shape[1]
        v0 = jnp.zeros((b, w_cell.shape[-1]), dt)
        _, spikes = jax.lax.scan(step, v0, x)
        counts = jnp.sum(spikes, axis=0)
        logits = (counts / dt(t)) @ params["w_out"].astype(dt)
        steps = jnp.full((b,), 2 ** cfg["code_bits"] - 1, jnp.float32)
        return logits.astype(jnp.float32), steps
