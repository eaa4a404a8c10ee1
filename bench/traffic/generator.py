"""The benchmark's one traffic generator: event streams and their arrivals.

Event streams are a copy of the program's synthetic event-camera data
(class prototypes on a two-polarity retina, sampled as ON/OFF events with
a per-sample gain and background noise), kept here so that no change to
the program can move the benchmark's inputs.  The retina side is
``sqrt(n_in / 2)``, so a configuration at a sensor's pixel count gets
that sensor's geometry.  ``motion`` drifts the prototypes over time (the
gesture-like streams).

A mix file, ``bench/traffic/<mix>.json``, holds the mix's parameters; a
serving mix names its arrival kind (``"kind"``), the module
``bench/traffic/<kind>.py`` that says when each request falls due.

Everything is drawn from the run's seed.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def seed32(seed: int) -> int:
    """The low 32 bits of a run seed, for JAX keys (seeds may exceed 2**31)."""
    return int(seed) & 0xFFFFFFFF


def _prototypes(ev: dict, n_in: int, n_steps: int,
                n_classes: int) -> np.ndarray:
    """Class prototype intensity maps in [-1, 1], (classes, T, N)."""
    rng = np.random.default_rng(ev["proto_seed"] + 1234)
    protos = np.zeros((n_classes, n_steps, n_in), np.float32)
    side = int(np.sqrt(n_in // 2))
    motion = bool(ev["motion"])
    for c in range(n_classes):
        n_blobs = 2 + (c % 3)
        xy = rng.uniform(2, side - 2, (n_blobs, 2))
        vel = (rng.uniform(-0.4, 0.4, (n_blobs, 2)) if motion
               else np.zeros((n_blobs, 2)))
        vel += (c % 4 - 1.5) * 0.1 * motion
        for t in range(n_steps):
            grid = np.zeros((side, side, 2), np.float32)
            for b in range(n_blobs):
                cx, cy = xy[b] + vel[b] * t
                ys, xs = np.mgrid[0:side, 0:side]
                blob = np.exp(-(((xs - cx) ** 2 + (ys - cy) ** 2)
                                / (2.0 + 0.5 * b)))
                grid[:, :, b % 2] += blob
            grid[:, :, 1] *= -1.0          # channel 1 carries OFF polarity
            protos[c, t, : side * side * 2] = grid.reshape(-1)[:n_in]
    peak = np.abs(protos).max(axis=(1, 2), keepdims=True) + 1e-6
    protos = protos / peak
    bg = protos.mean(axis=0, keepdims=True)
    bg = bg / (np.abs(bg).max() + 1e-6)
    return ev["alpha"] * protos + (1 - ev["alpha"]) * bg


class EventStreams:
    """Seeded ternary event streams (B, T, n_in) in {-1, 0, +1} for one
    configuration, made on the device in one jitted call."""

    def __init__(self, cfg: dict):
        self.n_in, self.n_steps = cfg["n_in"], cfg["n_steps"]
        self.n_classes = cfg["n_classes"]
        self.ev = cfg["events"]
        self.protos = jnp.asarray(_prototypes(
            self.ev, self.n_in, self.n_steps, self.n_classes))

    @functools.partial(jax.jit, static_argnums=(0, 2))
    def sample(self, key: jax.Array, batch: int):
        """(events (B, T, N) f32 in {-1, 0, 1}, labels (B,) int32)."""
        rate, noise_frac = self.ev["rate"], self.ev["noise_frac"]
        k1, k2, k3, k4 = jax.random.split(key, 4)
        labels = jax.random.randint(k1, (batch,), 0, self.n_classes)
        proto = self.protos[labels]
        gain = jax.random.uniform(k2, (batch, 1, 1), minval=0.7, maxval=1.3)
        p_evt = jnp.abs(proto) * gain * (rate / jnp.maximum(
            jnp.mean(jnp.abs(proto)), 1e-6))
        u = jax.random.uniform(k3, proto.shape)
        fire = (u < jnp.clip(p_evt, 0, 0.9)).astype(jnp.float32)
        pol = jnp.sign(proto)
        noise_u = jax.random.uniform(k4, proto.shape)
        noise = ((noise_u < rate * noise_frac).astype(jnp.float32)
                 * jnp.sign(noise_u - 0.5))
        return jnp.clip(fire * pol + noise, -1, 1), labels

    def __hash__(self):
        return id(self)


POOL_CHUNK = 64


def pool(cfg: dict, seed: int, size: int) -> np.ndarray:
    """The run's request pool: ``size`` distinct streams, on the host.

    Drawn ``POOL_CHUNK`` streams per jitted call (one shape), so that a
    pool at a large sensor never needs several copies of itself on the
    device at once."""
    chunk = min(size, POOL_CHUNK)
    if size % chunk:
        raise ValueError(f"pool size {size} is not a multiple of {chunk}")
    base = jax.random.fold_in(jax.random.PRNGKey(seed32(seed)), 1)
    streams = EventStreams(cfg)
    return np.concatenate([
        np.asarray(streams.sample(jax.random.fold_in(base, i), chunk)[0])
        for i in range(size // chunk)])


def pool_order(seed: int, pool_size: int, n: int) -> np.ndarray:
    """Which pool stream each successive request carries."""
    rng = np.random.default_rng([int(seed), 3])
    return rng.integers(0, pool_size, n)
