"""What the serving and training drivers share."""

from __future__ import annotations

import time

import jax

from bench.traffic import generator


def weight_key(seed: int) -> jax.Array:
    """The key the run's weights are drawn from."""
    return jax.random.fold_in(jax.random.PRNGKey(generator.seed32(seed)), 0)


def snn_config(snn, cfg: dict):
    """The program's ``SNNConfig`` for a configuration file."""
    widths = tuple(cfg["hidden_layers"])
    return snn.SNNConfig(
        n_in=cfg["n_in"], n_hidden=widths[-1], n_classes=cfg["n_classes"],
        n_steps=cfg["n_steps"], mode=cfg["mode"], k=cfg["k"],
        hidden_layers=widths if len(widths) > 1 else None)


def layer_shapes(cfg: dict) -> list:
    """[[fan_in, width], ...] of the hidden layers."""
    widths = list(cfg["hidden_layers"])
    return [[f, w] for f, w in zip([cfg["n_in"]] + widths[:-1], widths)]


def check(name: str, value: float, limits: dict) -> dict:
    return {"value": float(value), "limit": float(limits[name])}


class Longest:
    """The longest engine call of a window, as ``(wall s, process CPU s,
    start s after the window opened)``: a run that reads far off shows
    whether one call stalled, and whether the process was busy meanwhile."""

    def __init__(self, t0: float):
        self.t0, self.best = t0, (0.0, 0.0, 0.0)

    def call(self, fn, *args, **kw):
        w, c = time.perf_counter(), time.process_time()
        out = fn(*args, **kw)
        dw = time.perf_counter() - w
        if dw > self.best[0]:
            self.best = (dw, time.process_time() - c, w - self.t0)
        return out
