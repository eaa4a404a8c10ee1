"""The serving driver's arithmetic, on a fake engine: the closed loop's
rate over the whole window, a stall inside it, a request that never
returns."""

import contextlib
import time
from types import SimpleNamespace

import numpy as np

from bench import harness

serve = harness.load_module(harness.BENCH, "drivers", "serve.py")


class _Counter:
    value = 0


class FakeEngine:
    """Answers everything queued before each call; the first call stalls."""

    continuous, b, round_steps = True, 4, 8

    def __init__(self, stall_s, tick_s):
        self.pending, self.stall_s, self.tick_s = [], stall_s, tick_s
        self.calls, self.active = 0, 0
        self.metrics = SimpleNamespace(counter=lambda name: _Counter())

    def submit(self, r):
        self.pending.append(r)
        return r

    def run(self, max_rounds=None):
        time.sleep(self.stall_s if self.calls == 0 else self.tick_s)
        self.calls += 1
        out, self.pending = self.pending, []
        for r in out:
            r.logits, r.adc_steps = np.zeros(2), 0.0
        return out


def _ctx(kind, seconds, **traffic):
    return SimpleNamespace(
        traffic=dict(kind=kind, drain_s=5.0, **traffic), seconds=seconds,
        seed=5, cfg={"n_in": 3, "n_steps": 2, "hidden_layers": [4]}, span=lambda name: contextlib.nullcontext())


def _st(ctx, eng):
    return SimpleNamespace(
        eng=eng, pool=np.zeros((4, 2, 3), np.float32), which={}, row={},
        due={}, sent={}, inflight={}, next_uid=0, tracer=None, order=np.zeros(1 << 12, np.int64),
        src=serve.arrivals(ctx),
        Req=lambda uid, events: SimpleNamespace(uid=uid, events=events,
                                                logits=None))


def test_request_that_never_returns_is_failed():
    eng = FakeEngine(0.0, 0.002)
    real_run = eng.run

    def drop_one(max_rounds=None):
        out = real_run(max_rounds)
        return [r for r in out if r.uid != 7]
    eng.run = drop_one
    ctx = _ctx("closed", 0.2, clients=4)
    ctx.traffic["drain_s"] = 0.1
    rec = serve.window(ctx, _st(ctx, eng))
    assert rec["failed"] == 1
    assert 7 not in {r.uid for r in rec["answered"]}
    assert rec["attempted"] == len(rec["answered"]) + 1


def test_closed_loop_rate_counts_a_stall():
    """A stalled call stays in the window: the rate falls with it."""
    ctx = _ctx("closed", 0.5, clients=8)
    rec = serve.window(ctx, _st(ctx, FakeEngine(0.3, 0.01)))
    n = rec["returned_in_window"]
    assert rec["window_s"] >= 0.5
    assert serve.end_to_end(ctx, rec)["serve_rps"] == n / rec["window_s"]
    assert n <= 8 * (1 + 0.2 / 0.01 + 1)


def test_closed_loop_rate_covers_the_last_call():
    ctx = _ctx("closed", 0.3, clients=8)
    st = _st(ctx, FakeEngine(0.0, 0.01))
    rec = serve.window(ctx, st)
    assert rec["window_s"] >= 0.3
    n = rec["returned_in_window"]
    assert n % 8 == 0 and n >= 8 * 20
    assert len(rec["answered"]) == n and rec["failed"] == 0
    rps = serve.end_to_end(ctx, rec)["serve_rps"]
    assert rps == n / rec["window_s"]
    assert 400 < rps < 900          # 8 per ~10 ms tick
