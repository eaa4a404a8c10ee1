"""The correctness check: the sound program passes it, the bfloat16
control fails it, and so does each fault a cell can have.

Runs on the CPU (kernels in the Pallas interpreter) at sizes a test run
can hold; the harness's look for a chip is skipped, the rest of a run is
driven as on the chip."""

import jax
import jax.numpy as jnp
import pytest

from bench import control, harness

BACKLOG = {
    "cfg": {"n_in": 72, "n_steps": 4},
    "traffic": {"pool": 16, "clients": 16, "warmup_requests": 16},
    "spec": {"engine": {"batch_slots": 8, "round_steps": 2}}}
# case -> (cell, overrides)
TINY = {
    "nmnist-kwn.serve-backlog": ("nmnist-kwn.serve-backlog", BACKLOG),
    "dvs-stack.serve-backlog": ("dvs-stack.serve-backlog", {
        "cfg": {"n_in": 72, "n_steps": 4, "hidden_layers": [32, 16]},
        "traffic": {"pool": 16, "clients": 16, "warmup_requests": 16},
        "spec": {"engine": {"batch_slots": 8}}}),
    "nmnist-kwn.train-silicon": ("nmnist-kwn.train-silicon", {
        "cfg": {"n_in": 72, "n_steps": 4}, "traffic": {"batch": 8}}),
}
SEED = 2 ** 31 + 3


def _run(case, seconds=1.0):
    jax.clear_caches()
    cell, overrides = TINY[case]
    return harness.run(cell, SEED, seconds, False, require_chip=False,
                       overrides=overrides, log=lambda s: None)


@pytest.mark.parametrize("case", sorted(TINY))
def test_sound_run_is_correct(case):
    out = _run(case)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0


@pytest.mark.parametrize("cell", sorted({c for c, _ in TINY.values()}))
def test_bfloat16_control_fails(cell):
    """The control at the cells' own widths, on a smaller pool."""
    over = {"traffic": {"pool": 64}} if "serve" in cell else None
    c = harness.Cell(cell, over)
    got = control.readings(c, SEED)
    assert any(v > c.spec["limits"][k] for k, v in got.items()), got


# -- faults planted in the timed path ---------------------------------------

def _alter_answer(monkeypatch):
    """Some answers altered where the engine produces them: every fifth
    request, so the window has some whatever set-up already answered."""
    from repro.serve import engine
    for name in ("_evict", "_run_batch"):
        real = getattr(engine.SNNEventEngine, name)

        def wrapped(self, *a, _real=real):
            out = _real(self, *a)
            for r in out:
                if r.uid % 5 == 3:
                    r.logits = r.logits.at[0].add(0.25)
            return out
        monkeypatch.setattr(engine.SNNEventEngine, name, wrapped)


def _half_batch(monkeypatch):
    """Half of each launch's rows computed on no input."""
    from repro.models import snn
    from repro.serve import engine

    def halve(ev, axis):
        keep = jnp.arange(ev.shape[axis]) >= ev.shape[axis] // 2
        shape = [1] * ev.ndim
        shape[axis] = -1
        return ev * keep.reshape(shape)

    real_stream = snn.forward_silicon_stream
    monkeypatch.setattr(snn, "forward_silicon_stream",
                        lambda p, ev, cfg, st, noise=None: real_stream(
                            p, halve(ev, 1), cfg, st, noise=noise))
    real_fwd = engine._legacy_forward
    monkeypatch.setattr(engine, "_legacy_forward", lambda cfg, fused, noise:
                        (lambda p, ev, key: real_fwd(cfg, fused, noise)(
                            p, halve(ev, 0), key)))


def _train_state_unchanged(monkeypatch):
    from repro.models import snn

    def frozen(p, m, ev, lab, cfg, lr, seed=None, **kw):
        kw.pop("remat", None)
        return p, m, snn.loss_fn(p, ev, lab, cfg, seed, **kw)
    monkeypatch.setattr(snn, "train_step", frozen)


def _train_half_batch(monkeypatch):
    from repro.models import snn
    real = snn.train_step

    def half(p, m, ev, lab, cfg, lr, seed=None, **kw):
        h = ev.shape[0] // 2
        return real(p, m, ev[:h], lab[:h], cfg, lr, seed, **kw)
    monkeypatch.setattr(snn, "train_step", half)


FAULTS = [
    ("nmnist-kwn.serve-backlog", _alter_answer),
    ("nmnist-kwn.serve-backlog", _half_batch),
    ("dvs-stack.serve-backlog", _alter_answer),
    ("dvs-stack.serve-backlog", _half_batch),
    ("nmnist-kwn.train-silicon", _train_state_unchanged),
    ("nmnist-kwn.train-silicon", _train_half_batch),
]


@pytest.mark.parametrize("case,fault", FAULTS,
                         ids=[f"{c}-{f.__name__[1:]}" for c, f in FAULTS])
def test_fault_is_caught(case, fault, monkeypatch):
    fault(monkeypatch)
    out = _run(case)
    assert not out["correct"], out["checks"]
