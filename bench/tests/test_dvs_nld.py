"""The ``dvs-nld.serve-backlog`` cell on the CPU: its reference against the
program, its kernel's operation and byte counts, its three readers, and its
check, which the sound program passes and the bfloat16 control, a dropped
dendritic branch and silent output each fail.  Sizes are cut to what a
test run can hold; everything else runs as on the chip."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import control, harness, trace_reduce
from bench.drivers import common
from bench.reference import nld_ref

CELL = "dvs-nld.serve-backlog"
SEED = 2 ** 31 + 5
TINY = {"cfg": {"n_in": 128, "n_steps": 5},
        "traffic": {"pool": 16, "clients": 16, "warmup_requests": 16},
        "spec": {"engine": {"batch_slots": 8, "round_steps": 2}}}
MS = 1_000_000      # ns


def _cell(overrides=TINY):
    return harness.Cell(CELL, overrides)


def _run(seconds=1.0):
    jax.clear_caches()
    return harness.run(CELL, SEED, seconds, False, require_chip=False,
                       overrides=TINY, log=lambda s: None)


# -- the reference against the program ---------------------------------------

@pytest.mark.parametrize("activation", ["relu", "quadratic"])
def test_reference_matches_the_program(activation):
    from repro.models import snn
    cell = _cell({**TINY, "cfg": dict(TINY["cfg"], activation=activation)})
    cfg, drv = cell.cfg, cell.driver
    key = common.weight_key(SEED)
    mine, prog = nld_ref.init_params(cfg, key), drv.init_params(cfg, key)
    assert jnp.array_equal(mine["w_syn"], prog["dend"].w_syn)
    assert jnp.array_equal(mine["w_dend"], prog["dend"].w_dend)
    assert jnp.array_equal(mine["w_out"], prog["w_out"])
    from bench.traffic import generator
    pool = generator.pool(cfg, SEED, 16)
    logits, tele = snn.forward_silicon(
        prog, jnp.asarray(pool), drv.snn_config(snn, cfg),
        jax.random.PRNGKey(0), fused="seq")
    ref_logits, ref_adc = drv.reference(cfg, SEED, pool, np.arange(16),
                                        None, 8)
    gaps = drv.compare(np.asarray(logits), np.asarray(tele["adc_steps"]),
                       ref_logits, ref_adc, cfg["n_steps"])
    assert gaps["logit_gap"] <= 1e-6 and gaps["adc_gap"] == 0, gaps
    assert gaps["silent_share"] < 0.5, gaps


# -- the kernel's cost ------------------------------------------------------

def test_fused_seq_nld_counts_by_hand():
    cost = harness.load_module(harness.BENCH, "costs", "fused_seq_nld.py")
    s = {"t": 8, "m": 64, "k": 32768, "n": 128, "branches": 2, "codes": 32}
    assert cost.ops(s) == 2 * 8 * 64 * 32768 * 256
    events = 8 * 64 * 32768                  # int8
    planes = 2 * 32768 * 256                 # msb and lsb, int8
    scales, codebook = 4 * 256, 4 * (32 + 31)
    w_dend, ctl = 4 * 2 * 128, 4 * 2
    membrane = 4 * 64 * 128 * 2
    noise = 4 * 8 * 64 * 128
    outs = 4 * 8 * 64 * 128 * 2 + 4 * 8 * 64
    assert cost.nbytes(s) == (events + planes + scales + codebook + w_dend
                              + ctl + membrane + noise + outs)


def test_the_kernel_is_found_under_its_serving_name():
    cost = harness.load_module(harness.BENCH, "costs", "fused_seq_nld.py")
    hlo = "%fused_macro_seq.1 = (f32[64,128]) custom-call(s8[8,64,32768] %x)"
    assert trace_reduce.classify(hlo, {"fused_seq_nld": cost.MATCH}) == \
        "fused_seq_nld"


# -- the readers --------------------------------------------------------------

def _read(metric, rec):
    return harness.load_module(harness.BENCH, "metrics",
                               f"{metric}.py").read(rec)


def _reduced(busy_s, kernel_s):
    return trace_reduce.Reduced(
        window_s=2.0, busy_s=busy_s,
        kernels={"fused_seq_nld": (kernel_s, 10)}, top_ops=[], idle_gaps=[],
        n_devices=1, truncated=False)


def test_device_outside_kernel_share():
    rec = harness.Record(trace=_reduced(0.4, 0.1))
    assert _read("device_outside_kernel.rate", rec) == pytest.approx(75.0)
    assert _read("device_outside_kernel.rate", harness.Record()) is None
    idle = harness.Record(trace=_reduced(0.0, 0.0))
    assert _read("device_outside_kernel.rate", idle) is None


def test_roofline_share_of_the_nld_kernel():
    s = {"t": 8, "m": 64, "k": 32768, "n": 128, "branches": 2, "codes": 32}
    cost = harness.load_module(harness.BENCH, "costs", "fused_seq_nld.py")
    peaks = {"int8_ops": 393e12, "hbm_bytes_per_s": 819e9}
    rec = harness.Record(trace=_reduced(1.0, 0.5), peaks=peaks,
                         launches={"fused_seq_nld": (10, s)})
    want = 100.0 * 10 * max(cost.ops(s) / 393e12,
                            cost.nbytes(s) / 819e9) / 0.5
    assert _read("fused_seq_nld_roofline.rate", rec) == pytest.approx(want)
    assert _read("fused_seq_nld_roofline.rate", harness.Record()) is None


def _s(name, args=None):
    return (name, "scheduler", 0, MS, args)


def test_conversions_per_request():
    spans = [_s("round", {"steps": 8, "active": 2, "columns": 256,
                          "conversions": 2 * 8 * 256}),
             _s("evict", {"requests": 0, "pulls": 0}),
             _s("round", {"steps": 8, "active": 2, "columns": 256,
                          "conversions": (8 + 6) * 256}),
             _s("evict", {"requests": 2, "pulls": 1})]
    rec = harness.Record(spans=spans)
    assert _read("conversions_per_req.rate", rec) == 15 * 256
    # a program whose rounds carry no conversions reads nothing
    bare = [_s("round", {"steps": 8, "active": 2}),
            _s("evict", {"requests": 2, "pulls": 1})]
    assert _read("conversions_per_req.rate",
                 harness.Record(spans=bare)) is None
    assert _read("conversions_per_req.rate", harness.Record()) is None


# -- the check ----------------------------------------------------------------

def test_sound_run_is_correct():
    out = _run()
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert out["checks"]["silent_share"]["value"] < 0.5


def test_bfloat16_control_fails():
    cell = _cell({**TINY, "traffic": {"pool": 64}, "cfg": {}})
    got = control.readings(cell, SEED)
    assert got["logit_gap"] > cell.spec["limits"]["logit_gap"], got


def test_dropped_branch_is_caught(monkeypatch):
    """A dendritic branch lost at the soma: its combine weight read as 0."""
    from repro.core import dendrite
    real = dendrite.dendrite_init

    def one_branch(*a, **kw):
        p = real(*a, **kw)
        return p._replace(w_dend=p.w_dend.at[1].set(0.0))
    monkeypatch.setattr(dendrite, "dendrite_init", one_branch)
    out = _run()
    assert not out["correct"], out["checks"]
    assert out["checks"]["logit_gap"]["value"] > \
        out["checks"]["logit_gap"]["limit"]


def test_silent_output_is_caught():
    cell = _cell()
    drv, limits = cell.driver, cell.spec["limits"]
    zeros = np.zeros((16, cell.cfg["n_classes"]), np.float32)
    steps = np.full((16,), 31.0, np.float32)
    got = drv.compare(zeros, steps, zeros, steps, cell.cfg["n_steps"])
    assert got["logit_gap"] == 0 and got["adc_gap"] == 0
    assert got["silent_share"] == 1.0 > limits["silent_share"]
