"""The trace reduction: busy union, idle gaps named by host spans, kernel
time by stable name — on hand-made events and on a trace recorded here."""

import os

import jax
import jax.numpy as jnp
import pytest

from bench import harness
from bench import trace_reduce as tr


def test_union_and_gaps():
    iv = [(1.0, 2.0), (1.5, 3.0), (5.0, 6.0), (9.0, 12.0)]
    assert tr.union_length(iv, 0.0, 10.0) == pytest.approx(4.0)
    assert tr.gaps(iv, 0.0, 10.0) == [(0.0, 1.0), (3.0, 5.0), (6.0, 9.0)]


def test_reduce_events_names_kernels_and_gaps():
    texts = [
        ("%fused_macro_seq.1 = (f32[64,128]) custom-call(s8[8,64,2560])",
         1.0, 1.5),
        ("%fusion.2 = f32[64] fusion(f32[64] %x)", 1.5, 2.0),
        ("%fused_macro_multi_seq.1 = (f32[64,256]) custom-call(s8[2])",
         4.0, 4.5),
        ("%transpose_jvp_jit_fused_macro_seq_grad___.2 = (f32[2560,128]) "
         "custom-call(s32[200])", 6.0, 7.0),
    ]
    host = [("tick", 0.0, 8.0), ("evict", 2.0, 3.9), ("admit", 3.9, 4.0),
            ("submit", 4.5, 6.0)]
    pats = {k: harness.load_module(harness.BENCH, "costs", f"{k}.py").MATCH
            for k in ("fused_seq", "fused_multi_seq", "fused_seq_bptt")}
    dev = {"/device:TPU:0": [(tr.op_name(x), tr.classify(x, pats), s, e)
                             for x, s, e in texts]}
    r = tr.reduce_events(dev, host, tuple(pats), (0.0, 8.0))
    assert r.window_s == 8.0
    assert r.busy_s == pytest.approx(2.5)
    assert r.kernels["fused_seq"] == (pytest.approx(0.5), 1)
    assert r.kernels["fused_multi_seq"] == (pytest.approx(0.5), 1)
    assert r.kernels["fused_seq_bptt"] == (pytest.approx(1.0), 1)
    assert r.top_ops[0] == ["%transpose_jvp_jit_fused_macro_seq_grad___.2",
                            1.0]
    # longest gaps first; each named by the innermost span covering most
    assert [g[0] for g in r.idle_gaps] == ["evict", "submit", "tick",
                                           "tick"]
    assert [g[1] for g in r.idle_gaps] == pytest.approx([2.0, 1.5, 1.0,
                                                         1.0])


def test_trace_that_ends_early_shortens_the_window():
    """A trace holding fewer launches than the driver made ends the traced
    window at its last device operation."""
    pats = {"fused_seq": harness.load_module(
        harness.BENCH, "costs", "fused_seq.py").MATCH}
    k = "%fused_macro_seq.1 = (f32[64,128]) custom-call(s8[8])"
    dev = {"/device:TPU:0": [(tr.op_name(k), "fused_seq", s, s + 0.5)
                             for s in (1.0, 3.0)]}
    full = tr.reduce_events(dev, [], ("fused_seq",), (0.0, 10.0),
                            {"fused_seq": 2})
    assert not full.truncated and full.window_s == 10.0
    cut = tr.reduce_events(dev, [], ("fused_seq",), (0.0, 10.0),
                           {"fused_seq": 5})
    assert cut.truncated and cut.window_s == pytest.approx(3.5)
    assert cut.busy_s == pytest.approx(1.0)
    assert [g[1] for g in cut.idle_gaps] == pytest.approx([1.5, 1.0])


def test_recorded_trace_has_the_window(tmp_path):
    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("window"):
        with jax.profiler.TraceAnnotation("submit"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    r = tr.reduce_dir(str(tmp_path), {"k": "nothing"}, {"submit"})
    assert r.window_s > 0
    # the CPU has no device plane: nothing ran "on the device"
    assert r.busy_s == 0.0 and r.kernels["k"] == (0.0, 0)
    assert r.idle_gaps[0][0] == "submit"


def test_missing_window_is_an_error(tmp_path):
    jax.profiler.start_trace(str(tmp_path))
    jnp.ones(3).block_until_ready()
    jax.profiler.stop_trace()
    with pytest.raises(ValueError):
        tr.reduce_dir(str(tmp_path), {})
    assert os.path.isdir(tmp_path)


def test_patterns_on_recorded_chip_op_names():
    """Instruction names as a v5e trace gives them."""
    names = {
        "fused_seq": ["%fused_macro_seq.1 = (f32[64,128]) custom-call(x)",
                      "%jvp_jit_fused_macro_seq__.1 = (f32[20]) "
                      "custom-call(x)"],
        "fused_seq_bptt": ["%transpose_jvp_jit_fused_macro_seq_grad___.2 "
                           "= (f32[2560,128]) custom-call(x)"],
        "fused_multi_seq": ["%fused_macro_multi_seq.1 = (f32[64,256]) "
                            "custom-call(x)"],
    }
    pats = {k: harness.load_module(harness.BENCH, "costs", f"{k}.py").MATCH
            for k in names}
    for k, texts in names.items():
        for text in texts:
            assert tr.classify(text, pats) == k, (k, text)
    assert tr.classify("%fused_macro_seq.1 = f32[8] fusion(f32[8] %a)",
                       pats) is None
