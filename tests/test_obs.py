"""Observability subsystem tests: tracer invariants, the disabled-tracer
fast path, histogram bucket semantics, Perfetto schema round-trip, and the
engine-integration terminal-counter invariant.

The pure-python tests carry ``@pytest.mark.fast`` (they cost
milliseconds); the engine-integration tests live in the default tier —
``make obs-smoke`` covers the traced-engine path in CI.
"""

from __future__ import annotations

import json
import threading

import numpy as np
import pytest

from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace

fast = pytest.mark.fast


# --- tracer ----------------------------------------------------------------

@fast
def test_span_records_name_track_duration():
    tr = obs_trace.Tracer()
    with tr.span("work", track="lane", args={"x": 1}):
        pass
    (name, track, t0, dur, args), = tr.spans()
    assert name == "work" and track == "lane" and args == {"x": 1}
    assert t0 > 0 and dur >= 0


@fast
def test_span_nesting_and_ordering():
    """A child span closes first but sits inside the parent's interval."""
    tr = obs_trace.Tracer()
    with tr.span("outer", track="t"):
        with tr.span("inner", track="t"):
            pass
    spans = {s[0]: s for s in tr.spans()}
    assert list(spans) == ["inner", "outer"]   # completion order
    _, _, t0_out, dur_out, _ = spans["outer"]
    _, _, t0_in, dur_in, _ = spans["inner"]
    assert t0_out <= t0_in
    assert t0_in + dur_in <= t0_out + dur_out
    assert dur_in <= dur_out


@fast
def test_begin_end_explicit_api_merges_args():
    tr = obs_trace.Tracer()
    h = tr.begin("step", track="lane", args={"a": 1})
    tr.end(h, args={"b": 2})
    (_, _, _, _, args), = tr.spans()
    assert args == {"a": 1, "b": 2}


@fast
def test_disabled_tracer_is_null_and_allocation_free():
    tr = obs_trace.Tracer(enabled=False)
    # span() returns the shared singleton — no per-call object
    s1, s2 = tr.span("a"), tr.span("b", track="t")
    assert s1 is s2
    with s1:
        pass
    # begin() returns None; end(None) is a no-op
    h = tr.begin("a")
    assert h is None
    tr.end(h)
    tr.instant("marker")
    assert len(tr) == 0 and tr.spans() == []


@fast
def test_ring_buffer_caps_and_counts_drops():
    tr = obs_trace.Tracer(capacity=4)
    for i in range(10):
        with tr.span(f"s{i}"):
            pass
    assert len(tr) == 4
    assert tr.dropped == 6
    assert [s[0] for s in tr.spans()] == ["s6", "s7", "s8", "s9"]


@fast
def test_tracer_thread_safety():
    tr = obs_trace.Tracer(capacity=10_000)

    def worker(k):
        for i in range(100):
            with tr.span(f"w{k}.{i}", track=f"thread{k}"):
                pass

    threads = [threading.Thread(target=worker, args=(k,)) for k in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(tr) == 400


@fast
def test_global_tracer_swap():
    mine = obs_trace.Tracer()
    prev = obs_trace.set_tracer(mine)
    try:
        assert obs_trace.get_tracer() is mine
    finally:
        obs_trace.set_tracer(prev)
    assert obs_trace.get_tracer() is prev


# --- Perfetto export -------------------------------------------------------

@fast
def test_chrome_trace_schema_round_trip(tmp_path):
    tr = obs_trace.Tracer()
    with tr.span("outer", track="scheduler"):
        with tr.span("inner", track="scheduler", args={"k": "v"}):
            pass
    with tr.span("resident", track="slot00"):
        pass
    path = tmp_path / "trace.json"
    n = tr.export(str(path))
    assert n == 3
    doc = json.loads(path.read_text())           # loads in plain json
    assert isinstance(doc["traceEvents"], list)
    assert doc["displayTimeUnit"] == "ms"
    for ev in doc["traceEvents"]:
        for key in ("ph", "ts", "pid", "tid"):   # required event keys
            assert key in ev, f"missing {key} in {ev}"
    xs = [ev for ev in doc["traceEvents"] if ev["ph"] == "X"]
    assert len(xs) == 3
    for ev in xs:
        assert isinstance(ev["dur"], float) and ev["ts"] >= 0
    # one thread_name metadata event per named track
    names = {ev["args"]["name"] for ev in doc["traceEvents"]
             if ev["ph"] == "M" and ev["name"] == "thread_name"}
    assert {"scheduler", "slot00"} <= names
    # distinct tracks get distinct tids; same track shares one
    tids = {ev["cat"]: ev["tid"] for ev in xs}
    assert tids["scheduler"] != tids["slot00"]


@fast
def test_export_validates_with_obs_report(tmp_path):
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "obs_report", "tools/obs_report.py")
    obs_report = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(obs_report)
    tr = obs_trace.Tracer()
    with tr.span("a", track="t"):
        pass
    path = tmp_path / "t.json"
    tr.export(str(path))
    assert obs_report.check_trace(str(path)) == []
    # corrupt: drop a required key
    doc = json.loads(path.read_text())
    del doc["traceEvents"][-1]["tid"]
    path.write_text(json.dumps(doc))
    assert obs_report.check_trace(str(path))


# --- metrics ---------------------------------------------------------------

@fast
def test_counter_monotonic():
    reg = obs_metrics.MetricsRegistry()
    c = reg.counter("hits")
    c.inc()
    c.inc(4)
    assert reg.value("hits") == 5
    with pytest.raises(ValueError):
        c.inc(-1)


@fast
def test_labeled_series_are_independent():
    reg = obs_metrics.MetricsRegistry()
    reg.counter("terminal_total", state="completed").inc(3)
    reg.counter("terminal_total", state="expired").inc()
    assert reg.value("terminal_total", state="completed") == 3
    assert reg.value("terminal_total", state="expired") == 1
    assert reg.value("terminal_total", state="rejected") == 0  # untouched


@fast
def test_histogram_bucket_edges_le_semantics():
    h = obs_metrics.Histogram(buckets=(1.0, 2.0, 5.0))
    for v in (0.5, 1.0, 1.5, 2.0, 2.1, 5.0, 7.0):
        h.observe(v)
    # le semantics: a value exactly on an edge lands in that bucket
    assert h.counts == [2, 2, 2]      # (.5,1) (1.5,2) (2.1,5)
    assert h.overflow == 1            # 7.0 beyond the last edge
    assert h.total == 7
    assert h.min == 0.5 and h.max == 7.0
    assert h.sum == pytest.approx(19.1)


@fast
def test_histogram_rejects_unsorted_edges():
    with pytest.raises(ValueError):
        obs_metrics.Histogram(buckets=(2.0, 1.0))
    with pytest.raises(ValueError):
        obs_metrics.Histogram(buckets=())


@fast
def test_histogram_quantiles():
    h = obs_metrics.Histogram(buckets=(1.0, 2.0, 4.0, 8.0))
    for v in [0.5] * 50 + [3.0] * 45 + [10.0] * 5:
        h.observe(v)
    assert h.quantile(0.5) == 1.0      # rank 50 is in the first bucket
    assert h.quantile(0.95) == 4.0
    assert h.quantile(1.0) == 10.0     # overflow -> exact max
    assert obs_metrics.Histogram().quantile(0.5) is None


@fast
def test_histogram_merge():
    a = obs_metrics.Histogram(buckets=(1.0, 2.0))
    b = obs_metrics.Histogram(buckets=(1.0, 2.0))
    a.observe(0.5)
    b.observe(1.5)
    b.observe(9.0)
    a.merge(b)
    assert a.counts == [1, 1] and a.overflow == 1
    assert a.total == 3 and a.min == 0.5 and a.max == 9.0
    with pytest.raises(ValueError):
        a.merge(obs_metrics.Histogram(buckets=(3.0,)))


@fast
def test_registry_merge_and_exports():
    a = obs_metrics.MetricsRegistry()
    b = obs_metrics.MetricsRegistry()
    a.counter("n").inc(2)
    b.counter("n").inc(3)
    b.gauge("depth").set(7)
    b.histogram("ms", buckets=(1.0, 10.0)).observe(0.5)
    a.merge(b)
    assert a.value("n") == 5
    assert a.value("depth") == 7
    doc = a.to_dict()
    assert {s["name"] for s in doc["metrics"]} == {"n", "depth", "ms"}
    json.dumps(doc)                    # JSON-safe
    prom = a.to_prometheus()
    assert "# TYPE n counter" in prom
    assert 'ms_bucket{le="1"} 1' in prom
    assert 'ms_bucket{le="+Inf"} 1' in prom
    assert "ms_count 1" in prom


@fast
def test_registry_type_conflicts_raise():
    reg = obs_metrics.MetricsRegistry()
    reg.counter("x").inc()
    with pytest.raises(TypeError):
        reg.gauge("x")
    with pytest.raises(TypeError):
        reg.histogram("x")


# --- engine integration ----------------------------------------------------

def _tiny_engine(**kw):
    import jax
    from repro.models import snn as snn_lib
    from repro.serve.engine import SNNEventEngine
    cfg = snn_lib.SNNConfig(n_in=16, n_hidden=8, n_classes=3, n_steps=6,
                            k=3)
    params = snn_lib.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params, SNNEventEngine(cfg, params, batch_slots=2,
                                       round_steps=3, seed=1, **kw)


def _req(uid, t=6, n_in=16, **kw):
    from repro.serve.engine import EventRequest
    rng = np.random.default_rng(uid)
    ev = (rng.random((t, n_in)) < 0.3).astype(np.float32)
    return EventRequest(uid=uid, events=ev, **kw)


def test_every_terminal_state_increments_exactly_one_counter():
    """The PR 9 'exactly one terminal state' invariant, now countable:
    completed + rejected + expired counters == submissions, per state."""
    from repro.serve import lifecycle
    cfg, params, eng = _tiny_engine(max_pending=3)
    # the dead-on-arrival request goes first so shedding (newest-first)
    # never touches it: it must reach EXPIRED, not REJECTED
    subs = [eng.submit(_req(90, deadline_ms=0.0))]
    subs += [eng.submit(_req(i)) for i in range(5)]         # 3 shed
    eng.run()
    m = eng.metrics
    by_state = {s: m.value("terminal_total", state=s)
                for s in lifecycle.TERMINAL_STATES}
    assert by_state["completed"] == len(eng.completed)
    assert by_state["rejected"] == len(eng.rejected) == 3
    assert by_state["expired"] == len(eng.expired)
    assert sum(by_state.values()) == len(subs)
    for r in subs:
        assert r.state in lifecycle.TERMINAL_STATES
    assert m.value("shed_total") == len(eng.rejected)
    assert m.value("expired_total") == len(eng.expired)


def test_engine_trace_renders_residency_and_phases(tmp_path):
    tracer = obs_trace.Tracer()
    cfg, params, eng = _tiny_engine(tracer=tracer)
    for i in range(3):
        eng.submit(_req(i))
    eng.run()
    path = tmp_path / "engine_trace.json"
    tracer.export(str(path))
    doc = json.loads(path.read_text())
    xs = [ev for ev in doc["traceEvents"] if ev["ph"] == "X"]
    cats = {ev["cat"] for ev in xs}
    names = {ev["name"] for ev in xs}
    assert "scheduler" in cats and "slot00" in cats and "slot01" in cats
    assert {"tick", "expire", "preempt", "admit", "round", "evict"} <= names
    # request residency spans carry the lifecycle outcome
    res = [ev for ev in xs if ev["cat"].startswith("slot")]
    assert len(res) == 3
    assert all(ev["args"]["outcome"] == "completed" for ev in res)
    # a residency span contains at least one whole round span in time
    rounds = [ev for ev in xs if ev["name"] == "round"]
    r0 = res[0]
    assert any(r0["ts"] <= ev["ts"] and
               ev["ts"] + ev["dur"] <= r0["ts"] + r0["dur"] + 1e-3
               for ev in rounds)


def test_preemption_stamps_dwell_time_and_counters():
    tracer = obs_trace.Tracer()
    cfg, params, eng = _tiny_engine(tracer=tracer)
    reqs = [eng.submit(_req(i, t=6)) for i in range(2)]
    eng.run(max_rounds=1)
    victim = next(r for r in eng._slot_req if r is not None)
    eng.preempt_request(victim.uid, backoff=False)
    assert victim.preempted_ms == 0.0          # still checkpointed out
    eng.run()
    assert victim.preempted_ms > 0.0           # dwell stamped on restore
    assert victim.latency_ms > victim.preempted_ms
    m = eng.metrics
    assert m.value("preempted_total") == eng.preemption_count == 1
    assert m.value("terminal_total", state="completed") == len(reqs)
    # the preempted residency shows as two spans on slot tracks
    res = [s for s in tracer.spans() if s[1] and s[1].startswith("slot")
           and f"req{victim.uid}" == s[0]]
    assert len(res) == 2
    outcomes = [s[4]["outcome"] for s in res]
    assert outcomes.count("preempted") == 1
    assert outcomes.count("completed") == 1


def test_per_request_table_carries_preempted_ms():
    cfg, params, eng = _tiny_engine()
    for i in range(2):
        eng.submit(_req(i, t=6))
    eng.run(max_rounds=1)
    victim = next(r for r in eng._slot_req if r is not None)
    eng.preempt_request(victim.uid, backoff=False)
    eng.run()
    rep = eng.energy_report("dvs_gesture")
    rows = {row["uid"]: row for row in rep["per_request"]}
    assert rows[victim.uid]["preempted_ms"] > 0.0
    other = next(uid for uid in rows if uid != victim.uid)
    assert rows[other]["preempted_ms"] == 0.0
    # satellite: round-time quantiles from the measured sample window
    assert 0.0 < rep["round_ms_p50"] <= rep["round_ms_p95"]


def test_round_ms_estimate_prefers_p95_when_warm():
    from repro.serve import engine as engine_mod
    cfg, params, eng = _tiny_engine()
    eng._round_ms = 1.0                         # EMA says 1 ms
    eng._round_samples.extend([1.0] * 7)
    assert eng._round_ms_estimate() == 1.0      # < 8 samples: EMA wins
    eng._round_samples.append(50.0)             # tail the EMA would hide
    assert len(eng._round_samples) == \
        engine_mod.ROUND_MS_P95_MIN_SAMPLES
    assert eng._round_ms_estimate() == 50.0     # p95 of the window
    assert engine_mod.ROUND_MS_EMA_DECAY == 0.9


def test_transfer_spans_carry_byte_counts():
    from repro.models import snn as snn_lib
    tracer = obs_trace.Tracer()
    prev = obs_trace.set_tracer(tracer)
    try:
        cfg, params, eng = _tiny_engine()
        for i in range(2):
            eng.submit(_req(i, t=6))
        eng.run(max_rounds=1)
        victim = next(r for r in eng._slot_req if r is not None)
        eng.preempt_request(victim.uid, backoff=False)
        want = snn_lib.checkpoint_nbytes(victim._ckpt)
        eng.run()
    finally:
        obs_trace.set_tracer(prev)
    transfers = [s for s in tracer.spans() if s[1] == "transfer"]
    names = [s[0] for s in transfers]
    assert "checkpoint_save" in names and "checkpoint_restore" in names
    for s in transfers:
        assert s[4]["bytes"] == want
        assert s[4]["direction"] in ("device_to_host", "host_to_device")


def test_disabled_tracing_leaves_engine_results_bitwise_identical():
    """Tracing must observe, never perturb: logits with a live tracer are
    bitwise-equal to the default (disabled) run."""
    import jax.numpy as jnp
    cfg, params, eng_off = _tiny_engine()
    reqs_off = [eng_off.submit(_req(i)) for i in range(3)]
    eng_off.run()
    cfg, params, eng_on = _tiny_engine(tracer=obs_trace.Tracer())
    reqs_on = [eng_on.submit(_req(i)) for i in range(3)]
    eng_on.run()
    for a, b in zip(reqs_off, reqs_on):
        assert jnp.array_equal(a.logits, b.logits)
        assert a.adc_steps == b.adc_steps


# --- host phases: spans, pulls and queue wait --------------------------------

PATHS = {"continuous": {}, "drain": {"continuous": False}}

# child span -> the parent span it must sit inside, on the same track
PARENTS = {
    "continuous": {"validate": "enqueue", "density": "enqueue",
                   "stage": "round", "launch": "round",
                   "wait": "evict", "readout": "evict"},
    "drain": {"validate": "enqueue", "density": "enqueue",
              "stage": "legacy_batch", "launch": "legacy_batch",
              "wait": "legacy_batch", "readout": "legacy_batch"},
}


def _serve_traced(n=5, tracer=None, **kw):
    cfg, params, eng = _tiny_engine(tracer=tracer, **kw)
    reqs = [eng.submit(_req(i)) for i in range(n)]
    eng.run()
    return eng, reqs


def _inside(child, parent):
    return (child[1] == parent[1] and parent[2] <= child[2]
            and child[2] + child[3] <= parent[2] + parent[3])


@pytest.mark.parametrize("path", sorted(PATHS))
def test_host_phase_spans_nest_in_their_parents(path):
    tracer = obs_trace.Tracer()
    eng, reqs = _serve_traced(tracer=tracer, **PATHS[path])
    spans = tracer.spans()
    by_name = {}
    for s in spans:
        by_name.setdefault(s[0], []).append(s)
    for child, parent in PARENTS[path].items():
        assert by_name.get(child), f"no {child!r} span"
        for s in by_name[child]:
            assert any(_inside(s, p) for p in by_name[parent]), \
                f"{child} at {s[2]} outside every {parent}"
    assert [s[4]["uid"] for s in by_name["enqueue"]] == \
        [r.uid for r in reqs]
    for s in by_name["stage"]:
        assert s[4]["bytes"] > 0
    # every request's readout sits in exactly one readout phase
    parent = "evict" if path == "continuous" else "legacy_batch"
    assert sum(s[4]["requests"] for s in by_name[parent]) == len(reqs)


@pytest.mark.parametrize("path,noise", [("continuous", False),
                                        ("continuous", True),
                                        ("drain", False)])
def test_pull_args_sum_to_host_pulls_total(path, noise):
    from repro.core import ima as ima_lib
    tracer = obs_trace.Tracer()
    kw = dict(PATHS[path])
    if noise:
        kw["noise"] = ima_lib.IMANoiseModel()
    eng, reqs = _serve_traced(tracer=tracer, **kw)
    phases = ("admit", "evict", "legacy_batch")
    pulls = sum(s[4]["pulls"] for s in tracer.spans() if s[0] in phases)
    total = eng.metrics.value("host_pulls_total")
    assert pulls == total > 0
    if path == "continuous":
        # one pull per evict that retires a request (2 slots, 5 requests
        # of 2 rounds each: 3 such evicts); a noisy engine also pulls the
        # seed words of each pass that admits a fresh request, once
        evicts = [s for s in tracer.spans() if s[0] == "evict"]
        assert [s[4]["pulls"] for s in evicts] == \
            [int(s[4]["requests"] > 0) for s in evicts]
        assert sum(s[4]["pulls"] for s in evicts) == 3
        admits = [s for s in tracer.spans() if s[0] == "admit"]
        passes = [s for s in admits if s[4]["admitted"]]
        assert all(s[4]["pulls"] == s[4]["keys"] == 0 for s in admits
                   if not s[4]["admitted"])
        assert [s[4]["pulls"] for s in passes] == [int(noise)] * len(passes)
        assert total == 3 + (len(passes) if noise else 0)
        assert sum(s[4]["admitted"] for s in admits) == len(reqs)
        # every request is admitted fresh with its key derived: one
        # batched key call per admitting pass, tracing on or off
        assert sum(s[4]["keys"] for s in admits) == len(reqs)
        eng_off, _ = _serve_traced(**kw)
        for e in (eng, eng_off):
            assert e.metrics.value("admission_key_calls_total") == \
                len(passes)
    else:
        # one pull per batch of 2: 3 batches
        batches = [s for s in tracer.spans() if s[0] == "legacy_batch"]
        assert [s[4]["pulls"] for s in batches] == [1, 1, 1]
        assert total == 3


@pytest.mark.parametrize("path", sorted(PATHS))
def test_counters_and_results_same_with_tracing_off_and_on(path):
    import jax.numpy as jnp
    eng_off, off = _serve_traced(**PATHS[path])
    eng_on, on = _serve_traced(tracer=obs_trace.Tracer(), **PATHS[path])

    def counters(eng):
        return {(m["name"], tuple(sorted(m["labels"].items()))): m["value"]
                for m in eng.metrics.to_dict()["metrics"]
                if m["type"] == "counter"}

    assert counters(eng_off) == counters(eng_on)
    assert eng_off.metrics.value("host_pulls_total") > 0
    for a, b in zip(off, on):
        assert jnp.array_equal(a.logits, b.logits)
        assert (a.pred, a.adc_steps, a.sops, a.skipped_block_ratio) == \
            (b.pred, b.adc_steps, b.sops, b.skipped_block_ratio)


@pytest.mark.parametrize("path", sorted(PATHS))
def test_queue_ms_is_stamped_once_and_within_latency(path):
    tracer = obs_trace.Tracer()
    eng, reqs = _serve_traced(tracer=tracer, **PATHS[path])
    for r in reqs:
        assert 0.0 <= r.queue_ms <= r.latency_ms
    hist = eng.metrics.histogram("queue_wait_ms")
    assert hist.total == len(reqs)
    assert hist.sum == pytest.approx(sum(r.queue_ms for r in reqs))
    if path == "continuous":
        res = {s[4]["uid"]: s[4]["queue_ms"] for s in tracer.spans()
               if s[1] and s[1].startswith("slot")}
        assert res == {r.uid: r.queue_ms for r in reqs}


def test_drain_latency_is_taken_after_the_device_wait():
    tracer = obs_trace.Tracer()
    eng, reqs = _serve_traced(n=2, tracer=tracer, continuous=False)
    (wait,) = [s for s in tracer.spans() if s[0] == "wait"]
    (readout,) = [s for s in tracer.spans() if s[0] == "readout"]
    wait_end = wait[2] + wait[3]
    for r in reqs:
        # one clock (perf_counter); 1 ns covers the float round trip
        t_done_ns = (r._t_submit * 1e3 + r.latency_ms) * 1e6
        assert wait_end - 1 <= t_done_ns <= readout[2] + 1


def test_queue_wait_counts_first_admission_only():
    """A preempted request re-enters the queue; its queue wait stays the
    one from submit to its first admission."""
    cfg, params, eng = _tiny_engine()
    reqs = [eng.submit(_req(i)) for i in range(2)]
    eng.run(max_rounds=1)
    first = {r.uid: r.queue_ms for r in reqs}
    eng.preempt_request(reqs[0].uid, backoff=False)
    eng.run()
    assert {r.uid: r.queue_ms for r in reqs} == first
    assert eng.metrics.histogram("queue_wait_ms").total == 2


def test_engine_registers_no_energy_histogram():
    """Energy is ``energy_report()``'s; the registry holds no per-request
    energy series."""
    eng, _ = _serve_traced(n=2)
    names = {m["name"] for m in eng.metrics.to_dict()["metrics"]}
    assert not any("pj" in n for n in names)
    assert {"host_pulls_total", "queue_wait_ms",
            "request_latency_ms"} <= names


@pytest.mark.parametrize("mode", ["kwn", "nld"])
def test_round_conversions_and_counter_same_with_tracing_off_and_on(mode):
    """The ``round`` span's ``columns`` and ``conversions`` args, and the
    ``ima_conversions_total`` counter, which counts with tracing off too:
    every step of every request converts each column once (each branch of
    each soma in NLD mode), however the streams split into rounds."""
    import jax
    from repro.models import snn as snn_lib
    from repro.serve.engine import SNNEventEngine
    cfg = snn_lib.SNNConfig(n_in=16, n_hidden=8, n_classes=3, n_steps=6,
                            k=3, mode=mode, n_branches=2)
    params = snn_lib.init_params(cfg, jax.random.PRNGKey(0))
    lengths = [6, 4, 5, 2]
    columns = 16 if mode == "nld" else 8

    def serve(tracer):
        eng = SNNEventEngine(cfg, params, batch_slots=2, round_steps=3,
                             seed=1, tracer=tracer)
        reqs = [eng.submit(_req(i, t=t)) for i, t in enumerate(lengths)]
        eng.run()
        return eng, reqs

    tracer = obs_trace.Tracer()
    eng_on, on = serve(tracer)
    eng_off, off = serve(None)
    rounds = [s for s in tracer.spans() if s[0] == "round"]
    assert rounds and all(s[4]["columns"] == columns for s in rounds)
    assert all(0 < s[4]["conversions"] <= columns * s[4]["steps"]
               * s[4]["active"] for s in rounds)
    want = columns * sum(lengths)
    assert sum(s[4]["conversions"] for s in rounds) == want
    assert eng_on.metrics.value("ima_conversions_total") == want
    assert eng_off.metrics.value("ima_conversions_total") == want
    for a, b in zip(off, on):
        assert np.array_equal(np.asarray(a.logits), np.asarray(b.logits))
