"""The readers of the engine's host-phase spans, on hand-made span tuples
``(name, track, t0_ns, dur_ns, args)``: each gives its ratio, and nothing
when the spans are missing or carry no arguments (a program without
them)."""

import pytest

from bench import harness

MS = 1_000_000      # ns


def _read(metric, spans):
    reader = harness.load_module(harness.BENCH, "metrics", f"{metric}.py")
    return reader.read(harness.Record(spans=spans))


def _s(name, dur_ms, args=None, track="scheduler"):
    return (name, track, 0, int(dur_ms * MS), args)


CONTINUOUS = [
    _s("enqueue", 1.0, {"uid": 0}, "admission"),
    _s("validate", 0.5, None, "admission"),
    _s("enqueue", 3.0, {"uid": 1}, "admission"),
    _s("admit", 4.0, {"admitted": 2, "pulls": 0}),
    _s("admit", 2.0, {"admitted": 0, "pulls": 0}),
    _s("stage", 6.0, {"bytes": 8}),
    _s("launch", 1.0),
    _s("round", 7.0, {"steps": 8, "active": 2}),
    _s("stage", 2.0, {"bytes": 8}),
    _s("wait", 0.5),
    _s("readout", 9.0),
    _s("evict", 9.6, {"requests": 2, "pulls": 8}),
    _s("evict", 0.1, {"requests": 0, "pulls": 0}),
]

DRAIN = [
    _s("enqueue", 6.0, {"uid": 0}, "admission"),
    _s("stage", 20.0, {"bytes": 64}),
    _s("launch", 1.0),
    _s("wait", 30.0),
    _s("readout", 12.0),
    _s("legacy_batch", 63.0, {"batch": 4, "requests": 4, "pulls": 12}),
    _s("stage", 10.0, {"bytes": 64}),
    _s("wait", 10.0),
    _s("readout", 4.0),
    _s("legacy_batch", 25.0, {"batch": 4, "requests": 4, "pulls": 12}),
]

# what the engine before these spans recorded: phases with no arguments
BARE = [_s("tick", 9.0), _s("admit", 1.0), _s("round", 2.0, {"steps": 8}),
        _s("evict", 3.0), _s("legacy_batch", 5.0, {"batch": 4})]


@pytest.mark.parametrize("metric,spans,want", [
    ("submit_ms_per_req.rate", CONTINUOUS, 2.0),
    ("submit_ms_per_req.rate", DRAIN, 6.0),
    ("admit_ms_per_req.rate", CONTINUOUS, 3.0),
    ("stage_ms_per_launch.rate", CONTINUOUS, 4.0),
    ("stage_ms_per_launch.rate", DRAIN, 15.0),
    ("device_wait_ms_per_launch.rate", CONTINUOUS, 0.5),
    ("device_wait_ms_per_launch.rate", DRAIN, 20.0),
    ("readout_ms_per_req.rate", CONTINUOUS, 4.5),
    ("readout_ms_per_req.rate", DRAIN, 2.0),
    ("host_pulls_per_req.rate", CONTINUOUS, 4.0),
    ("host_pulls_per_req.rate", DRAIN, 3.0),
])
def test_reader_gives_its_ratio(metric, spans, want):
    assert _read(metric, spans) == pytest.approx(want)


def test_admission_pulls_count_toward_pulls_per_request():
    spans = [_s("admit", 1.0, {"admitted": 2, "pulls": 2}),
             _s("evict", 1.0, {"requests": 2, "pulls": 8})]
    assert _read("host_pulls_per_req.rate", spans) == pytest.approx(5.0)


READERS = ("submit_ms_per_req.rate", "admit_ms_per_req.rate",
           "stage_ms_per_launch.rate", "device_wait_ms_per_launch.rate",
           "readout_ms_per_req.rate", "host_pulls_per_req.rate")


@pytest.mark.parametrize("metric", READERS)
@pytest.mark.parametrize("spans", [None, [], BARE],
                         ids=["untraced", "empty", "bare"])
def test_reader_reads_nothing_without_its_spans(metric, spans):
    rec = harness.Record() if spans is None else harness.Record(spans=spans)
    reader = harness.load_module(harness.BENCH, "metrics", f"{metric}.py")
    assert reader.read(rec) is None
