"""Reduce a profiler trace (``.xplane.pb``) to the benchmark's numbers.

* device time per kernel, by stable name: the summed durations of the
  device operations whose HLO instruction matches the kernel's pattern in
  ``bench/costs/<kernel>.py`` (the Pallas kernels carry no name of their
  own; their custom call is named after the jitted function that wraps
  them, such as ``%fused_macro_seq.1``);
* the device-busy union and the window's length: busy is the union of the
  intervals in which an operation ran on a device, averaged over the
  devices, inside the benchmark's ``window`` span.  The device's trace
  buffer can fill before the window ends (an operation inside a device
  loop is recorded once per iteration); when the trace holds fewer
  launches of a kernel than the driver made, the traced window ends with
  the last device operation the trace holds;
* the device operations that took most time;
* the longest idle gaps, each named by the host span it fell in: the
  innermost span of the program or the benchmark that covers its middle.

The window is the benchmark's own ``window`` annotation on the host.
Host and device events share the trace's clock.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

WINDOW_SPAN = "window"
DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OPS_LINE = "XLA Ops"
TOP = 10


@dataclasses.dataclass
class Reduced:
    window_s: float        # the traced window (see ``truncated``)
    busy_s: float
    kernels: dict          # stable name -> (seconds, events)
    top_ops: list          # [[name, seconds], ...]
    idle_gaps: list        # [[host span, seconds], ...]
    n_devices: int
    truncated: bool        # the device trace ended before the window did


def op_name(text: str) -> str:
    """``%fusion.2`` of an HLO instruction ``%fusion.2 = f32[...] ...``."""
    return text.split(" = ", 1)[0]


def classify(text: str, patterns: dict):
    """The stable kernel name whose pattern the instruction matches."""
    if "custom-call" not in text:
        return None
    return next((k for k, pat in patterns.items() if re.search(pat, text)),
                None)


def union_length(intervals, lo: float, hi: float) -> float:
    """Length of the union of [s, e) intervals, clipped to [lo, hi)."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals, lo: float, hi: float) -> list:
    """The idle [s, e) stretches of [lo, hi) between busy intervals."""
    out, t = [], lo
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def name_gap(gap, host_spans) -> str:
    """The innermost host span that covers the middle of ``gap``."""
    mid = 0.5 * (gap[0] + gap[1])
    best, best_len = "no span", None
    for name, hs, he in host_spans:
        if hs <= mid < he and (best_len is None or he - hs < best_len):
            best, best_len = name, he - hs
    return best


def reduce_events(device_ops: dict, host_spans: list, kernels: tuple,
                  window: tuple, launches: dict | None = None) -> Reduced:
    """``device_ops``: {device: [(op name, kernel or None, start_s,
    end_s)]};
    ``host_spans``: [(name, start_s, end_s)]; ``window``: (lo, hi) s;
    ``launches``: {kernel: launches the driver made in the window}."""
    lo, hi = window
    n_dev = max(1, len(device_ops))
    seen = {k: 0 for k in kernels}
    last = lo
    for ops in device_ops.values():
        for _, kern, s, e in ops:
            if s < hi and e > lo:
                last = max(last, min(e, hi))
                if kern is not None:
                    seen[kern] += 1
    truncated = any(seen[k] < n * n_dev
                    for k, n in (launches or {}).items() if k in seen)
    if truncated:
        hi = last
    busy = sum(union_length([(s, e) for _, _, s, e in ops], lo, hi)
               for ops in device_ops.values()) / n_dev
    kernels = {k: [0.0, 0] for k in kernels}
    by_name: dict = {}
    all_iv = []
    for ops in device_ops.values():
        for name, kern, s, e in ops:
            if e <= lo or s >= hi:
                continue
            d = min(e, hi) - max(s, lo)
            by_name[name] = by_name.get(name, 0.0) + d
            all_iv.append((s, e))
            if kern is not None:
                kernels[kern][0] += d
                kernels[kern][1] += 1
    top = sorted(by_name.items(), key=lambda kv: -kv[1])[:TOP]
    idle = sorted(gaps(all_iv, lo, hi), key=lambda g: g[0] - g[1])[:TOP]
    return Reduced(
        window_s=hi - lo, busy_s=busy,
        kernels={k: (v[0] / n_dev, v[1]) for k, v in kernels.items()},
        top_ops=[[n, v / n_dev] for n, v in top],
        idle_gaps=[[name_gap(g, host_spans), g[1] - g[0]] for g in idle],
        n_devices=n_dev, truncated=truncated)


def read_xplane(path: str, patterns: dict, span_names=frozenset()):
    """(device_ops, host_spans, window) from one ``.xplane.pb``; device
    operations are named and matched to kernels as they are read, host
    spans kept when their name is in ``span_names``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    device_ops, host_spans, window = {}, [], None
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            ops = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    s, text = ev.start_ns * 1e-9, ev.name
                    ops.append((op_name(text), classify(text, patterns), s,
                                s + ev.duration_ns * 1e-9))
            device_ops[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    s = ev.start_ns * 1e-9
                    if ev.name == WINDOW_SPAN:
                        window = (s, s + ev.duration_ns * 1e-9)
                    elif ev.name in span_names:
                        host_spans.append(
                            (ev.name, s, s + ev.duration_ns * 1e-9))
    if window is None:
        raise ValueError(f"no {WINDOW_SPAN!r} span in {path}")
    return device_ops, host_spans, window


def reduce_dir(trace_dir: str, patterns: dict, span_names=frozenset(),
               launches: dict | None = None) -> Reduced:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    device_ops, host_spans, window = read_xplane(sorted(paths)[-1],
                                                 patterns, span_names)
    return reduce_events(device_ops, host_spans, tuple(patterns), window,
                         launches)
