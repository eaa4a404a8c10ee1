"""The autotuner's measurement instruments: a timing loop + event streams.

``median_us`` is the median-of-iters wall time of a jitted call, and
``event_stream`` the DVS-like event stream every candidate plan is timed
on; the autotuner picks and records plans under this one stopwatch.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import trace as obs_trace

# Per-element event rate inside an active (burst) step of a DVS-like
# stream.  The tuner's candidate measurements depend on this temporal
# structure, because the activity planner's skipped-block ratio (and
# therefore the measured latency ordering of candidate plans) does.
IN_BURST_DENSITY = 0.2


def median_us(fn, args, iters: int = 20, label: str | None = None) -> float:
    """Median per-call wall time in microseconds (median over ``iters``
    timed calls — robust to the scheduler hiccups a mean would absorb).

    Each call emits one ``measure`` span on the ``measure`` track of the
    process-global tracer (when enabled), covering warmup + all timed
    iterations and carrying the resulting median — so a traced autotune
    run (``--trace-out``) renders every candidate measurement
    as its own block on the timeline.  ``label`` names the span
    (e.g. the autotuner's candidate tile plan); the measurement itself
    is unchanged.
    """
    tr = obs_trace.get_tracer()
    span = tr.begin(label or "measure", track="measure")
    out = fn(*args)                       # compile + warm up
    jax.block_until_ready(out)
    samples = []
    for _ in range(iters):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        samples.append(time.perf_counter() - t0)
    us = float(np.median(samples)) * 1e6
    if span is not None:
        tr.end(span, args={"iters": iters, "median_us": us})
    return us


def median_ms(fn, args, iters: int = 20, label: str | None = None) -> float:
    """``median_us`` in milliseconds — the unit the plan cache persists."""
    return median_us(fn, args, iters=iters, label=label) * 1e-3


def event_stream(key, density, shape):
    """Density-d ternary events; bursty (DVS-like) when time-major.

    A (T, M, K) stream at density < IN_BURST_DENSITY is modelled as silent
    steps plus active steps firing at the in-burst rate (saccade/gesture
    streams are temporally clustered, which is exactly the structure the
    per-(step, row-tile, K-tile) activity planner converts into skipped
    blocks); at or above the in-burst rate every step is active with
    uniform per-element density.  2-D (single-step) shapes are uniform —
    one step has no temporal structure to exploit.
    """
    k_val, k_el, k_step = jax.random.split(key, 3)
    tern = jax.random.randint(k_val, shape, -1, 2).astype(jnp.int8)
    if len(shape) == 3 and density < IN_BURST_DENSITY:
        active = jax.random.uniform(k_step, (shape[0], 1, 1)) \
            < (density / IN_BURST_DENSITY)
        sparse = (jax.random.uniform(k_el, shape) < IN_BURST_DENSITY) & active
    else:
        sparse = jax.random.uniform(k_el, shape) < density
    return (tern * sparse).astype(jnp.int8)
