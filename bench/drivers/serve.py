"""Serving driver: ``SNNEventEngine`` under the mix's arrivals.

The mix file (``bench/traffic/<mix>.json``) names its arrival kind, and
``bench/traffic/<kind>.py`` says when each request falls due: on a
schedule whatever the engine does, or when a client's previous request
returns.  This driver does the same for every kind: it submits each
request as it falls due, lets the engine make one call (one scheduling
tick on the continuous path, one drain on the drain path), and tells the
arrivals what returned.  Once the window closes no new request falls due,
and the driver keeps calling the engine for up to ``drain_s`` until every
request it submitted has returned; one that never does counts as failed.

A request is timed from when it was due until the call that returned it
returned (printed as percentiles; a failed one is left out and counted).
``serve_rps`` is the requests returned by calls that started inside the
window, over the time from the window's start to the end of the last such
call.

The engine chooses its own path: the continuous slots for a single layer,
whole-sequence batches (the drain path) for a stack.  Answers are checked
against ``bench/reference/snn_ref.py``: every answer the run returned,
logits and the mean ramp steps (ADC telemetry).
"""

from __future__ import annotations

import time
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np

from bench.drivers import common
from bench.harness import BENCH, Record, load_module
from bench.reference import snn_ref
from bench.traffic import generator

ENGINE_SPANS = ("tick", "expire", "preempt", "admit", "round", "evict",
                "legacy_batch")
BENCH_SPANS = ("submit", "engine.run")
REF_BLOCK = 128


def arrivals(ctx):
    """The mix's arrival process, ``bench/traffic/<kind>.py``."""
    kind = load_module(BENCH, "traffic", f"{ctx.traffic['kind']}.py")
    return kind.Arrivals(ctx.traffic, ctx.seconds, ctx.seed)


def setup(ctx):
    from repro.models import snn
    from repro.obs.trace import Tracer
    from repro.serve.engine import EventRequest, SNNEventEngine

    scfg = common.snn_config(snn, ctx.cfg)
    params = snn.init_params(scfg, common.weight_key(ctx.seed))
    pool = generator.pool(ctx.cfg, ctx.seed, ctx.traffic["pool"])
    tracer = (Tracer(enabled=True, capacity=1 << 21, jax_annotations=True)
              if ctx.trace else None)
    eng = SNNEventEngine(scfg, params, tracer=tracer, **ctx.spec["engine"])
    st = SimpleNamespace(
        eng=eng, params=params, pool=pool, tracer=tracer, Req=EventRequest,
        density=[float(np.count_nonzero(e)) / e.size for e in pool],
        which={}, row={}, due={}, inflight={}, next_uid=0,
        order=generator.pool_order(ctx.seed, len(pool), 1 << 20),
        src=arrivals(ctx))
    # Warm-up: every slot filled and emptied, every program compiled.
    for i in range(2 * eng.b):
        eng.submit(EventRequest(uid=-1 - i, events=pool[i % len(pool)]))
    eng.run()
    # then the mix itself, until it has returned its warm-up requests (a
    # mix that offers nothing before its window has none)
    done, clock = 0, time.perf_counter
    while done < ctx.traffic.get("warmup_requests", 0):
        for d in st.src.take(clock()):
            _submit(st, d)
        if not (eng.pending or eng.active):
            break
        out = _call(ctx, st)
        st.src.returned([clock()] * len(out))
        done += len(out)
    if tracer is not None:
        tracer.clear()
    return st


def _submit(st, due: float):
    """Submit the next request of the pool order, due at ``due``."""
    uid = st.next_uid
    idx = int(st.order[uid % len(st.order)])
    st.which[uid], st.due[uid] = idx, due
    st.next_uid += 1
    st.inflight[uid] = st.eng.submit(st.Req(uid=uid, events=st.pool[idx]))


def _call(ctx, st, run=None):
    """One engine call; returns the requests it returned.  ``run(fn, *a)``
    makes the call (it may time it)."""
    eng = st.eng
    if not eng.continuous:
        # the drain path's batches: pending by (density, uid), 64 at a
        # time — the SNL noise of a request depends on its batch row
        order = sorted(eng.pending,
                       key=lambda r: (st.density[st.which[r.uid]], r.uid))
        for j, r in enumerate(order):
            st.row[r.uid] = j % eng.b
    run = run or (lambda fn, *a, **kw: fn(*a, **kw))
    with ctx.span("engine.run"):
        out = (run(eng.run, max_rounds=1) if eng.continuous
               else run(eng.run))
    for r in out:
        st.inflight.pop(r.uid, None)
    return out


def window(ctx, st):
    eng, src, clock = st.eng, st.src, time.perf_counter
    attempted = set(st.inflight)           # in flight from the warm-up
    answered, done = [], {}
    n_rate, rounds0 = 0, eng.metrics.counter("rounds_total").value
    with ctx.span("window"):
        t0 = clock()
        src.start(t0)
        longest = common.Longest(t0)
        t_close = t0 + ctx.seconds
        t_stop = t_close + ctx.traffic["drain_s"]
        t_rate = t0
        while True:
            now = clock()
            if now < t_close:
                due = src.take(now)
                if due:
                    with ctx.span("submit"):
                        for d in due:
                            attempted.add(st.next_uid)
                            _submit(st, d)
            if eng.pending or eng.active:
                started = clock()
                out = _call(ctx, st, longest.call)
                t = clock()
                for r in out:
                    done[r.uid] = t
                answered += out
                if started < t_close:
                    n_rate, t_rate = n_rate + len(out), t
                src.returned([t] * len(out))
            elif now < t_close and src.next_due() is not None:
                time.sleep(max(0.0, src.next_due() - clock()))
            else:
                break
            if now > t_stop:
                break
    in_window = [u for u in attempted if t0 <= st.due[u] < t_close]
    lat = np.array([done[u] - st.due[u] if u in done else np.inf
                    for u in in_window])
    fin = lat[np.isfinite(lat)] * 1e3
    rec = Record(
        window_s=t_rate - t0, returned_in_window=n_rate,
        attempted=len(attempted),
        failed=sum(1 for u in attempted if u not in done),
        latency_s=lat, answered=answered, longest_call=longest.best,
        rounds=eng.metrics.counter("rounds_total").value - rounds0)
    if len(fin):
        rec["info"] = ("latency_ms p50={:.2f} p90={:.2f} p95={:.2f} "
                       "p99={:.2f} mean={:.2f} missing={}".format(
                           *np.percentile(fin, (50, 90, 95, 99)),
                           float(np.mean(fin)), len(lat) - len(fin)))
    cfg = ctx.cfg
    shapes = common.layer_shapes(cfg)
    rec["mac_ops"] = n_rate * 2 * cfg["n_steps"] * sum(
        k * n for k, n in shapes)
    if eng.continuous:
        rec["launches"] = {"fused_seq": (rec["rounds"], {
            "t": eng.round_steps, "m": eng.b, "k": cfg["n_in"],
            "n": shapes[0][1], "noise": True, "train": False})}
    else:
        rec["launches"] = {"fused_multi_seq": (
            -(-len(answered) // eng.b),
            {"t": cfg["n_steps"], "m": eng.b, "layers": shapes})}
    rec["slots"] = eng.b
    rec["span_names"] = ENGINE_SPANS + BENCH_SPANS
    if st.tracer is not None:
        rec["spans"] = st.tracer.spans()
    return rec


def release(ctx, st):
    """Drop the engine and its weights; the answers stay."""
    st.eng = st.params = None


def reference(cfg: dict, seed: int, pool: np.ndarray, which, rows,
              slots: int, dt=jnp.float32):
    """The reference's answers for the requests that carry the pool's
    streams ``which``; ``rows`` gives each request's batch row on the drain
    path (None: continuous slots, where every request draws its own SNL
    stream).  Streams are gathered one block at a time."""
    params = snn_ref.init_params(cfg, common.weight_key(seed))
    t, ks = cfg["n_steps"], (cfg["k"],) * len(cfg["hidden_layers"])
    fwd = jax.jit(snn_ref.serve, static_argnames=("k_layers", "dt"))
    logits, adc = [], []
    which = np.asarray(which, np.int64)
    for b0 in range(0, len(which), REF_BLOCK):
        ev = pool[which[b0:b0 + REF_BLOCK]]
        if rows is None:
            noises = [np.broadcast_to(snn_ref.prbs_noise(t, 1, w),
                                      (t, len(ev), w))
                      for w in cfg["hidden_layers"]]
        else:
            r = np.asarray(rows[b0:b0 + REF_BLOCK])
            noises = [snn_ref.prbs_noise(t, slots, w)[:, r, :]
                      for w in cfg["hidden_layers"]]
        lg, ad = fwd(params, jnp.asarray(ev),
                     [jnp.asarray(nz) for nz in noises], k_layers=ks, dt=dt)
        logits.append(np.asarray(lg))
        adc.append(np.asarray(ad))
    return np.concatenate(logits), np.concatenate(adc)


def compare(logits, adc, ref_logits, ref_adc, n_steps: int) -> dict:
    """The numbers compared: the widest logit gap, and the widest gap in a
    request's total ramp steps (an integer, the mean times T: compared
    exactly)."""
    if len(logits) == 0:
        return {"logit_gap": float("inf"), "adc_gap": float("inf")}
    steps = np.rint(np.asarray(adc, np.float64) * n_steps)
    ref_steps = np.rint(np.asarray(ref_adc, np.float64) * n_steps)
    return {"logit_gap": float(np.max(np.abs(logits - ref_logits))),
            "adc_gap": float(np.max(np.abs(steps - ref_steps)))}


def check(ctx, st, rec):
    answered = rec["answered"]
    logits = np.stack(jax.device_get([r.logits for r in answered])) \
        if answered else np.zeros((0,))
    adc = np.array([r.adc_steps for r in answered], np.float32)
    which = [st.which[r.uid] for r in answered]
    drain = bool(st.row)
    rows = [st.row[r.uid] for r in answered] if drain else None
    if drain:
        ref_logits, ref_adc = reference(
            ctx.cfg, ctx.seed, st.pool, which, rows, rec["slots"])
    else:
        # continuous slots: a request's answer depends on its stream alone
        uniq, inv = np.unique(np.asarray(which, np.int64),
                              return_inverse=True)
        ul, ua = reference(ctx.cfg, ctx.seed, st.pool, uniq, None,
                           rec["slots"])
        ref_logits, ref_adc = ul[inv], ua[inv]
    gaps = compare(logits, adc, ref_logits, ref_adc, ctx.cfg["n_steps"])
    out = {"missing": common.check("missing", rec["failed"], ctx.limits)}
    for name, v in gaps.items():
        out[name] = common.check(name, v, ctx.limits)
    return out


def end_to_end(ctx, rec):
    return {"serve_rps": (rec["returned_in_window"] / rec["window_s"]
                          if rec["window_s"] > 0 else None)}
