"""Serving driver for an NLD configuration: ``SNNEventEngine`` in the
macro's dendritic mode, under the mix's arrivals.

The window, the arrivals and ``serve_rps`` are ``serve.py``'s, unchanged;
what differs is the network.  The engine is built on an NLD ``SNNConfig``
(J branches per soma, the configuration's activation, code bits and ramp
range) with weights from ``dendrite.dendrite_init`` at the configuration's
branch fan-in and gain, and every answer is checked against
``bench/reference/nld_ref.py``: the widest logit gap, the widest gap in a
request's total ramp steps (exact), the requests that never returned
(exact), and ``silent_share``, the share of answered requests whose logits
are all zero, which keeps the logit comparison from being one of silence.
"""

from __future__ import annotations

import time
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np

from bench.drivers import common, serve
from bench.reference import nld_ref
from bench.traffic import generator

KERNEL = "fused_seq_nld"


def snn_config(snn, cfg: dict):
    """The program's ``SNNConfig`` for an NLD configuration file."""
    return snn.SNNConfig(
        n_in=cfg["n_in"], n_hidden=cfg["hidden_layers"][-1],
        n_classes=cfg["n_classes"], n_steps=cfg["n_steps"], mode="nld",
        n_branches=cfg["n_branches"], activation=cfg["activation"],
        code_bits=cfg["code_bits"], dend_range=cfg["dend_range"])


def init_params(cfg: dict, key: jax.Array) -> dict:
    """The program's NLD weights: ``snn.init_params``'s random stream, with
    the branches drawn at the configuration's fan-in and gain."""
    from repro.core import dendrite
    n = cfg["hidden_layers"][-1]
    k1, _, k3 = jax.random.split(key, 3)
    return {
        "w_out": jax.random.normal(k3, (n, cfg["n_classes"])) / jnp.sqrt(n),
        "dend": dendrite.dendrite_init(
            k1, cfg["n_in"], n, cfg["n_branches"],
            fanin_frac=cfg["branch_fanin_frac"], gain=cfg["dendrite_gain"])}


def setup(ctx):
    from repro.models import snn
    from repro.obs.trace import Tracer
    from repro.serve.engine import EventRequest, SNNEventEngine

    scfg = snn_config(snn, ctx.cfg)
    params = init_params(ctx.cfg, common.weight_key(ctx.seed))
    pool = generator.pool(ctx.cfg, ctx.seed, ctx.traffic["pool"])
    tracer = (Tracer(enabled=True, capacity=1 << 21, jax_annotations=True)
              if ctx.trace else None)
    eng = SNNEventEngine(scfg, params, tracer=tracer, **ctx.spec["engine"])
    st = SimpleNamespace(
        eng=eng, params=params, pool=pool, tracer=tracer, Req=EventRequest,
        density=[float(np.count_nonzero(e)) / e.size for e in pool],
        which={}, row={}, due={}, inflight={}, next_uid=0,
        order=generator.pool_order(ctx.seed, len(pool), 1 << 20),
        src=serve.arrivals(ctx))
    # Warm-up: every slot filled and emptied, every program compiled.
    for i in range(2 * eng.b):
        eng.submit(EventRequest(uid=-1 - i, events=pool[i % len(pool)]))
    eng.run()
    # then the mix itself, until it has returned its warm-up requests
    done, clock = 0, time.perf_counter
    while done < ctx.traffic.get("warmup_requests", 0):
        for d in st.src.take(clock()):
            serve._submit(st, d)
        if not (eng.pending or eng.active):
            break
        out = serve._call(ctx, st)
        st.src.returned([clock()] * len(out))
        done += len(out)
    if tracer is not None:
        tracer.clear()
    return st


def window(ctx, st):
    """``serve.window``, with the NLD launch's shape and MAC work: each
    request needs 2 T K (J N) ternary MACs."""
    rec = serve.window(ctx, st)
    cfg, eng = ctx.cfg, st.eng
    n, j = cfg["hidden_layers"][-1], cfg["n_branches"]
    rec["mac_ops"] = (rec["returned_in_window"] * 2 * cfg["n_steps"]
                      * cfg["n_in"] * j * n)
    rec["launches"] = {KERNEL: (rec["rounds"], {
        "t": eng.round_steps, "m": eng.b, "k": cfg["n_in"], "n": n,
        "branches": j, "codes": 2 ** cfg["code_bits"]})}
    return rec


release = serve.release
end_to_end = serve.end_to_end


def reference(cfg: dict, seed: int, pool: np.ndarray, which, rows,
              slots: int, dt=jnp.float32):
    """The reference's answers for the requests that carry the pool's
    streams ``which`` (``rows`` and ``slots`` are unused: nothing in NLD
    draws noise).  Streams are gathered one block at a time."""
    params = nld_ref.init_params(cfg, common.weight_key(seed))
    fwd = jax.jit(lambda p, ev: nld_ref.serve(p, ev, cfg, dt))
    logits, adc = [], []
    which = np.asarray(which, np.int64)
    for b0 in range(0, len(which), serve.REF_BLOCK):
        lg, ad = fwd(params, jnp.asarray(pool[which[b0:b0 + serve.REF_BLOCK]]))
        logits.append(np.asarray(lg))
        adc.append(np.asarray(ad))
    return np.concatenate(logits), np.concatenate(adc)


def compare(logits, adc, ref_logits, ref_adc, n_steps: int) -> dict:
    """``serve.compare``'s numbers, and the share of answers that are all
    zero."""
    out = serve.compare(logits, adc, ref_logits, ref_adc, n_steps)
    logits = np.asarray(logits)
    out["silent_share"] = (float(np.mean(np.all(logits == 0, axis=-1)))
                           if len(logits) else 1.0)
    return out


def check(ctx, st, rec):
    answered = rec["answered"]
    logits = np.stack(jax.device_get([r.logits for r in answered])) \
        if answered else np.zeros((0,))
    adc = np.array([r.adc_steps for r in answered], np.float32)
    which = np.asarray([st.which[r.uid] for r in answered], np.int64)
    # a request's answer depends on its stream alone
    uniq, inv = np.unique(which, return_inverse=True)
    ul, ua = reference(ctx.cfg, ctx.seed, st.pool, uniq, None, rec["slots"])
    gaps = compare(logits, adc, ul[inv], ua[inv], ctx.cfg["n_steps"])
    out = {"missing": common.check("missing", rec["failed"], ctx.limits)}
    for name, v in gaps.items():
        out[name] = common.check(name, v, ctx.limits)
    return out
