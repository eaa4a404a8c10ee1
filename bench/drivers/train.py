"""Training driver: noise-aware silicon fine-tuning through the fused
forward and the BPTT kernel (``snn.train_step(silicon=True, noise=...)``,
the step ``snn.train`` runs).

Set-up makes the run's data set on the device, ``data_batches`` batches
of distinct streams from the run's seed (as int8, in one jitted call),
builds the step and its state once and drives it through the first
``checked_steps`` steps with the window's own feed: one jitted call per
step takes batch ``i mod data_batches`` and draws the step's noise seed
from the run's seed and ``i``.  The window continues the same object,
keeping at most ``in_flight`` steps queued on the device, and ends when
the last step it started has finished.

The reference follows the first steps from its own weights: the first
step's loss, the norm of the first gradient (the momentum after one step),
and the norm of each parameter's change over the checked steps.  Later
steps' losses are not compared.  The program's readout product runs at the
TPU's default precision (one bfloat16 pass), the reference's at highest, so
the two sides' updates differ by that rounding; the forward rounds each
hidden weight onto the 3-bit grid, a weight left within that rounding of a
half-level lands on different levels, and the next step's loss jumps by
some 1e-5 to 1e-4 between two sound runs.  The reference on the CPU and on
the TPU shows the same jumps between themselves.
"""

from __future__ import annotations

import statistics
import time
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np

from bench.drivers import common
from bench.harness import Record
from bench.reference import snn_ref
from bench.traffic import generator

BENCH_SPANS = ("feed", "train_step", "sync")


def make_data(cfg: dict, traffic: dict, seed: int):
    """(events (n, B, T, N) int8, labels (n, B), key) of one run."""
    key = jax.random.PRNGKey(generator.seed32(seed))
    n, b = traffic["data_batches"], traffic["batch"]
    streams = generator.EventStreams(cfg)

    @jax.jit
    def make(k):
        ev, lab = streams.sample(k, n * b)
        return (ev.astype(jnp.int8).reshape((n, b) + ev.shape[1:]),
                lab.reshape(n, b))
    ev, lab = make(jax.random.fold_in(key, 2))
    return ev, lab, jax.random.fold_in(key, 3)


@jax.jit
def feed(ev, lab, key, i):
    """Step ``i``'s batch (events f32, labels) and its noise seed."""
    j = i % ev.shape[0]
    seed_f = jax.random.randint(jax.random.fold_in(key, i), (), 0,
                                2 ** 23).astype(jnp.float32)
    return ev[j].astype(jnp.float32), lab[j], seed_f


def setup(ctx):
    from repro.core import ima
    from repro.models import snn

    tr = ctx.traffic
    scfg = common.snn_config(snn, ctx.cfg)
    data = make_data(ctx.cfg, tr, ctx.seed)
    p = snn.init_params(scfg, common.weight_key(ctx.seed))
    p = jax.tree.map(lambda x: x + 0, p)
    m = jax.tree.map(jnp.zeros_like, p)
    noise = ima.IMANoiseModel() if tr["noise"] == "ima" else None
    lr = jnp.float32(tr["lr"])

    def step(p, m, i):
        with ctx.span("feed"):
            ev, lab, seed_f = feed(*data, i)
        with ctx.span("train_step"):
            return snn.train_step(p, m, ev, lab, scfg, lr, seed_f,
                                  silicon=True, noise=noise)

    st = SimpleNamespace(step=step, batches=[], losses=[])
    p0 = jax.device_get(p)
    for i in range(tr["checked_steps"]):
        st.batches.append(jax.device_get(feed(*data, i)))
        p, m, loss = step(p, m, i)
        if i == 0:
            st.g1 = jax.device_get(m)      # momentum after one step = g1
        st.losses.append(float(loss))
    st.p0, st.p_checked = p0, jax.device_get(p)
    st.p, st.m, st.i, st.data = p, m, tr["checked_steps"], data
    return st


def window(ctx, st):
    tr, clock = ctx.traffic, time.perf_counter
    p, m, i, queued = st.p, st.m, st.i, []
    with ctx.span("window"):
        t0 = clock()
        t_end, t_prev, longest = t0 + ctx.seconds, t0, (0.0, 0.0, 0.0)
        while (now := clock()) < t_end:
            if now - t_prev > longest[0]:     # the longest step, wall only
                longest = (now - t_prev, float("nan"), t_prev - t0)
            t_prev = now
            p, m, loss = st.step(p, m, i)
            queued.append(loss)
            i += 1
            if len(queued) > tr["in_flight"]:
                with ctx.span("sync"):
                    queued[-1 - tr["in_flight"]].block_until_ready()
        with ctx.span("sync"):
            jax.block_until_ready((p, m))
        t_last = clock()
    st.p, st.m = p, m
    n = i - st.i
    losses = np.asarray(jax.device_get(queued))
    cfg, b = ctx.cfg, tr["batch"]
    shape = {"t": cfg["n_steps"], "m": b, "k": cfg["n_in"],
             "n": cfg["hidden_layers"][0], "noise": False, "train": True}
    return Record(
        window_s=t_last - t0, steps=n, attempted=n,
        failed=int((~np.isfinite(losses)).sum()),
        # forward MACs plus the dW contraction, per step
        mac_ops=n * 4 * cfg["n_steps"] * b * cfg["n_in"]
        * cfg["hidden_layers"][0],
        launches={"fused_seq": (n, shape), "fused_seq_bptt": (n, shape)},
        span_names=BENCH_SPANS, longest_call=longest)


def release(ctx, st):
    st.p = st.m = st.step = st.data = None


def _leaves(params) -> dict:
    """{name: array} of a parameter tree, program's or reference's."""
    w_hid = params["w_hid"]
    w_hid = w_hid if isinstance(w_hid, (list, tuple)) else [w_hid]
    out = {f"w_hid.{i}": np.asarray(w, np.float64)
           for i, w in enumerate(w_hid)}
    out["w_out"] = np.asarray(params["w_out"], np.float64)
    return out


def norm_gap(prog: dict, ref: dict, moved: dict) -> float:
    """Worst leaf's |norm(prog) - norm(ref)| over the larger of the leaf's
    reference norm and the median leaf's; leaves whose reference gradient
    is under a thousandth of the median leaf's are left out."""
    med = statistics.median(np.linalg.norm(v) for v in ref.values())
    med_moved = statistics.median(moved.values())
    worst = 0.0
    for name, r in ref.items():
        if moved[name] < 1e-3 * med_moved:
            continue
        gap = abs(np.linalg.norm(prog[name]) - np.linalg.norm(r))
        worst = max(worst, gap / max(np.linalg.norm(r), med))
    return worst


def compare(losses, g1, delta, ref_losses, ref_g1, ref_delta) -> dict:
    moved = {k: float(np.linalg.norm(v)) for k, v in ref_g1.items()}
    return {
        "first_loss_gap": abs(losses[0] - ref_losses[0])
        / abs(ref_losses[0]),
        "grad_norm_gap": norm_gap(g1, ref_g1, moved),
        "update_norm_gap": norm_gap(delta, ref_delta, moved),
    }


def reference(cfg: dict, seed: int, batches, lr: float, dt=jnp.float32):
    """(losses, first gradient, parameter change) of the reference."""
    p0 = snn_ref.init_params(cfg, common.weight_key(seed))
    losses, g1, p = snn_ref.train_steps(
        p0, [tuple(jnp.asarray(x) for x in b) for b in batches],
        cfg["k"], lr, dt)
    p0 = _leaves(jax.device_get(p0))
    p = _leaves(p)
    return losses, _leaves(g1), {k: p[k] - p0[k] for k in p}


def check(ctx, st, rec):
    ref_losses, ref_g1, ref_delta = reference(
        ctx.cfg, ctx.seed, st.batches, ctx.traffic["lr"])
    p0, p = _leaves(st.p0), _leaves(st.p_checked)
    gaps = compare(st.losses, _leaves(st.g1), {k: p[k] - p0[k] for k in p},
                   ref_losses, ref_g1, ref_delta)
    return {k: common.check(k, v, ctx.limits) for k, v in gaps.items()}


def end_to_end(ctx, rec):
    return {"train_step_ms": 1e3 * rec["window_s"] / rec["steps"]}
