"""Serving: jit-able serve_step (one decode token for a batch of requests), a
small batched engine (prompt queue -> prefill -> decode rounds) used by the
serving example and tests, and a batched event-stream engine that runs SNN
inference through the fused macro-step kernel.

serve_step is what the decode_32k / long_500k dry-run cells lower: one new
token against a KV cache of the cell's sequence length.
"""

from __future__ import annotations

import dataclasses
import functools
import math
import time
from collections import deque
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import energy as energy_lib
from repro.models import lm
from repro.models import snn as snn_lib
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.serve import lifecycle

# Round-time estimation (see _round_ms_estimate).  The EMA keeps a
# cheap running estimate for the first few rounds; once at least
# ROUND_MS_P95_MIN_SAMPLES kernel rounds have been timed, deadline-risk
# slack switches to the exact p95 of the recent-sample window — an EMA
# tracks the *center* of a jittery distribution, while admission slack
# needs the *tail* (an optimistic estimate admits requests that then
# blow their deadline; ROADMAP flagged the EMA as near-meaningless in
# interpret mode for exactly this reason).
ROUND_MS_EMA_DECAY = 0.9          # weight on history per EMA update
ROUND_MS_P95_MIN_SAMPLES = 8      # exact-p95 takes over at this depth
ROUND_MS_SAMPLE_WINDOW = 512      # recent rounds kept for exact quantiles

# Fixed bucket edges for the per-request metric histograms.  ADC sweep
# depth is bounded by the ramp (2**code_bits - 1 = 15 for the paper's
# 4-bit code); ratios live in [0, 1].
ADC_STEP_BUCKETS = (1.0, 2.0, 3.0, 4.0, 5.0, 6.0, 8.0, 10.0, 12.0,
                    14.0, 15.0)
RATIO_BUCKETS = (0.05, 0.1, 0.2, 0.3, 0.4, 0.5, 0.6, 0.7, 0.8, 0.9, 1.0)


def build_serve_step(cfg: lm.LMConfig, mesh=None, *, temperature: float = 0.0):
    """Returns step(params, cache, tokens, pos, rng) ->
    (next_tokens (B,1), logits (B,V), cache)."""

    def serve_step(params, cache, tokens, pos, rng):
        logits, cache = lm.decode_step(params, cache, tokens, pos, cfg, mesh)
        logits = logits[:, :cfg.vocab_size]
        if temperature > 0.0:
            nxt = jax.random.categorical(rng, logits / temperature, axis=-1)
        else:
            nxt = jnp.argmax(logits, axis=-1)
        return nxt[:, None].astype(jnp.int32), logits, cache

    return serve_step


@dataclasses.dataclass
class Request:
    uid: int
    prompt: list[int]
    max_new_tokens: int = 16
    generated: list[int] = dataclasses.field(default_factory=list)

    @property
    def done(self) -> bool:
        return len(self.generated) >= self.max_new_tokens


@dataclasses.dataclass
class EventRequest:
    """One event-stream classification request: events (T, N_in) in {-1,0,1}.

    ``priority`` (higher wins) and ``deadline_ms`` (wall milliseconds from
    submission) feed the preemptive scheduler; both default to "no
    opinion", under which the engine behaves exactly like the plain
    continuous-batching engine (no preemption ever triggers).  ``state``
    walks the ``serve.lifecycle`` machine and always ends in a terminal
    state — COMPLETED, EXPIRED, or REJECTED.
    """

    uid: int
    events: Any                 # (T, N_in) array-like
    label: int | None = None
    logits: Any = None
    pred: int | None = None
    adc_steps: float | None = None   # mean early-stop ramp steps per time step
    density: float | None = None     # measured |event| rate (set on submit)
    skipped_block_ratio: float | None = None  # batch activity-plan skip rate
    key: Any = None                  # per-request PRNG key (continuous path)
    latency_ms: float | None = None  # submit -> eviction wall time
    queue_ms: float | None = None    # submit -> first admission wall time
    sops: float | None = None        # measured synaptic ops per time step
    priority: int = 0                # scheduler priority (higher preempts)
    deadline_ms: float | None = None  # SLO deadline, wall ms from submit
    state: str = lifecycle.QUEUED    # lifecycle state (see serve.lifecycle)
    preemptions: int = 0             # times this request was checkpointed out
    preempted_ms: float = 0.0        # total wall ms spent checkpointed out
    deadline_missed: bool | None = None  # completed after its deadline?
    _order: int | None = dataclasses.field(default=None, repr=False,
                                           compare=False)  # submission index
    _t_submit: float | None = dataclasses.field(default=None, repr=False,
                                                compare=False)
    _ckpt: Any = dataclasses.field(default=None, repr=False, compare=False)
    _not_before: int = dataclasses.field(default=0, repr=False, compare=False)
    _t_preempt_out: float | None = dataclasses.field(default=None, repr=False,
                                                     compare=False)
    _span: Any = dataclasses.field(default=None, repr=False, compare=False)


@functools.lru_cache(maxsize=None)
def _legacy_forward(cfg, fused: str, noise):
    """One jitted drain-path forward per (config, cadence, noise model).

    Module-level cache so every engine instance over the same config
    shares one compiled executable (a per-instance ``jax.jit(lambda ...)``
    would recompile per engine — ruinous for the serve benchmarks' warm
    trials).  ``cfg`` (frozen dataclass) and ``noise`` (NamedTuple) are
    hashable, so they can key the cache and close over the trace.
    """
    return jax.jit(lambda p, ev, key: snn_lib.forward_silicon(
        p, ev, cfg, key, fused=fused, noise=noise))


class SNNEventEngine:
    """Event-stream inference on the fused macro kernel, served either by
    step-granularity *continuous batching* (default) or by legacy
    drain-the-queue batches.

    **Continuous path** (``continuous=True``, auto-selected for
    time-major single-layer configs).  The engine keeps ``batch_slots``
    persistent serving slots whose LIF membrane — the SNN analog of an LM
    engine's KV cache — lives on device in a
    ``snn.SiliconStreamState`` and is carried across rounds.  Each round
    advances every occupied slot by ``round_steps`` time steps through
    one time-major fused kernel launch; between rounds, finished requests
    are evicted (their slot's accumulators are normalized by *their own*
    stream length, never the round count) and waiting requests are
    admitted into the freed slots mid-flight, with the slot state reset
    on admit.  Mixed stream lengths batch naturally — the batch shape is
    always ``(round_steps, batch_slots)``, so the jit cache holds one
    entry regardless of the traffic's length mix.

    Noise is *per-request* on this path: each request's counter-PRNG seed
    (from ``req.key``, folded from the engine seed by submission index)
    rides the kernel's ``row_ctl`` lane, and the clean-path SNL PRBS is a
    per-slot LFSR.  Served logits and ADC telemetry are therefore
    bitwise-identical to a one-shot batch-1
    ``forward_silicon(fused="seq")`` of the same request — independent of
    co-batched traffic, admission order, or scheduling policy.

    With ``pack_by_density=True`` the admission scheduler uses measured
    event density as its cost model: it fills free slots with the pending
    requests closest to the resident batch's mean density (quietest-first
    into an empty batch), so activity-gated block skipping — which is
    per row-*tile*, shared across co-resident slots — survives batching.
    Results are unchanged either way; only the work moves.

    **Legacy path** (``continuous=False``, and the automatic fallback for
    ``time_major=False`` or multi-layer stacks).  One jitted
    ``forward_silicon(fused=...)`` call per fixed-size batch of whole
    sequences, padded to ``batch_slots`` rows; batches are bucketed by
    stream length (one jit entry per distinct T served).  ``noise`` draws
    then come from the engine's per-batch key stream, as before.

    **Robustness layer** (this is what turns the round loop into something
    that can face real traffic; see ``docs/SERVING.md``):

    * *Validation*: ``submit()`` rejects malformed event tensors with the
      typed ``serve.lifecycle`` errors before anything is staged for a
      kernel launch (``validate=False`` opts out for trusted callers).
    * *Load shedding*: with ``max_pending`` set, the admission queue is
      bounded — an overflowing submit sheds the lowest-priority (then
      newest) queued request with the terminal ``REJECTED`` state instead
      of growing without bound.
    * *Deadlines*: a queued request whose ``deadline_ms`` passes before it
      can be admitted is retired with the terminal ``EXPIRED`` state
      (resident requests always run to completion — finishing beats
      killing mid-stream).
    * *Preemption* (continuous path, ``preemptive=True``): when the queue
      holds a higher-priority or deadline-at-risk request and no slot is
      free, the scheduler checkpoints the longest-running lowest-priority
      slot to host memory (``snn.SlotCheckpoint``) and admits the urgent
      request.  The victim re-enters the queue with exponential backoff
      (``backoff_rounds * 2**(preemptions-1)`` scheduling ticks) and
      resumes from its checkpoint — in any free slot, at its exact step
      offset — bitwise-identical to an uninterrupted run.  Thrash guards:
      a slot must be resident ``preempt_quantum`` rounds before it is a
      victim, a request is never preempted more than ``max_preemptions``
      times, and at most one preemption happens per scheduling tick.

    Raw-MAC telemetry stays off on both hot paths.
    """

    def __init__(self, cfg: snn_lib.SNNConfig, params, batch_slots: int = 64,
                 seed: int = 0, time_major: bool = True, noise=None,
                 pack_by_density: bool = True,
                 continuous: bool | None = None, round_steps: int = 8,
                 max_pending: int | None = None, preemptive: bool = True,
                 preempt_quantum: int = 1, max_preemptions: int = 3,
                 backoff_rounds: int = 1, risk_margin_ms: float | None = None,
                 validate: bool = True, tracer=None, metrics=None):
        self.cfg = cfg
        self.params = params
        self.b = batch_slots
        self.time_major = time_major
        self.noise = noise
        self.pack_by_density = pack_by_density
        self.pending: list[EventRequest] = []
        self.completed: list[EventRequest] = []
        self.rejected: list[EventRequest] = []
        self.expired: list[EventRequest] = []
        self._submitted = 0
        self._key = jax.random.PRNGKey(seed)
        self._base_key = jax.random.PRNGKey(seed)
        self._fused = "seq" if time_major else "step"
        supported = time_major and len(cfg.layer_widths) == 1
        if continuous is None:
            continuous = supported
        elif continuous and not supported:
            raise ValueError(
                "continuous batching needs the time-major fused kernel and "
                "a single-layer config; pass continuous=False (or leave it "
                "None to auto-select) for per-step cadence or stacks")
        self.continuous = continuous
        self.round_steps = round_steps
        self.max_pending = max_pending
        self.preemptive = preemptive
        self.preempt_quantum = preempt_quantum
        self.max_preemptions = max_preemptions
        self.backoff_rounds = backoff_rounds
        # deadline-risk margin: a deadline-bearing candidate counts as
        # at-risk when its estimated slack falls under this many wall ms.
        # None = auto (two rounds at the measured EMA round time).
        self.risk_margin_ms = risk_margin_ms
        self.validate = validate
        self.preemption_count = 0        # total preemptions (policy + forced)
        self._rounds_total = 0           # monotonic scheduling-tick counter
        self._round_ms = 0.0             # EMA wall ms per round (estimates)
        self._round_samples: deque[float] = deque(
            maxlen=ROUND_MS_SAMPLE_WINDOW)
        # observability: spans go to the engine tracer (falls back to the
        # process-global, which starts disabled — the zero-cost default);
        # metrics are always recorded into a per-engine registry so the
        # chaos harness can cross-check counters against *this* engine's
        # ledgers without bleed from other engines in the process.
        self._tracer = tracer
        self.metrics = metrics if metrics is not None \
            else obs_metrics.MetricsRegistry()
        m = self.metrics
        self._m_rounds = m.counter("rounds_total")
        self._m_round_ms = m.histogram("round_ms")
        self._m_admitted = m.counter("admitted_total")
        self._m_evicted = m.counter("evicted_total")
        self._m_preempted = m.counter("preempted_total")
        self._m_shed = m.counter("shed_total")
        self._m_expired = m.counter("expired_total")
        self._m_queue = m.gauge("queue_depth")
        self._m_occupancy = m.gauge("slot_occupancy")
        self._m_terminal = {
            s: m.counter("terminal_total", state=s)
            for s in sorted(lifecycle.TERMINAL_STATES)}
        self._m_latency = m.histogram("request_latency_ms")
        self._m_queue_wait = m.histogram("queue_wait_ms")
        self._m_adc = m.histogram("request_adc_steps",
                                  buckets=ADC_STEP_BUCKETS)
        self._m_skip = m.histogram("request_skipped_block_ratio",
                                   buckets=RATIO_BUCKETS)
        self._m_pulls = m.counter("host_pulls_total")
        self._m_conversions = m.counter("ima_conversions_total")
        self._m_key_calls = m.counter("admission_key_calls_total")
        # IMA-converted columns per slot-step: every branch of every soma
        # in NLD mode, every column in KWN mode
        self._columns = cfg.n_hidden * (cfg.n_branches if cfg.mode == "nld"
                                        else 1)
        # continuous-path slot table (host shadows of the device state)
        self._state = (snn_lib.silicon_stream_init(cfg, batch_slots)
                       if continuous else None)
        self._slot_req: list[EventRequest | None] = [None] * batch_slots
        self._slot_len = np.zeros(batch_slots, np.int32)
        self._slot_done = np.zeros(batch_slots, np.int32)
        self._slot_seed = np.zeros(batch_slots, np.int32)
        self._slot_admit_round = np.zeros(batch_slots, np.int64)

    @property
    def tracer(self) -> obs_trace.Tracer:
        """Engine tracer: the one passed at construction, else the
        process-global (resolved per access so ``set_tracer`` after
        engine construction still takes effect)."""
        t = self._tracer
        return t if t is not None else obs_trace.get_tracer()

    def _record_terminal(self, req: EventRequest) -> None:
        """Exactly-one-increment bookkeeping for a terminal transition.

        Every code path that appends to a terminal ledger (completed /
        rejected / expired) calls this exactly once, so
        ``terminal_total{state=...}`` always equals the ledger lengths —
        the invariant tests/test_obs.py and the chaos harness assert.
        """
        self._m_terminal[req.state].inc()

    def _observe_completed(self, req: EventRequest) -> None:
        """Feed the per-request telemetry histograms at completion."""
        if req.latency_ms is not None:
            self._m_latency.observe(req.latency_ms)
        if req.adc_steps is not None:
            self._m_adc.observe(req.adc_steps)
        if req.skipped_block_ratio is not None:
            self._m_skip.observe(req.skipped_block_ratio)

    def _observe_admitted(self, req: EventRequest, now: float) -> None:
        """Stamp the submit -> first admission wait (``queue_ms``)."""
        if req.queue_ms is None and req._t_submit is not None:
            req.queue_ms = (now - req._t_submit) * 1e3
            self._m_queue_wait.observe(req.queue_ms)

    def _to_host(self, x):
        """Pull a device array, or a pytree of them, to the host in one read.

        Every device->host read of a result or a seed goes through here:
        it counts once in ``host_pulls_total`` whether tracing is on or
        off, and it is the one read allowed under
        ``jax.transfer_guard_device_to_host("disallow")``, so serving
        under that guard proves the counter misses no pull.
        """
        self._m_pulls.inc()
        with jax.transfer_guard_device_to_host("allow"):
            return jax.device_get(x)

    def submit(self, req: EventRequest) -> EventRequest:
        """Enqueue a request; returns it with ``state`` set.

        Raises a typed ``serve.lifecycle`` error (``EmptyEventError`` /
        ``EventDtypeError`` / ``EventShapeError`` / ``NonFiniteEventError``
        / ``NonTernaryEventError``) if the event tensor violates the kernel
        input contract — nothing malformed ever reaches a launch.  With a
        bounded queue (``max_pending``), an overflowing submit sheds the
        lowest-priority / newest request instead: the shed request (which
        may be ``req`` itself) gets the terminal ``REJECTED`` state and is
        recorded in ``self.rejected``.

        Traced as an ``enqueue`` span (track ``admission``) holding its
        ``validate`` and ``density`` phases.
        """
        tr = self.tracer
        with tr.span("enqueue", track="admission",
                     args={"uid": req.uid} if tr.enabled else None):
            if self.validate:
                with tr.span("validate", track="admission"):
                    lifecycle.validate_events(req.events, self.cfg.n_in)
            if req.density is None:
                # host-side numpy: no device dispatch/sync on the submit path
                with tr.span("density", track="admission"):
                    ev = np.asarray(req.events)
                    req.density = float(np.count_nonzero(ev)) / ev.size
            return self._enqueue(req)

    def _enqueue(self, req: EventRequest) -> EventRequest:
        """Queue a validated request, shedding one if the queue is full."""
        req._order = self._submitted
        req._t_submit = time.perf_counter()
        req.state = lifecycle.QUEUED
        self._submitted += 1
        if self.max_pending is not None and \
                len(self.pending) >= self.max_pending:
            # shed the least valuable: lowest priority, then newest arrival
            # (never shed a preempted request holding a checkpoint — its
            # work would be lost; shedding fresh work is strictly cheaper)
            victims = [r for r in self.pending + [req] if r._ckpt is None]
            victim = min(victims or [req],
                         key=lambda r: (r.priority, -r._order))
            victim.state = lifecycle.REJECTED
            self.rejected.append(victim)
            self._m_shed.inc()
            self._record_terminal(victim)
            tr = self.tracer
            if tr.enabled:
                tr.instant(f"shed req{victim.uid}", track="scheduler",
                           args={"uid": victim.uid,
                                 "priority": victim.priority})
            if victim is req:
                return req
            self.pending.remove(victim)
        self.pending.append(req)
        self._m_queue.set(len(self.pending))
        return req

    # ------------------------------------------------------------------
    # Legacy drain path (continuous=False): fixed batches, whole sequences
    # ------------------------------------------------------------------

    def _run_batch(self, reqs: list[EventRequest]) -> list[EventRequest]:
        """One whole-sequence batch: a ``legacy_batch`` span holding its
        ``stage``, ``launch``, ``wait`` and ``readout`` phases."""
        tr = self.tracer
        batch_span = tr.begin("legacy_batch", track="scheduler")
        pulls0 = self._m_pulls.value
        now = time.perf_counter()
        for req in reqs:
            self._observe_admitted(req, now)
        stage = tr.begin("stage", track="scheduler")
        ev = jnp.stack([jnp.asarray(r.events, jnp.float32) for r in reqs])
        pad = self.b - ev.shape[0]
        if pad:
            ev = jnp.concatenate(
                [ev, jnp.zeros((pad,) + ev.shape[1:], ev.dtype)])
        if stage is not None:
            tr.end(stage, args={"bytes": ev.nbytes})
        with tr.span("launch", track="scheduler"):
            self._key, sub = jax.random.split(self._key)
            fwd = _legacy_forward(self.cfg, self._fused, self.noise)
            logits, tele = fwd(self.params, ev, sub)
        with tr.span("wait", track="scheduler"):
            jax.block_until_ready((logits, tele))
        t_done = time.perf_counter()
        with tr.span("readout", track="scheduler"):
            preds = jnp.argmax(logits, axis=-1)
            rows = list(logits)             # one unstacking dispatch
            preds, adc, sops, skipped = self._to_host(
                (preds, tele["adc_steps"], tele["sops"],
                 tele.get("skipped_block_ratio")))
            for i, req in enumerate(reqs):
                req.logits = rows[i]
                req.pred = int(preds[i])
                req.adc_steps = float(adc[i])
                req.sops = float(sops[i])
                if skipped is not None:
                    req.skipped_block_ratio = float(skipped[i])
                if req._t_submit is not None:
                    req.latency_ms = (t_done - req._t_submit) * 1e3
                req.state = lifecycle.COMPLETED
                if req.deadline_ms is not None and \
                        req.latency_ms is not None:
                    req.deadline_missed = req.latency_ms > req.deadline_ms
                self.completed.append(req)
                self._record_terminal(req)
                self._observe_completed(req)
        if batch_span is not None:
            tr.end(batch_span, args={
                "batch": len(reqs), "requests": len(reqs),
                "pulls": self._m_pulls.value - pulls0})
        return reqs

    def _take_bucket(self) -> list[EventRequest]:
        """Next batch off the queue: up to ``b`` requests sharing one T.

        The legacy launch stacks whole sequences, so a batch must be
        rectangular; bucketing by stream length (instead of the old
        ``jnp.stack`` crash) keeps results exact.  Each distinct T compiles
        its own jit entry — the engine's cache holds one entry *per stream
        length served*, not one total.
        """
        t0 = np.asarray(self.pending[0].events).shape[0]
        batch = [r for r in self.pending
                 if np.asarray(r.events).shape[0] == t0][:self.b]
        taken = {id(r) for r in batch}
        self.pending = [r for r in self.pending if id(r) not in taken]
        return batch

    def _run_legacy(self) -> list[EventRequest]:
        self._expire_pending()
        if self.pack_by_density:
            self.pending.sort(key=lambda r: (r.density or 0.0, r.uid))
        drained: list[EventRequest] = []
        while self.pending:
            drained.extend(self._run_batch(self._take_bucket()))
        drained.sort(key=lambda r: r._order if r._order is not None
                     else r.uid)
        return drained

    # ------------------------------------------------------------------
    # Continuous path: step-granularity rounds over persistent slots
    # ------------------------------------------------------------------

    def _key_fresh(self, fresh: list[tuple[int, EventRequest]]) -> int:
        """Key and seed one pass's fresh admissions in one device program.

        Each request gets its own key (folded from the engine seed by
        submission index unless the caller set ``req.key``), so its noise
        stream — and therefore its logits — are a pure function of the
        request, independent of co-batched traffic or admission order.
        A one-shot ``forward_silicon(p, ev[None], cfg, req.key,
        fused="seq", noise=...)`` reproduces the served result bitwise.
        One ``snn.silicon_stream_keys`` call per pass derives the keys and
        seed words (counted in ``admission_key_calls_total``); the derived
        keys are unstacked in one dispatch, and a noisy engine pulls the
        seed words in one read (clean serving never reads them).  Returns
        how many keys the pass derived.
        """
        orders = np.zeros(self.b, np.uint32)
        derive = np.zeros(self.b, bool)
        caller = {}
        for slot, req in fresh:
            if req.key is None:
                orders[slot], derive[slot] = req._order, True
            else:
                caller[slot] = jax.random.key_data(req.key)
        given = np.zeros((self.b, 2), np.uint32)
        if caller:
            given = jnp.asarray(given).at[np.asarray(list(caller))].set(
                jnp.stack(list(caller.values())))
        keys, seeds = snn_lib.silicon_stream_keys(
            self._base_key, orders, given, derive)
        self._m_key_calls.inc()
        if derive.any():
            rows = list(keys)               # one unstacking dispatch
            for slot, req in fresh:
                if derive[slot]:
                    req.key = rows[slot]
        if self.noise is not None:
            seeds = self._to_host(seeds)
            for slot, _ in fresh:
                self._slot_seed[slot] = seeds[slot]
        return int(derive.sum())

    # --- deadline bookkeeping -----------------------------------------

    def _expire_pending(self) -> None:
        """Retire queued requests whose deadline has already passed.

        Only *queued* requests expire — a resident request always runs to
        completion (its work is already partly paid for; finishing late
        beats discarding mid-stream).  Expired requests reach the terminal
        ``EXPIRED`` state and land in ``self.expired``.
        """
        if not any(r.deadline_ms is not None for r in self.pending):
            return
        now = time.perf_counter()
        keep: list[EventRequest] = []
        tr = self.tracer
        for r in self.pending:
            if r.deadline_ms is not None and r._t_submit is not None and \
                    (now - r._t_submit) * 1e3 > r.deadline_ms:
                r.state = lifecycle.EXPIRED
                self.expired.append(r)
                self._m_expired.inc()
                self._record_terminal(r)
                if tr.enabled:
                    tr.instant(f"expire req{r.uid}", track="scheduler",
                               args={"uid": r.uid,
                                     "deadline_ms": r.deadline_ms})
            else:
                keep.append(r)
        self.pending = keep

    def _round_ms_estimate(self) -> float:
        """Round-time estimate feeding the deadline-risk slack math.

        Exact p95 of the recent-round sample window once at least
        ``ROUND_MS_P95_MIN_SAMPLES`` kernel rounds have been timed — the
        pessimistic tail is what slack estimation needs — falling back
        to the EMA while the window is still warming up.
        """
        n = len(self._round_samples)
        if n >= ROUND_MS_P95_MIN_SAMPLES:
            s = sorted(self._round_samples)
            return s[min(n - 1, int(n * 0.95))]
        return self._round_ms

    def _slack_ms(self, req: EventRequest, now: float) -> float:
        """Estimated deadline slack in wall ms (+inf if no deadline).

        slack = deadline - elapsed - (remaining rounds x estimated round
        time; p95 of recent rounds once warm, EMA before that — see
        ``_round_ms_estimate``).  A checkpointed request's remaining
        work starts at its recorded step offset, so a mostly-done
        preempted request reads as *less* at-risk than a fresh one with
        the same deadline.
        """
        if req.deadline_ms is None or req._t_submit is None:
            return math.inf
        elapsed = (now - req._t_submit) * 1e3
        if req._ckpt is not None:
            t, done = req._ckpt.length, req._ckpt.steps_done
        else:
            t, done = np.asarray(req.events).shape[0], 0
        est = math.ceil((t - done) / self.round_steps) \
            * self._round_ms_estimate()
        return req.deadline_ms - elapsed - est

    # --- admission ----------------------------------------------------

    def _admit(self) -> tuple[int, int]:
        """Fill free slots from the queue; returns how many were admitted
        and how many of their keys were derived."""
        free = [i for i, r in enumerate(self._slot_req) if r is None]
        if not free or not self.pending:
            return 0, 0
        # backoff gate: a freshly preempted request sits out its
        # exponential-backoff window (measured in scheduling ticks, which
        # advance even on idle rounds, so the window always expires)
        eligible = [r for r in self.pending
                    if r._not_before <= self._rounds_total]
        if not eligible:
            return 0, 0
        scheduled = any(r.priority != 0 or r.deadline_ms is not None
                        or r._ckpt is not None for r in eligible)
        if scheduled:
            # urgency order: priority first, then tightest deadline slack,
            # then submission order (total order -> deterministic)
            now = time.perf_counter()
            eligible.sort(key=lambda r: (-r.priority,
                                         self._slack_ms(r, now), r._order))
        elif self.pack_by_density:
            active = [r.density or 0.0
                      for r in self._slot_req if r is not None]
            if active:
                # keep rounds density-homogeneous: nearest-density first
                target = sum(active) / len(active)
                eligible.sort(
                    key=lambda r: (abs((r.density or 0.0) - target),
                                   r._order))
            else:
                # empty batch: start from the quietest traffic
                eligible.sort(key=lambda r: (r.density or 0.0, r._order))
        chosen = eligible[:len(free)]
        taken = {id(r) for r in chosen}
        self.pending = [r for r in self.pending if id(r) not in taken]
        mask = np.zeros(self.b, bool)
        fresh: list[tuple[int, EventRequest]] = []
        tr = self.tracer
        now = time.perf_counter()
        for slot, req in zip(free, chosen):
            self._slot_req[slot] = req
            self._slot_admit_round[slot] = self._rounds_total
            req.state = lifecycle.RUNNING
            self._m_admitted.inc()
            self._observe_admitted(req, now)
            if tr.enabled:
                # residency span: one lane per slot, open until the
                # request leaves the slot (evict or preempt)
                req._span = tr.begin(
                    f"req{req.uid}", track=f"slot{slot:02d}",
                    args={"uid": req.uid, "priority": req.priority,
                          "resumed": req._ckpt is not None,
                          "queue_ms": req.queue_ms})
            if req._ckpt is not None:
                if req._t_preempt_out is not None:
                    # checkpoint dwell: wall time spent off-device since
                    # the preemption that produced this checkpoint
                    req.preempted_ms += (time.perf_counter() -
                                         req._t_preempt_out) * 1e3
                    req._t_preempt_out = None
                # re-admission: update the host shadows *first*, then push
                # the checkpoint into the slot.  Order matters — the
                # masked admit below rewrites the full length/seed vectors
                # from these shadows, so they must already carry the
                # restored values when fresh admits share this pass.
                ck = req._ckpt
                self._slot_len[slot] = ck.length
                self._slot_done[slot] = ck.steps_done
                self._slot_seed[slot] = ck.seed
                self._state = snn_lib.silicon_stream_restore(
                    self._state, slot, ck)
                req._ckpt = None
            else:
                self._slot_len[slot] = np.asarray(req.events).shape[0]
                self._slot_done[slot] = 0
                self._slot_seed[slot] = 0   # a noisy engine's comes next
                mask[slot] = True
                fresh.append((slot, req))
        derived = 0
        if fresh:
            derived = self._key_fresh(fresh)
            self._state = snn_lib.silicon_stream_admit(
                self._state, mask, self._slot_len, self._slot_seed)
        return len(chosen), derived

    # --- preemption ---------------------------------------------------

    def _preempt_slot(self, slot: int, backoff: bool = True) -> EventRequest:
        """Checkpoint slot ``slot`` to host memory and requeue its request."""
        req = self._slot_req[slot]
        req._ckpt = snn_lib.silicon_stream_save(self._state, slot)
        req.state = lifecycle.PREEMPTED
        req.preemptions += 1
        req._t_preempt_out = time.perf_counter()
        self.preemption_count += 1
        self._m_preempted.inc()
        if req._span is not None:
            self.tracer.end(req._span, args={"outcome": "preempted",
                                             "steps_done":
                                                 int(self._slot_done[slot])})
            req._span = None
        if backoff:
            req._not_before = (self._rounds_total + self.backoff_rounds *
                               2 ** (req.preemptions - 1))
        self._slot_req[slot] = None
        self.pending.append(req)
        return req

    def _maybe_preempt(self) -> None:
        """One scheduling decision: preempt at most one slot per tick.

        Fires only when the batch is full, the best eligible queued
        request outranks the weakest resident one (strictly higher
        priority, or deadline-at-risk at >= priority), and the victim has
        been resident at least ``preempt_quantum`` ticks with fewer than
        ``max_preemptions`` prior preemptions.  The one-per-tick cap plus
        quantum plus exponential backoff is the anti-thrash budget.
        """
        if not (self.preemptive and self.continuous and self.pending):
            return
        if any(r is None for r in self._slot_req):
            return                      # a free slot: admission handles it
        eligible = [r for r in self.pending
                    if r._not_before <= self._rounds_total]
        if not eligible:
            return
        now = time.perf_counter()
        cand = min(eligible, key=lambda r: (-r.priority,
                                            self._slack_ms(r, now),
                                            r._order))
        victims = [(i, r) for i, r in enumerate(self._slot_req)
                   if self._rounds_total - self._slot_admit_round[i]
                   >= self.preempt_quantum
                   and r.preemptions < self.max_preemptions]
        if not victims:
            return
        # weakest resident: lowest priority, then longest resident
        slot, victim = min(victims,
                           key=lambda iv: (iv[1].priority,
                                           self._slot_admit_round[iv[0]],
                                           iv[1]._order))
        margin = (2.0 * self._round_ms_estimate()
                  if self.risk_margin_ms is None else self.risk_margin_ms)
        at_risk = self._slack_ms(cand, now) < margin
        if cand.priority > victim.priority or \
                (at_risk and cand.priority >= victim.priority):
            self._preempt_slot(slot)

    def preempt_request(self, uid: int, at_step: int | None = None,
                        backoff: bool = True) -> EventRequest:
        """Force-preempt a resident request (fault-injection / test hook).

        With ``at_step`` the stream is first advanced to exactly that
        absolute offset — including offsets that are *not* multiples of
        ``round_steps`` — by running partial rounds (the whole batch
        advances together, so every co-resident slot stays bitwise-exact;
        see ``forward_silicon_stream``).  The slot is then checkpointed to
        host memory and the request requeued (``PREEMPTED``).  Call it
        from a ``run(round_hook=...)`` callback to inject preemptions at
        randomized offsets mid-serve.
        """
        if not self.continuous:
            raise RuntimeError("preemption requires the continuous path")
        slot = next((i for i, r in enumerate(self._slot_req)
                     if r is not None and r.uid == uid), None)
        if slot is None:
            raise KeyError(f"request {uid} is not resident in any slot")
        if at_step is not None:
            done, length = int(self._slot_done[slot]), \
                int(self._slot_len[slot])
            if not done <= at_step < length:
                raise ValueError(
                    f"at_step={at_step} outside [{done}, {length}) for "
                    f"request {uid}")
            while int(self._slot_done[slot]) < at_step:
                self._round(min(self.round_steps,
                                at_step - int(self._slot_done[slot])))
        return self._preempt_slot(slot, backoff=backoff)

    def _round(self, r: int | None = None) -> None:
        """Advance every occupied slot by ``r`` time steps (one launch).

        ``r`` defaults to the regular ``round_steps`` cadence; smaller
        values are the *partial rounds* the preemption path uses to stop a
        stream at a non-round-aligned offset (each distinct ``r`` compiles
        one jit entry, bounded by ``round_steps``).  Traced as a ``round``
        span holding its ``stage`` (host buffer and transfer) and
        ``launch`` phases; its args give the kernel's converted
        ``columns`` and the ``conversions`` the round asks of the IMA
        (active slot-steps times columns), which ``ima_conversions_total``
        sums whether tracing is on or off.
        """
        r = self.round_steps if r is None else r
        busy = np.array([req is not None for req in self._slot_req])
        conversions = self._columns * int(np.sum(np.minimum(
            r, self._slot_len - self._slot_done)[busy]))
        tr = self.tracer
        span = tr.begin("round", track="scheduler")
        stage = tr.begin("stage", track="scheduler")
        ev = np.zeros((r, self.b, self.cfg.n_in), np.float32)
        for i, req in enumerate(self._slot_req):
            if req is None:
                continue
            chunk = np.asarray(req.events,
                               np.float32)[self._slot_done[i]:
                                           self._slot_done[i] + r]
            ev[:chunk.shape[0], i, :] = chunk
        events = jnp.asarray(ev)
        if stage is not None:
            tr.end(stage, args={"bytes": ev.nbytes})
        with tr.span("launch", track="scheduler"):
            self._state = snn_lib.forward_silicon_stream(
                self.params, events, self.cfg, self._state,
                noise=self.noise)
        self._slot_done = np.minimum(self._slot_done + r, self._slot_len)
        self._m_rounds.inc()
        self._m_conversions.inc(conversions)
        if span is not None:
            tr.end(span, args={"steps": r, "active": self.active,
                               "columns": self._columns,
                               "conversions": conversions})

    def _evict(self) -> list[EventRequest]:
        """Retire every slot whose stream has ended: an ``evict`` span
        holding, when a request finishes, its ``wait`` (the device
        finishing the round) and ``readout`` phases."""
        tr = self.tracer
        span = tr.begin("evict", track="scheduler")
        pulls0 = self._m_pulls.value
        finished = [i for i, req in enumerate(self._slot_req)
                    if req is not None
                    and self._slot_done[i] >= self._slot_len[i]]
        out: list[EventRequest] = []
        if finished:
            # the first pull would block here anyway; waiting first keeps
            # device time out of the readout
            with tr.span("wait", track="scheduler"):
                jax.block_until_ready(self._state)
            with tr.span("readout", track="scheduler"):
                mask = np.zeros(self.b, bool)
                mask[finished] = True
                logits, *scalars = snn_lib.silicon_stream_readout(
                    self._state, self.params["w_out"], mask)
                rows = list(logits)         # one unstacking dispatch
                pred, adc, sops, skip = self._to_host(scalars)
                out = [self._complete_slot(i, rows[i], pred[i], adc[i],
                                           sops[i], skip[i])
                       for i in finished]
        if span is not None:
            tr.end(span, args={"requests": len(out),
                               "pulls": self._m_pulls.value - pulls0})
        return out

    def _complete_slot(self, i: int, logits: jax.Array, pred, adc, sops,
                       skip) -> EventRequest:
        """Retire slot ``i``'s request with its answer, already read back:
        the device logits row, and on the host the argmax and the per-step
        means of the accumulators (divided on the device, as one-shot
        telemetry is)."""
        req = self._slot_req[i]
        req.logits = logits
        req.pred = int(pred)
        req.adc_steps = float(adc)
        req.sops = float(sops)
        req.skipped_block_ratio = float(skip)
        if req._t_submit is not None:
            req.latency_ms = (time.perf_counter() - req._t_submit) * 1e3
        req.state = lifecycle.COMPLETED
        if req.deadline_ms is not None and req.latency_ms is not None:
            req.deadline_missed = req.latency_ms > req.deadline_ms
        self._slot_req[i] = None
        self.completed.append(req)
        self._m_evicted.inc()
        self._record_terminal(req)
        self._observe_completed(req)
        if req._span is not None:
            self.tracer.end(req._span,
                            args={"outcome": "completed",
                                  "latency_ms": req.latency_ms,
                                  "preemptions": req.preemptions})
            req._span = None
        return req

    @property
    def active(self) -> int:
        """Occupied slot count (continuous path)."""
        return sum(r is not None for r in self._slot_req)

    def run(self, max_rounds: int | None = None,
            round_hook: Callable[["SNNEventEngine"], None] | None = None
            ) -> list[EventRequest]:
        """Serve the queue; returns the requests completed by *this* call,
        in submission order.

        Continuous path (default): rounds of ``round_steps`` time steps
        over the persistent slot batch — new requests are admitted into
        free slots *between rounds* (density-aware when
        ``pack_by_density``, urgency-ordered when any queued request
        carries a priority/deadline), finished requests are evicted as
        soon as their own stream ends, and the per-slot LIF membrane
        carries across rounds on device.  Each tick also expires
        dead-on-arrival queued requests and makes at most one preemption
        decision (see ``_maybe_preempt``).  ``max_rounds`` bounds this
        call (leaving unfinished requests resident for the next
        ``run()``).  ``round_hook(engine)``, if given, fires after every
        tick's eviction — the chaos harness uses it to inject forced
        preemptions at arbitrary step offsets mid-serve.

        Legacy path (``continuous=False``): drains in fixed whole-sequence
        batches, bucketed by stream length.

        Either way the returned list covers only requests drained by this
        call — history accumulates in ``self.completed`` (and
        ``self.expired`` / ``self.rejected`` for the shed paths) — and
        scheduling never leaks into result order (always submission
        order) or result values (noise is per-request on the continuous
        path; the legacy key stream is per-batch as before).
        """
        if not self.continuous:
            return self._run_legacy()
        drained: list[EventRequest] = []
        tr = self.tracer
        rounds = 0
        while self.pending or self.active:
            if max_rounds is not None and rounds >= max_rounds:
                break
            tick = tr.begin("tick", track="scheduler")
            h = tr.begin("expire", track="scheduler")
            self._expire_pending()
            tr.end(h)
            if not (self.pending or self.active):
                tr.end(tick)
                break
            h = tr.begin("preempt", track="scheduler")
            self._maybe_preempt()
            tr.end(h)
            h = tr.begin("admit", track="scheduler")
            pulls0 = self._m_pulls.value
            admitted, keys = self._admit()
            if h is not None:
                tr.end(h, args={"admitted": admitted, "keys": keys,
                                "pulls": self._m_pulls.value - pulls0})
            self._m_queue.set(len(self.pending))
            self._m_occupancy.set(self.active)
            ran = self.active > 0
            t0 = time.perf_counter()
            if ran:
                self._round()
            drained.extend(self._evict())
            if ran:
                # round-time estimators, fed only by ticks that launched
                # a kernel (idle ticks are microseconds and would poison
                # the slack estimates): EMA for warmup, an exact sample
                # window for p50/p95, and the mergeable histogram export
                dt = (time.perf_counter() - t0) * 1e3
                self._round_ms = (
                    dt if self._round_ms == 0.0
                    else ROUND_MS_EMA_DECAY * self._round_ms +
                    (1.0 - ROUND_MS_EMA_DECAY) * dt)
                self._round_samples.append(dt)
                self._m_round_ms.observe(dt)
            if round_hook is not None:
                round_hook(self)
                drained.extend(self._evict())
            # tick advances even when idle: backoff windows are measured
            # in ticks and must expire with zero active slots too
            self._rounds_total += 1
            rounds += 1
            tr.end(tick)
        drained.sort(key=lambda r: r._order if r._order is not None
                     else r.uid)
        return drained

    def energy_report(self, dataset: str) -> dict:
        """Serving-side energy estimate from the served traffic's measured
        statistics, through the calibrated per-component model
        (core.energy).

        KWN mode: ``kwn_step_energy`` at the dataset's calibrated input
        spike rate, with the analytic early-stop saving replaced by the
        mean ADC step count the KWN controller reported.  NLD mode:
        ``nld_step_energy`` for the configured activation at the input
        spike rate the traffic measured (its SOPs per step over the layer's
        ``n_in * n_hidden`` synapses), converting every branch column on
        the full ramp (NLD has no early stop); the dataset is checked but
        its calibrated rate is not used.

        Every statistic in the report — ADC steps, spike rate, energy, and
        the skipped-block ratio — is computed over the same population: the
        completed requests that carry measured ``adc_steps`` (and, in NLD
        mode, measured ``sops``).  Returns ``{}`` (documented contract, not
        an error) when that population is empty.

        Besides the population means, the report carries a
        ``per_request`` table (one row per completed request: uid,
        latency, measured ADC steps, per-request pJ/SOP from *that
        request's* statistics, density) and — when latencies were
        measured — the serving SLO summary ``latency_ms_mean`` /
        ``latency_ms_p50`` / ``latency_ms_p95``.
        """
        nld = self.cfg.mode == "nld"
        done = [r for r in self.completed if r.adc_steps is not None
                and (r.sops is not None or not nld)]
        if not done:
            return {}
        if dataset not in energy_lib.SPIKE_RATES:
            raise ValueError(
                f"unknown dataset {dataset!r} for the calibrated spike rate; "
                f"expected one of {sorted(energy_lib.SPIKE_RATES)}")
        synapses = float(self.cfg.n_in * self.cfg.n_hidden)

        def rate_of(r):
            return r.sops / synapses if nld else energy_lib.SPIKE_RATES[dataset]

        def energy(rate, steps):
            """(pJ per macro step, pJ per SOP) at this spike rate and ramp
            depth."""
            bd = (energy_lib.nld_step_energy(rate, self.cfg.activation) if nld
                  else energy_lib.kwn_step_energy(self.cfg.k, rate,
                                                  adc_steps=steps))
            sops = energy_lib.sops_per_step(rate)
            return bd.total, (bd.total / sops if sops > 0 else None)

        mean_steps = sum(r.adc_steps for r in done) / len(done)
        mean_rate = sum(rate_of(r) for r in done) / len(done)
        pj_step, pj_sop = energy(mean_rate, mean_steps)
        full = 2 ** self.cfg.code_bits - 1
        rep = {
            "requests": len(done),
            "mean_adc_steps": mean_steps,
            "measured_adc_saving": 1.0 - mean_steps / full,
            "spike_rate": mean_rate,
            "pj_per_step": pj_step,
            "pj_per_sop": pj_sop,
        }
        # same population as the ADC/energy stats above — a request that
        # carries a skip ratio but no adc_steps must not dilute the mean
        skipped = [r.skipped_block_ratio for r in done
                   if r.skipped_block_ratio is not None]
        if skipped:
            # measured activity-plan saving, next to the early-stop saving
            rep["mean_skipped_block_ratio"] = sum(skipped) / len(skipped)
        rep["per_request"] = [
            {"uid": r.uid,
             "latency_ms": r.latency_ms,
             # checkpoint dwell: wall ms spent checkpointed off-device.
             # latency_ms includes it, so fairness analysis can separate
             # "ran slowly" from "sat preempted" per request.
             "preempted_ms": r.preempted_ms,
             "adc_steps": r.adc_steps,
             "pj_per_sop": energy(rate_of(r), r.adc_steps)[1],
             "density": r.density}
            for r in done]
        lat = sorted(r.latency_ms for r in done if r.latency_ms is not None)
        if lat:
            rep["latency_ms_mean"] = sum(lat) / len(lat)
            rep["latency_ms_p50"] = lat[len(lat) // 2]
            rep["latency_ms_p95"] = lat[min(len(lat) - 1,
                                            int(len(lat) * 0.95))]
        if self._round_samples:
            # exact quantiles over the recent kernel-round window (the
            # same samples that feed the round_ms histogram metric and
            # the deadline-slack p95) — replaces squinting at the EMA
            rs = sorted(self._round_samples)
            rep["round_ms_p50"] = rs[len(rs) // 2]
            rep["round_ms_p95"] = rs[min(len(rs) - 1,
                                         int(len(rs) * 0.95))]
        # serving SLO ledger: every submission's fate is visible here
        rep["preemptions"] = self.preemption_count
        rep["rejected"] = len(self.rejected)
        rep["expired"] = len(self.expired)
        rep["deadline_misses"] = sum(
            1 for r in self.completed if r.deadline_missed)
        return rep


class BatchedEngine:
    """Minimal continuous-batching engine: fixed B slots, requests are
    admitted as slots free, prefill runs token-by-token through the decode
    path (teacher forcing), then decode until each request completes."""

    def __init__(self, cfg: lm.LMConfig, params, batch_slots: int = 4,
                 s_max: int = 256, mesh=None):
        self.cfg = cfg
        self.params = params
        self.b = batch_slots
        self.s_max = s_max
        self.step_fn = jax.jit(build_serve_step(cfg, mesh))
        self.cache = lm.init_cache(cfg, batch_slots, s_max)
        self.pos = jnp.zeros((batch_slots,), jnp.int32)
        self.slots: list[Request | None] = [None] * batch_slots
        self.pending: list[Request] = []
        self.completed: list[Request] = []
        self._next_token = jnp.zeros((batch_slots, 1), jnp.int32)
        self._rng = jax.random.PRNGKey(0)

    def submit(self, req: Request):
        self.pending.append(req)

    def _admit(self):
        for i in range(self.b):
            if self.slots[i] is None and self.pending:
                req = self.pending.pop(0)
                self.slots[i] = req
                # prefill: feed prompt tokens through decode path, one
                # fresh key per step (sampling temperature > 0 must not
                # see the same draw at every prompt position)
                for t, tok in enumerate(req.prompt):
                    self._rng, sub = jax.random.split(self._rng)
                    toks = self._next_token.at[i, 0].set(tok)
                    pos = self.pos.at[i].set(t)
                    nxt, _, self.cache = self.step_fn(
                        self.params, self.cache, toks, pos, sub)
                    self._next_token = self._next_token.at[i].set(nxt[i])
                self.pos = self.pos.at[i].set(len(req.prompt))

    def run(self, max_rounds: int = 64):
        # max_rounds budgets *decode* rounds — admission/prefill work is
        # never charged against it
        rounds = 0
        while self.pending or any(self.slots):
            self._admit()
            if not any(self.slots):
                break
            if rounds >= max_rounds:
                break
            rounds += 1
            self._rng, sub = jax.random.split(self._rng)
            nxt, _, self.cache = self.step_fn(self.params, self.cache,
                                              self._next_token, self.pos, sub)
            self._next_token = nxt
            self.pos = self.pos + jnp.array(
                [1 if s is not None else 0 for s in self.slots], jnp.int32)
            for i, req in enumerate(self.slots):
                if req is None:
                    continue
                req.generated.append(int(nxt[i, 0]))
                if req.done or int(self.pos[i]) >= self.s_max - 1:
                    self.completed.append(req)
                    self.slots[i] = None
        return self.completed
