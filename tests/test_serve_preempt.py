"""Preemptive serving: checkpoint/restore parity, scheduler policy, shedding.

The contract under test (docs/SERVING.md): a request that is preempted —
checkpointed to host memory at an arbitrary step offset, possibly restored
into a *different* slot, possibly preempted again — produces logits and ADC
telemetry **bitwise identical** to an uninterrupted one-shot batch-1
``forward_silicon(fused="seq")`` run, clean and noisy.  Plus the policy
layer around it: typed submit-time validation, bounded-queue load shedding,
deadline expiry, priority preemption with quantum/backoff/max-preemption
budgets, and submission-order results under every scheduling order.

The randomized sweeps are seeded and parametrized so they always run; the
``@given`` properties upgrade them when hypothesis is installed (see
tests/_hypothesis_compat.py).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ima as ima_lib
from repro.models import snn as snn_lib
from repro.serve import lifecycle
from repro.serve.engine import EventRequest, SNNEventEngine

from tests._hypothesis_compat import given, settings, st


@pytest.fixture(scope="module", autouse=True)
def _drop_compile_caches():
    """Release this module's compiled executables at teardown.

    Same rationale as tests/test_serve_engine.py: the parity matrix here
    compiles many interpret-mode Pallas entries (one-shot per stream
    length, stream rounds per extent R including partial rounds), and
    jaxlib 0.4.36's CPU compiler has segfaulted when a later module
    compiles its largest graph on top of all of them.
    """
    yield
    jax.clear_caches()


def _cfg(**kw):
    base = dict(n_in=32, n_hidden=16, n_classes=3, n_steps=8, k=4)
    base.update(kw)
    return snn_lib.SNNConfig(**base)


def _events(key, t, n_in=32, rate=0.25):
    return np.asarray(jax.random.bernoulli(key, rate, (t, n_in)), np.float32)


def _setup(**kw):
    cfg = _cfg(**kw)
    p = snn_lib.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, p


def _one_shot(p, cfg, req, noise=None):
    logits, tele = snn_lib.forward_silicon(
        p, jnp.asarray(req.events)[None], cfg, req.key, fused="seq",
        noise=noise)
    return logits[0], float(tele["adc_steps"][0])


def _assert_parity(engine, p, cfg, reqs, noise=None):
    for r in reqs:
        assert r.state == lifecycle.COMPLETED
        ref_logits, ref_adc = _one_shot(p, cfg, r, noise=noise)
        np.testing.assert_array_equal(np.asarray(r.logits),
                                      np.asarray(ref_logits))
        assert r.adc_steps == ref_adc


_NOISE = ima_lib.IMANoiseModel()


class TestCheckpointRestore:
    """snn.SlotCheckpoint round-trips, including cross-slot relocation."""

    @pytest.mark.fast
    def test_save_restore_same_slot_roundtrip(self):
        cfg, p = _setup()
        state = snn_lib.silicon_stream_init(cfg, 4)
        state = snn_lib.silicon_stream_admit(
            state, np.array([False, True, False, False]),
            np.array([0, 12, 0, 0], np.int32),
            np.array([0, 77, 0, 0], np.int32))
        ev = np.zeros((4, 4, cfg.n_in), np.float32)
        ev[:, 1] = _events(jax.random.PRNGKey(1), 4)
        state = snn_lib.forward_silicon_stream(p, jnp.asarray(ev), cfg, state)
        ck = snn_lib.silicon_stream_save(state, 1)
        assert ck.steps_done == 4 and ck.length == 12 and ck.seed == 77
        restored = snn_lib.silicon_stream_restore(state, 1, ck)
        for a, b in zip(restored, state):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    @pytest.mark.fast
    @pytest.mark.parametrize("noise", [None, _NOISE],
                             ids=["clean", "noisy"])
    def test_cross_slot_restore_is_bitwise(self, noise):
        """Finish a stream half in slot 0, half in slot 3: same answer.

        Relocatability is the row_ctl row-id-0 property — nothing in the
        noise keying sees the physical slot index.
        """
        cfg, p = _setup()
        t = 14
        req = EventRequest(uid=0, events=_events(jax.random.PRNGKey(5), t),
                           key=jax.random.fold_in(jax.random.PRNGKey(9), 0))
        seed = 0 if noise is None else int(snn_lib._noise_seed(req.key))

        def _admit_one(state, slot, length):
            mask = np.zeros(4, bool)
            mask[slot] = True
            lens = np.zeros(4, np.int32)
            lens[slot] = length
            seeds = np.zeros(4, np.int32)
            seeds[slot] = seed
            return snn_lib.silicon_stream_admit(state, mask, lens, seeds)

        def _step(state, slot, lo, hi):
            ev = np.zeros((hi - lo, 4, cfg.n_in), np.float32)
            ev[:, slot] = np.asarray(req.events)[lo:hi]
            return snn_lib.forward_silicon_stream(
                p, jnp.asarray(ev), cfg, state, noise=noise)

        # uninterrupted run, slot 0
        ref = _step(_admit_one(snn_lib.silicon_stream_init(cfg, 4), 0, t),
                    0, 0, t)
        # preempted at step 6 (not a multiple of anything), moved to slot 3
        state = _step(_admit_one(snn_lib.silicon_stream_init(cfg, 4), 0, t),
                      0, 0, 6)
        ck = snn_lib.silicon_stream_save(state, 0)
        state = snn_lib.silicon_stream_restore(
            snn_lib.silicon_stream_init(cfg, 4), 3, ck)
        state = _step(state, 3, 6, t)
        for field in ("v", "counts", "adc", "sops"):
            np.testing.assert_array_equal(
                np.asarray(getattr(ref, field)[0]),
                np.asarray(getattr(state, field)[3]), err_msg=field)


class TestPreemptionParity:
    """Engine-level: preempted-and-resumed == never-preempted, bitwise."""

    @pytest.mark.fast
    @pytest.mark.parametrize("noise", [None, _NOISE],
                             ids=["clean", "noisy"])
    def test_forced_preempt_nonaligned_offset(self, noise):
        """Preempt mid-round at a non-multiple of round_steps; resume."""
        cfg, p = _setup()
        key = jax.random.PRNGKey(2)
        lengths = [16, 12, 20, 8, 14]
        engine = SNNEventEngine(cfg, p, batch_slots=2, seed=4, round_steps=4,
                                noise=noise)
        reqs = [EventRequest(uid=i, events=_events(
            jax.random.fold_in(key, i), t)) for i, t in enumerate(lengths)]
        for r in reqs:
            engine.submit(r)
        fired = []

        def hook(eng):
            # once: stop request 0 at absolute step 6 (round cadence is 4)
            if not fired and any(r is not None and r.uid == 0
                                 for r in eng._slot_req):
                if int(eng._slot_done[[s is not None and s.uid == 0
                                       for s in eng._slot_req].index(True)]
                       ) >= 4:
                    victim = eng.preempt_request(0, at_step=6, backoff=False)
                    assert victim.state == lifecycle.PREEMPTED
                    assert victim._ckpt.steps_done == 6
                    fired.append(True)

        done = engine.run(round_hook=hook)
        assert fired and engine.preemption_count == 1
        assert [r.uid for r in done] == [0, 1, 2, 3, 4]
        _assert_parity(engine, p, cfg, reqs, noise=noise)

    @pytest.mark.fast
    @pytest.mark.parametrize("noise", [None, _NOISE],
                             ids=["clean", "noisy"])
    def test_readmission_shares_a_pass_with_fresh_admits(self, noise):
        """One admission pass restores a checkpoint and admits two fresh
        requests, one with a caller-set key: the checkpoint keeps its
        stored seed, the caller's key is kept, and every request still
        matches one-shot ``forward_silicon`` bitwise."""
        from repro.obs import trace as obs_trace
        cfg, p = _setup()
        tracer = obs_trace.Tracer()
        engine = SNNEventEngine(cfg, p, batch_slots=3, seed=12,
                                round_steps=4, noise=noise,
                                pack_by_density=False, tracer=tracer)
        key = jax.random.PRNGKey(21)
        own = jax.random.PRNGKey(77)
        reqs = [engine.submit(EventRequest(
            uid=i, events=_events(jax.random.fold_in(key, i), t),
            key=own if i == 4 else None))
            for i, t in enumerate([12, 4, 4, 8, 6])]
        fired = []

        def hook(eng):
            # after the first round requests 1 and 2 have left; request 0
            # leaves too, so the next pass fills all three slots
            if not fired:
                eng.preempt_request(0, backoff=False)
                fired.append(True)

        done = engine.run(round_hook=hook)
        assert [r.uid for r in done] == [0, 1, 2, 3, 4]
        assert reqs[4].key is own
        passes = [s[4] for s in tracer.spans()
                  if s[0] == "admit" and s[4]["admitted"]]
        assert [(a["admitted"], a["keys"]) for a in passes] == \
            [(3, 3), (3, 1)]
        if noise is not None:
            assert [a["pulls"] for a in passes] == [1, 1]
        _assert_parity(engine, p, cfg, reqs, noise=noise)

    @pytest.mark.parametrize("activation", ["relu", "quadratic"])
    def test_nld_slot_preempted_and_resumed_bitwise(self, activation):
        """An NLD slot checkpointed at a non-aligned step and restored
        into whichever slot is free finishes bitwise equal to one-shot."""
        cfg = snn_lib.SNNConfig(n_in=256, n_hidden=32, n_classes=3,
                                n_steps=10, mode="nld", n_branches=2,
                                activation=activation)
        p = snn_lib.init_params(cfg, jax.random.PRNGKey(7))
        key = jax.random.PRNGKey(8)
        engine = SNNEventEngine(cfg, p, batch_slots=2, seed=6, round_steps=4)
        reqs = []
        for i, t in enumerate([13, 10, 7, 9]):
            ev = np.asarray(jax.random.randint(jax.random.fold_in(key, i),
                                               (t, cfg.n_in), -1, 2),
                            np.float32)
            ev *= np.asarray(jax.random.bernoulli(
                jax.random.fold_in(key, 100 + i), 0.2, ev.shape))
            reqs.append(engine.submit(EventRequest(uid=i, events=ev)))
        fired = []

        def hook(eng):
            if not fired and 0 in [r.uid for r in eng._slot_req if r]:
                victim = eng.preempt_request(0, at_step=6, backoff=False)
                assert victim._ckpt.steps_done == 6
                fired.append(True)

        done = engine.run(round_hook=hook)
        assert fired and engine.preemption_count == 1
        assert [r.uid for r in done] == [0, 1, 2, 3]
        _assert_parity(engine, p, cfg, reqs)

    @pytest.mark.parametrize("noise", [None, _NOISE],
                             ids=["clean", "noisy"])
    @pytest.mark.parametrize("case", range(4))
    def test_randomized_offsets_sweep(self, noise, case):
        """Seeded fuzz: random lengths, random victims, random offsets."""
        cfg, p = _setup()
        rng = np.random.default_rng(100 + case)
        key = jax.random.PRNGKey(40 + case)
        n = 6
        lengths = rng.integers(5, 24, size=n)
        engine = SNNEventEngine(cfg, p, batch_slots=3,
                                seed=int(rng.integers(0, 99)), round_steps=4,
                                noise=noise)
        reqs = [EventRequest(uid=i, events=_events(
            jax.random.fold_in(key, i), int(t)))
            for i, t in enumerate(lengths)]
        order = rng.permutation(n)          # randomized admission order
        for i in order:
            engine.submit(reqs[i])
        budget = [2]                        # up to two forced preemptions

        def hook(eng):
            if not budget[0]:
                return
            live = [(i, r) for i, r in enumerate(eng._slot_req)
                    if r is not None]
            if not live:
                return
            slot, victim = live[int(rng.integers(0, len(live)))]
            done, length = int(eng._slot_done[slot]), int(eng._slot_len[slot])
            if done >= length - 1:
                return                      # nothing left to preempt
            at = int(rng.integers(done, length))  # any offset, incl. done
            if at == done:
                eng.preempt_request(victim.uid, backoff=False)
            else:
                eng.preempt_request(victim.uid, at_step=at, backoff=False)
            budget[0] -= 1

        done = engine.run(round_hook=hook)
        # results come back in *submission* order — here, the permutation
        assert [r.uid for r in done] == [int(i) for i in order]
        _assert_parity(engine, p, cfg, reqs, noise=noise)

    @pytest.mark.fast
    def test_double_preemption_same_request(self):
        """Preempt the same stream twice (two checkpoints) — still exact."""
        cfg, p = _setup()
        engine = SNNEventEngine(cfg, p, batch_slots=2, seed=1, round_steps=4,
                                noise=_NOISE)
        reqs = [EventRequest(uid=i, events=_events(
            jax.random.fold_in(jax.random.PRNGKey(8), i), t))
            for i, t in enumerate([18, 9, 7])]
        for r in reqs:
            engine.submit(r)
        hits = []

        def hook(eng):
            if len(hits) >= 2:
                return
            slot = next((i for i, r in enumerate(eng._slot_req)
                         if r is not None and r.uid == 0), None)
            if slot is None:
                return
            done = int(eng._slot_done[slot])
            at = 5 if not hits else 11
            if done < at < int(eng._slot_len[slot]):
                eng.preempt_request(0, at_step=at, backoff=False)
                hits.append(at)

        engine.run(round_hook=hook)
        assert hits == [5, 11] and reqs[0].preemptions == 2
        _assert_parity(engine, p, cfg, reqs, noise=_NOISE)

    @given(offset=st.integers(min_value=1, max_value=15),
           seed=st.integers(min_value=0, max_value=2 ** 16))
    @settings(max_examples=10, deadline=None)
    def test_property_any_offset_bitwise(self, offset, seed):
        """Hypothesis upgrade of the sweep: arbitrary (offset, seed)."""
        cfg, p = _setup()
        engine = SNNEventEngine(cfg, p, batch_slots=2, seed=seed,
                                round_steps=4, noise=_NOISE)
        req = EventRequest(uid=0, events=_events(jax.random.PRNGKey(seed),
                                                 16))
        engine.submit(req)
        fired = []

        def hook(eng):
            if not fired and 0 in [getattr(r, "uid", None)
                                   for r in eng._slot_req]:
                slot = [getattr(r, "uid", None)
                        for r in eng._slot_req].index(0)
                if int(eng._slot_done[slot]) <= offset:
                    eng.preempt_request(0, at_step=max(
                        offset, int(eng._slot_done[slot])), backoff=False)
                    fired.append(True)

        engine.run(round_hook=hook)
        _assert_parity(engine, p, cfg, [req], noise=_NOISE)


class TestSchedulerPolicy:
    """Priority preemption, budgets, backoff, deadline handling."""

    @pytest.mark.fast
    def test_priority_preempts_and_both_complete(self):
        cfg, p = _setup()
        engine = SNNEventEngine(cfg, p, batch_slots=1, seed=0, round_steps=4,
                                preempt_quantum=1, backoff_rounds=1)
        hog = EventRequest(uid=0, events=_events(jax.random.PRNGKey(0), 40))
        urgent = EventRequest(uid=1, priority=5,
                              events=_events(jax.random.PRNGKey(1), 8))
        engine.submit(hog)
        engine.run(max_rounds=2)            # hog resident, mid-stream
        engine.submit(urgent)
        done = engine.run()
        assert engine.preemption_count >= 1
        assert hog.preemptions >= 1
        # urgent finished before the preempted hog resumed to completion
        assert [r.uid for r in engine.completed] == [1, 0] or \
            engine.completed[0].uid == 1
        assert {r.uid for r in done} == {0, 1}
        _assert_parity(engine, p, cfg, [hog, urgent])

    @pytest.mark.fast
    def test_no_priorities_means_no_preemption(self):
        """Back-compat: plain traffic never triggers the preemptor."""
        cfg, p = _setup()
        engine = SNNEventEngine(cfg, p, batch_slots=2, seed=0, round_steps=4)
        for i in range(6):
            engine.submit(EventRequest(uid=i, events=_events(
                jax.random.fold_in(jax.random.PRNGKey(3), i), 10)))
        engine.run()
        assert engine.preemption_count == 0
        assert len(engine.completed) == 6

    @pytest.mark.fast
    def test_max_preemptions_budget(self):
        """A request is never preempted more than max_preemptions times."""
        cfg, p = _setup()
        engine = SNNEventEngine(cfg, p, batch_slots=1, seed=0, round_steps=2,
                                max_preemptions=1, preempt_quantum=1,
                                backoff_rounds=1)
        hog = EventRequest(uid=0, events=_events(jax.random.PRNGKey(0), 30))
        engine.submit(hog)
        engine.run(max_rounds=2)
        for i in range(4):
            engine.submit(EventRequest(uid=1 + i, priority=9, events=_events(
                jax.random.fold_in(jax.random.PRNGKey(1), i), 6)))
        engine.run()
        assert hog.preemptions == 1        # budget capped it despite 4 vips
        assert len(engine.completed) == 5
        _assert_parity(engine, p, cfg, [hog])

    @pytest.mark.fast
    def test_quantum_blocks_immediate_revictimization(self):
        """preempt_quantum=3: a fresh admit is safe for 3 ticks."""
        cfg, p = _setup()
        engine = SNNEventEngine(cfg, p, batch_slots=1, seed=0, round_steps=2,
                                preempt_quantum=3, backoff_rounds=1)
        a = EventRequest(uid=0, events=_events(jax.random.PRNGKey(0), 12))
        engine.submit(a)
        engine.run(max_rounds=1)
        admit_tick = int(engine._slot_admit_round[0])
        engine.submit(EventRequest(uid=1, priority=7,
                                   events=_events(jax.random.PRNGKey(1), 4)))
        engine.run(max_rounds=2)
        # inside the quantum window nothing may be preempted
        assert engine.preemption_count == 0 or \
            engine._rounds_total - admit_tick >= 3
        engine.run()
        assert len(engine.completed) == 2

    @pytest.mark.fast
    def test_backoff_is_exponential_and_expires(self):
        cfg, p = _setup()
        engine = SNNEventEngine(cfg, p, batch_slots=1, seed=0, round_steps=2,
                                backoff_rounds=2, max_preemptions=8)
        hog = EventRequest(uid=0, events=_events(jax.random.PRNGKey(0), 24))
        engine.submit(hog)
        engine.run(max_rounds=1)
        engine.preempt_request(0)          # policy-style: with backoff
        assert hog._not_before == engine._rounds_total + 2   # 2 * 2**0
        # drain: backoff must expire (ticks advance even while idle)
        done = engine.run()
        assert [r.uid for r in done] == [0]
        assert hog.state == lifecycle.COMPLETED
        _assert_parity(engine, p, cfg, [hog])

    @pytest.mark.fast
    def test_deadline_expiry_typed_outcome(self):
        cfg, p = _setup()
        engine = SNNEventEngine(cfg, p, batch_slots=1, seed=0, round_steps=4)
        late = EventRequest(uid=0, deadline_ms=0.0,
                            events=_events(jax.random.PRNGKey(0), 8))
        ok = EventRequest(uid=1, events=_events(jax.random.PRNGKey(1), 8))
        engine.submit(late)
        engine.submit(ok)
        done = engine.run()
        assert late.state == lifecycle.EXPIRED
        assert late in engine.expired and late.logits is None
        assert [r.uid for r in done] == [1]
        _assert_parity(engine, p, cfg, [ok])

    @pytest.mark.fast
    def test_completed_after_deadline_flags_miss(self):
        cfg, p = _setup()
        engine = SNNEventEngine(cfg, p, batch_slots=1, seed=0, round_steps=4)
        req = EventRequest(uid=0, deadline_ms=1e9,
                           events=_events(jax.random.PRNGKey(0), 8))
        engine.submit(req)
        engine.run()
        assert req.state == lifecycle.COMPLETED
        assert req.deadline_missed is False


class TestLoadShedding:
    """Bounded queue: overflow sheds with a typed terminal outcome."""

    @pytest.mark.fast
    def test_overflow_sheds_lowest_priority_newest(self):
        cfg, p = _setup()
        engine = SNNEventEngine(cfg, p, batch_slots=1, max_pending=2,
                                round_steps=4)
        keep = [EventRequest(uid=i, priority=5, events=_events(
            jax.random.fold_in(jax.random.PRNGKey(0), i), 8))
            for i in range(2)]
        for r in keep:
            engine.submit(r)
        shed = engine.submit(EventRequest(
            uid=9, priority=0, events=_events(jax.random.PRNGKey(7), 8)))
        assert shed.state == lifecycle.REJECTED
        assert shed in engine.rejected and len(engine.pending) == 2
        done = engine.run()
        assert {r.uid for r in done} == {0, 1}
        _assert_parity(engine, p, cfg, keep)

    @pytest.mark.fast
    def test_high_priority_submit_sheds_queued_low(self):
        cfg, p = _setup()
        engine = SNNEventEngine(cfg, p, batch_slots=1, max_pending=1,
                                round_steps=4)
        low = engine.submit(EventRequest(
            uid=0, priority=0, events=_events(jax.random.PRNGKey(0), 8)))
        high = engine.submit(EventRequest(
            uid=1, priority=3, events=_events(jax.random.PRNGKey(1), 8)))
        assert low.state == lifecycle.REJECTED
        assert high.state == lifecycle.QUEUED and high in engine.pending

    @pytest.mark.fast
    def test_shedding_never_drops_checkpointed_work(self):
        cfg, p = _setup()
        engine = SNNEventEngine(cfg, p, batch_slots=1, max_pending=1,
                                round_steps=4)
        hog = EventRequest(uid=0, events=_events(jax.random.PRNGKey(0), 24))
        engine.submit(hog)
        engine.run(max_rounds=1)
        engine.preempt_request(0, backoff=False)   # hog queued with _ckpt
        fresh = engine.submit(EventRequest(
            uid=1, events=_events(jax.random.PRNGKey(1), 8)))
        # the fresh request is shed, not the checkpoint holder
        assert fresh.state == lifecycle.REJECTED
        assert hog in engine.pending
        engine.run()
        assert hog.state == lifecycle.COMPLETED
        _assert_parity(engine, p, cfg, [hog])


class TestSubmitValidation:
    """Typed rejection of malformed tensors before any kernel launch."""

    def _engine(self):
        cfg, p = _setup()
        return SNNEventEngine(cfg, p, batch_slots=1)

    @pytest.mark.fast
    def test_empty_stream(self):
        with pytest.raises(lifecycle.EmptyEventError):
            self._engine().submit(EventRequest(
                uid=0, events=np.zeros((0, 32), np.float32)))

    @pytest.mark.fast
    def test_wrong_width(self):
        with pytest.raises(lifecycle.EventShapeError):
            self._engine().submit(EventRequest(
                uid=0, events=np.zeros((4, 33), np.float32)))

    @pytest.mark.fast
    def test_wrong_rank(self):
        with pytest.raises(lifecycle.EventShapeError):
            self._engine().submit(EventRequest(
                uid=0, events=np.zeros((4,), np.float32)))

    @pytest.mark.fast
    def test_nan_events(self):
        ev = np.zeros((4, 32), np.float32)
        ev[2, 5] = np.nan
        with pytest.raises(lifecycle.NonFiniteEventError):
            self._engine().submit(EventRequest(uid=0, events=ev))

    @pytest.mark.fast
    def test_non_ternary(self):
        ev = np.zeros((4, 32), np.float32)
        ev[1, 1] = 0.5
        with pytest.raises(lifecycle.NonTernaryEventError):
            self._engine().submit(EventRequest(uid=0, events=ev))

    @pytest.mark.fast
    def test_bad_dtype(self):
        with pytest.raises(lifecycle.EventDtypeError):
            self._engine().submit(EventRequest(
                uid=0, events=np.array([["a"] * 32] * 4)))

    @pytest.mark.fast
    def test_ternary_negatives_accepted(self):
        eng = self._engine()
        ev = np.zeros((8, 32), np.float32)
        ev[0, 0], ev[1, 1] = -1.0, 1.0
        req = eng.submit(EventRequest(uid=0, events=ev))
        assert req.state == lifecycle.QUEUED

    @pytest.mark.fast
    def test_validate_false_opts_out(self):
        cfg, p = _setup()
        eng = SNNEventEngine(cfg, p, batch_slots=1, validate=False)
        ev = np.full((4, 32), 0.5, np.float32)   # non-ternary but trusted
        assert eng.submit(EventRequest(uid=0, events=ev)).state == \
            lifecycle.QUEUED
