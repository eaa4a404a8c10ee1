"""In-kernel stochastic IMA: the Fig. 7 error model inside the fused kernel.

Three contracts, in increasing altitude:

* **bitwise oracle parity** — the noisy fused kernel (counter-PRNG draws
  generated inside the Pallas body) equals ``kernels.ref``'s counter-based
  noisy oracle exactly, in both modes, including multi-macro tiled layers;
* **seeded determinism / launch-shape invariance** — the same seed gives
  bitwise-identical spikes across runs *and across tile plans* (every draw
  is a pure function of ``(seed, step, absolute row, logical column)``, so
  (bm, bk, bn) choices and padding cannot move the stream);
* **statistics goldens** — the counter stream reproduces the paper's
  measured conversion-error moments (Fig. 7a: mu ~ 0.41 LSB, sigma ~ 1.34
  LSB) through the same calibration the composed ``jax.random`` model uses.

The wide statistical sweep is marked ``slow``; everything else is smoke-tier
(<60 s budget, see conftest FAST_MODULES).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ctrprng
from repro.core import ima as ima_lib
from repro.kernels import ops, ref


def _tern(key, shape, rate=0.2):
    sparse = jax.random.uniform(jax.random.fold_in(key, 1), shape) < rate
    vals = jax.random.randint(key, shape, -1, 2)
    return (vals * sparse).astype(jnp.int8)


def _kwn_operands(t, m, n_in, n_out, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = _tern(keys[0], (t, m, n_in))
    msb, lsb = _tern(keys[1], (n_in, n_out)), _tern(keys[2], (n_in, n_out))
    cb = ima_lib.nlq_codebook(5, -24.0, 24.0)
    scale = jax.random.uniform(keys[3], (n_out,), minval=0.05, maxval=0.3)
    v = jax.random.normal(keys[4], (m, n_out)) * 0.5
    return x, msb, lsb, cb, scale, v


def _noise_params(cb):
    return ima_lib.kernel_noise_params(ima_lib.IMANoiseModel(), cb)


def _assert_all_equal(out, want, msg=""):
    names = ("mac", "v_mem", "spikes", "mask", "adc_steps")
    for name, a, b in zip(names, out, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=f"{msg}{name} mismatch")


class TestNoisyOracleParity:
    """Noisy fused kernel == counter-based ref.py oracle, bitwise."""

    @pytest.mark.parametrize("t,m,n_in,n_out", [
        pytest.param(1, 16, 256, 128,
                     marks=pytest.mark.fast),   # one macro: smoke tier
        (3, 8, 300, 130),         # odd everything (padding in m, k, n)
        (2, 24, 512, 256),        # 2x2 virtual macro grid, multi-tile
    ])
    def test_kwn(self, t, m, n_in, n_out):
        x, msb, lsb, cb, scale, v = _kwn_operands(t, m, n_in, n_out)
        nz = _noise_params(cb)
        kw = dict(mode="kwn", k=12, drive_gain=0.25, ima_noise=nz,
                  snl_amp=0.05, seed=31)
        out = ops.fused_macro_seq(x, msb, lsb, cb.boundaries, cb.levels,
                                  scale, v, None, **kw)
        want = jax.jit(functools.partial(ref.fused_macro_seq_ref, **kw))(
            x, msb, lsb, cb.boundaries, cb.levels, scale, v, None)
        want = list(want)
        want[4] = want[4][..., 0]
        _assert_all_equal(out, want)

    @pytest.mark.parametrize("j,n_out", [(2, 128), (3, 130)])
    def test_nld(self, j, n_out):
        keys = jax.random.split(jax.random.PRNGKey(j), 6)
        t, m, n_in = 2, 9, 300
        x = _tern(keys[0], (t, m, n_in))
        msb = _tern(keys[1], (n_in, j * n_out))
        lsb = _tern(keys[2], (n_in, j * n_out))
        cb = ima_lib.activation_codebook(5, ima_lib.quadratic, -4.0, 4.0)
        scale = jax.random.uniform(keys[3], (j * n_out,), minval=0.01,
                                   maxval=0.05)
        w_dend = jax.random.normal(keys[4], (j, n_out)) / np.sqrt(j)
        v = jax.random.normal(keys[5], (m, n_out)) * 0.5
        nz = _noise_params(cb)
        kw = dict(mode="nld", drive_gain=0.25, ima_noise=nz, seed=17)
        out = ops.fused_macro_seq(x, msb, lsb, cb.boundaries, cb.levels,
                                  scale, v, None, w_dend=w_dend, **kw)
        want = jax.jit(functools.partial(ref.fused_macro_seq_ref, **kw))(
            x, msb, lsb, cb.boundaries, cb.levels, scale, v, None, w_dend)
        want = list(want)
        want[4] = want[4][..., 0]
        _assert_all_equal(out, want)

    def test_noise_perturbs_clean_result(self):
        """The injected error must actually change winners/spikes (a no-op
        noise path would pass every parity test vacuously)."""
        x, msb, lsb, cb, scale, v = _kwn_operands(4, 16, 256, 128)
        kw = dict(mode="kwn", k=12, drive_gain=0.25)
        clean = ops.fused_macro_seq(x, msb, lsb, cb.boundaries, cb.levels,
                                    scale, v, None, **kw)
        noisy = ops.fused_macro_seq(x, msb, lsb, cb.boundaries, cb.levels,
                                    scale, v, None, ima_noise=_noise_params(cb),
                                    seed=3, **kw)
        assert not np.array_equal(np.asarray(clean[3]), np.asarray(noisy[3]))


class TestSeededDeterminism:
    """Same seed -> bitwise-identical spikes, for any launch shape."""

    @pytest.mark.fast
    def test_identical_across_runs(self):
        x, msb, lsb, cb, scale, v = _kwn_operands(4, 16, 256, 128)
        kw = dict(mode="kwn", k=12, drive_gain=0.25,
                  ima_noise=_noise_params(cb), snl_amp=0.05, seed=99)
        a = ops.fused_macro_seq(x, msb, lsb, cb.boundaries, cb.levels,
                                scale, v, None, **kw)
        b = ops.fused_macro_seq(x, msb, lsb, cb.boundaries, cb.levels,
                                scale, v, None, **kw)
        _assert_all_equal(a, b, msg="rerun ")

    def test_identical_across_tile_plans(self):
        """(bm, bk, bn) sweeps must not move a single draw: counters are
        global element coordinates, not tile-local ones."""
        x, msb, lsb, cb, scale, v = _kwn_operands(2, 24, 512, 256)
        kw = dict(mode="kwn", k=12, drive_gain=0.25,
                  ima_noise=_noise_params(cb), snl_amp=0.05, seed=5)
        base = ops.fused_macro_seq(x, msb, lsb, cb.boundaries, cb.levels,
                                   scale, v, None, **kw)
        for bm, bk, bn in ((8, 256, 128), (128, 512, 256), (16, 512, 128)):
            out = ops.fused_macro_seq(x, msb, lsb, cb.boundaries, cb.levels,
                                      scale, v, None, bm=bm, bk=bk, bn=bn,
                                      **kw)
            _assert_all_equal(base, out, msg=f"plan {(bm, bk, bn)}: ")

    @pytest.mark.fast
    def test_step_offset_reproduces_seq_stream(self):
        """T = 1 launches that carry the membrane and pass the step index
        as step_offset draw the exact one-launch sequence stream."""
        x, msb, lsb, cb, scale, v = _kwn_operands(4, 16, 256, 128)
        kw = dict(mode="kwn", k=12, drive_gain=0.25,
                  ima_noise=_noise_params(cb), snl_amp=0.05, seed=21)
        seq = ops.fused_macro_seq(x, msb, lsb, cb.boundaries, cb.levels,
                                  scale, v, None, **kw)
        vv, spk = v, []
        for t in range(4):
            _, vv, s, _, _ = ops.fused_macro_seq(
                x[t][None], msb, lsb, cb.boundaries, cb.levels, scale, vv,
                None, step_offset=t, **kw)
            spk.append(np.asarray(s[0]))
        np.testing.assert_array_equal(np.stack(spk), np.asarray(seq[2]))
        np.testing.assert_array_equal(np.asarray(vv), np.asarray(seq[1]))

    def test_seeds_decorrelate(self):
        x, msb, lsb, cb, scale, v = _kwn_operands(2, 16, 256, 128)
        kw = dict(mode="kwn", k=12, drive_gain=0.25,
                  ima_noise=_noise_params(cb))
        a = ops.fused_macro_seq(x, msb, lsb, cb.boundaries, cb.levels,
                                scale, v, None, seed=1, **kw)
        b = ops.fused_macro_seq(x, msb, lsb, cb.boundaries, cb.levels,
                                scale, v, None, seed=2, **kw)
        assert not np.array_equal(np.asarray(a[3]), np.asarray(b[3]))


class TestForwardSiliconNoisy:
    """Model + serving layers: noisy evaluation never leaves the fused path."""

    def _setup(self, mode="kwn"):
        from repro.data import events as ev_lib
        from repro.models import snn
        dcfg = ev_lib.NMNIST
        ds = ev_lib.EventDataset(dcfg)
        cfg = snn.SNNConfig(n_in=dcfg.n_in, n_steps=dcfg.n_steps,
                            n_classes=dcfg.n_classes, mode=mode, k=12)
        p = snn.init_params(cfg, jax.random.PRNGKey(0))
        ev, lab = ds.sample(jax.random.PRNGKey(1), 6)
        return snn, p, ev, lab, cfg

    def test_noisy_seq_is_deterministic_per_key(self):
        snn, p, ev, _, cfg = self._setup()
        noisy = ima_lib.IMANoiseModel()
        la, _ = snn.forward_silicon(p, ev, cfg, jax.random.PRNGKey(4),
                                    noise=noisy, fused="seq")
        lb, _ = snn.forward_silicon(p, ev, cfg, jax.random.PRNGKey(4),
                                    noise=noisy, fused="seq")
        lc, _ = snn.forward_silicon(p, ev, cfg, jax.random.PRNGKey(5),
                                    noise=noisy, fused="seq")
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
        assert not np.array_equal(np.asarray(la), np.asarray(lc))

    def test_noisy_nld_seq_runs(self):
        snn, p, ev, _, cfg = self._setup("nld")
        logits, tele = snn.forward_silicon(
            p, ev, cfg, jax.random.PRNGKey(2), noise=ima_lib.IMANoiseModel(),
            fused="seq")
        assert logits.shape == (6, cfg.n_classes)
        np.testing.assert_allclose(np.asarray(tele["adc_steps"]), 31.0)

    def test_noisy_engine_serves_batches(self):
        from repro.serve.engine import EventRequest, SNNEventEngine
        snn, p, ev, lab, cfg = self._setup()
        # pack_by_density=False + continuous=False pin the legacy FIFO
        # batches so the direct-forward recomputation below sees the
        # engine's exact first batch and per-batch key stream
        engine = SNNEventEngine(cfg, p, batch_slots=4, seed=5,
                                noise=ima_lib.IMANoiseModel(),
                                pack_by_density=False, continuous=False)
        for i in range(6):
            engine.submit(EventRequest(uid=i, events=ev[i],
                                       label=int(lab[i])))
        done = engine.run()
        assert len(done) == 6 and not engine.pending
        assert all(0.0 <= r.adc_steps <= 31.0 for r in done)
        # same key sequence as the engine's first batch, straight through
        # forward_silicon: the engine adds nothing on top of the model path
        key = jax.random.split(jax.random.PRNGKey(5))[1]
        logits, _ = jax.jit(lambda pp, e, kk: snn.forward_silicon(
            pp, e, cfg, kk, fused="seq",
            noise=ima_lib.IMANoiseModel()))(p, ev[:4], key)
        np.testing.assert_array_equal(np.asarray(logits[0]),
                                      np.asarray(done[0].logits))


class TestNoiseStatisticsGolden:
    """The counter stream reproduces the Fig. 7a measured moments."""

    @pytest.mark.fast
    def test_fig7a_moments(self):
        cb = ima_lib.nlq_codebook(5, -64, 64)
        m = ima_lib.measure_transfer_error_ctr(cb, n_points=4096, n_steps=4)
        assert m["mean_lsb"] == pytest.approx(0.41, abs=0.08)
        assert m["std_lsb"] == pytest.approx(1.34, abs=0.12)

    def test_gaussian_moments(self):
        rows = jnp.arange(256, dtype=jnp.int32)[:, None]
        cols = jnp.arange(512, dtype=jnp.int32)[None, :]
        g = ctrprng.counter_normal(7, 3, rows, cols, ctrprng.TAG_IMA)
        assert float(jnp.mean(g)) == pytest.approx(0.0, abs=0.01)
        assert float(jnp.std(g)) == pytest.approx(1.0, abs=0.01)

    def test_sign_noise_is_two_level(self):
        rows = jnp.arange(64, dtype=jnp.int32)[:, None]
        cols = jnp.arange(128, dtype=jnp.int32)[None, :]
        s = ctrprng.counter_sign(7, 3, rows, cols, ctrprng.TAG_SNL)
        assert set(np.unique(np.asarray(s))) == {-1.0, 1.0}
        assert abs(float(jnp.mean(s))) < 0.05

    @pytest.mark.slow
    def test_fig7a_moment_sweep(self):
        """Wide seed x step sweep of the measured moments (slow tier)."""
        cb = ima_lib.nlq_codebook(5, -64, 64)
        for seed in (0, 11, 1234):
            m = ima_lib.measure_transfer_error_ctr(cb, seed=seed,
                                                   n_points=8192, n_steps=16)
            assert m["mean_lsb"] == pytest.approx(0.41, abs=0.06), seed
            assert m["std_lsb"] == pytest.approx(1.34, abs=0.08), seed
