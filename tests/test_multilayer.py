"""Multi-layer fused stacks + the sequence-length / telemetry-population
regressions the depth change exposed.

Kernel level: the stacked one-launch kernel (inter-layer spikes never in
HBM, deep layers gated by the in-kernel occupancy of the previous layer's
winner set) must be bitwise-equal to the composed per-layer oracle chain
(``ref.fused_macro_multi_seq_ref``) — clean and noisy, across tile plans.

Model level: composed / fused-seq / fused-step 2-layer forwards agree
bitwise, and every forward normalizes by the events' actual T (not
``cfg.n_steps``).  Engine level: ``run()`` returns submission order and
``energy_report`` draws all stats from one population.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ima as ima_lib
from repro.core import macro as macro_lib
from repro.kernels import ops, ref
from repro.models import snn as snn_lib

KW = dict(drive_gain=0.25, beta=0.9, v_th1=1.0, v_th2=0.6, v_reset=0.0,
          v_lim=8.0)


def _tern(key, shape, rate=0.2):
    sparse = jax.random.uniform(jax.random.fold_in(key, 1), shape) < rate
    vals = jax.random.randint(key, shape, -1, 2)
    return (vals * sparse).astype(jnp.int8)


def _stack(key, n_in=96, widths=(64, 48), mcfg=None):
    mcfg = mcfg or macro_lib.CIMMacroConfig(mac_range=24.0)
    ks = jax.random.split(key, 2 * len(widths))
    w_ints, scales, f_in = [], [], n_in
    for li, w in enumerate(widths):
        w_ints.append(jax.random.randint(ks[2 * li], (f_in, w), -3, 4))
        scales.append(jnp.abs(jax.random.normal(ks[2 * li + 1], (w,)))
                      * 0.1 + 0.05)
        f_in = w
    return macro_lib.pack_kwn_stack(w_ints, scales, mcfg)


class TestMultiSeqKernelParity:
    """Stacked kernel vs composed per-layer oracle chain, bitwise."""

    T, M, N_IN = 6, 16, 96
    WIDTHS, KS = (64, 48), (7, 5)
    # default tiling + a ragged per-layer override: two distinct tile plans
    PLANS = (None, ((32, 32), (16, 24)))

    def _operands(self):
        key = jax.random.PRNGKey(0)
        x = _tern(jax.random.fold_in(key, 3), (self.T, self.M, self.N_IN),
                  0.15)
        stack = _stack(jax.random.fold_in(key, 4), self.N_IN, self.WIDTHS)
        planes = [(fw.msb, fw.lsb, fw.boundaries, fw.levels, fw.scale)
                  for fw in stack]
        vs = [jnp.zeros((self.M, w)) for w in self.WIDTHS]
        return x, stack, planes, vs

    @pytest.mark.fast
    @pytest.mark.parametrize("gate", [True, False])
    @pytest.mark.parametrize("tiles", PLANS)
    def test_clean_matches_oracle_chain(self, gate, tiles):
        x, _, planes, vs = self._operands()
        out = ops.fused_macro_multi_seq(
            x, planes, vs, None, ks=self.KS, use_snl=False, gate=gate,
            tile_shapes=tiles, **KW)
        v_fins, spk, mask, steps, cnts = ref.fused_macro_multi_seq_ref(
            x, planes, vs, None, ks=self.KS, use_snl=False, **KW)
        np.testing.assert_array_equal(np.asarray(out.spikes), np.asarray(spk))
        np.testing.assert_array_equal(np.asarray(out.mask), np.asarray(mask))
        for got, want in zip(out.v_outs, v_fins):
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        for got, want in zip(out.steps, steps):
            np.testing.assert_array_equal(np.asarray(got),
                                          np.asarray(want[..., 0]))
        for got, want in zip(out.spike_counts, cnts):
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    @pytest.mark.fast
    @pytest.mark.parametrize("tiles", PLANS)
    def test_noisy_matches_oracle_chain(self, tiles):
        """In-kernel IMA conversion noise + SNL, per-layer counter seeds."""
        x, stack, planes, vs = self._operands()
        mcfg = macro_lib.CIMMacroConfig(mac_range=24.0,
                                        ima_noise=ima_lib.IMANoiseModel())
        ima_kn = macro_lib.fused_kernel_noise(stack[0], mcfg)
        seeds = jnp.asarray([11, 22], jnp.int32)
        out = ops.fused_macro_multi_seq(
            x, planes, vs, None, ks=self.KS, use_snl=True, ima_noise=ima_kn,
            snl_amp=0.05, seeds=seeds, step_offset=3, gate=True,
            tile_shapes=tiles, **KW)
        v_fins, spk, _, steps, _ = ref.fused_macro_multi_seq_ref(
            x, planes, vs, None, ks=self.KS, use_snl=True, ima_noise=ima_kn,
            snl_amp=0.05, seeds=[11, 22], step_offset=3, **KW)
        np.testing.assert_array_equal(np.asarray(out.spikes), np.asarray(spk))
        for got, want in zip(out.v_outs, v_fins):
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        for got, want in zip(out.steps, steps):
            np.testing.assert_array_equal(np.asarray(got),
                                          np.asarray(want[..., 0]))

    @pytest.mark.fast
    def test_step_cadence_matches_seq(self):
        """T=1 launches with carried membranes == the one-launch sequence."""
        x, stack, planes, vs = self._operands()
        mcfg = macro_lib.CIMMacroConfig(mac_range=24.0,
                                        ima_noise=ima_lib.IMANoiseModel())
        ima_kn = macro_lib.fused_kernel_noise(stack[0], mcfg)
        seeds = jnp.asarray([11, 22], jnp.int32)
        nkw = dict(ks=self.KS, use_snl=True, ima_noise=ima_kn, snl_amp=0.05,
                   seeds=seeds, gate=True, **KW)
        spk_steps, vs_c = [], vs
        for t in range(self.T):
            o = ops.fused_macro_multi_seq(x[t:t + 1], planes, vs_c, None,
                                          step_offset=t, **nkw)
            vs_c = list(o.v_outs)
            spk_steps.append(o.spikes[0])
        seq = ops.fused_macro_multi_seq(x, planes, vs, None, step_offset=0,
                                        **nkw)
        np.testing.assert_array_equal(np.asarray(jnp.stack(spk_steps)),
                                      np.asarray(seq.spikes))
        for got, want in zip(vs_c, seq.v_outs):
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    @pytest.mark.fast
    def test_occupancy_counts_deep_layer_winner_gating(self):
        """The deep layer's occupancy is its in-kernel activity plan: with
        k winners per row, at most the winner-bearing K-tiles are occupied,
        and an all-zero input occupies nothing anywhere."""
        x, _, planes, vs = self._operands()
        out = ops.fused_macro_multi_seq(
            x, planes, vs, None, ks=self.KS, use_snl=False, gate=True,
            tile_shapes=((32, 32), (16, 24)), **KW)
        assert out.total_blocks > 0
        occ1 = np.asarray(out.occupancy[1])          # (T, row-tiles, 1)
        n_k1 = -(-self.WIDTHS[0] // 16)              # layer-1 K-tiles
        assert occ1.max() <= n_k1
        zero = ops.fused_macro_multi_seq(
            jnp.zeros_like(x), planes, vs, None, ks=self.KS, use_snl=False,
            gate=True, **KW)
        assert sum(int(jnp.sum(o)) for o in zero.occupancy) == 0


class TestMultiLayerModel:
    """2-layer SNNConfig stacks through every forward path."""

    def _setup(self):
        key = jax.random.PRNGKey(0)
        cfg = snn_lib.SNNConfig(n_in=64, hidden_layers=(48, 32), n_classes=5,
                                n_steps=20, k=7, k_layers=(7, 5))
        p = snn_lib.init_params(cfg, key)
        ev = _tern(jax.random.fold_in(key, 7), (4, 5, 64),
                   0.25).astype(jnp.float32)
        return cfg, p, ev, jax.random.fold_in(key, 9)

    def test_config_stack_fields(self):
        cfg, p, _, _ = self._setup()
        assert cfg.n_hidden == 32
        assert cfg.layer_widths == (48, 32)
        assert cfg.layer_k == (7, 5)
        assert [w.shape for w in p["w_hid"]] == [(64, 48), (48, 32)]
        with pytest.raises(ValueError):
            snn_lib.SNNConfig(n_in=8, hidden_layers=(16, 8), mode="nld")
        with pytest.raises(ValueError):
            snn_lib.SNNConfig(n_in=8, hidden_layers=(16, 8), k_layers=(3,))

    def test_single_layer_params_unchanged(self):
        """hidden_layers=(n,) must reproduce the legacy RNG stream."""
        key = jax.random.PRNGKey(3)
        a = snn_lib.init_params(snn_lib.SNNConfig(n_in=32, n_hidden=16), key)
        b = snn_lib.init_params(
            snn_lib.SNNConfig(n_in=32, hidden_layers=(16,)), key)
        np.testing.assert_array_equal(np.asarray(a["w_hid"]),
                                      np.asarray(b["w_hid"]))

    @pytest.mark.fast
    def test_composed_equals_fused_seq_and_step(self):
        cfg, p, ev, key = self._setup()
        lc, tc = snn_lib.forward_silicon(p, ev, cfg, key)
        ls, ts = snn_lib.forward_silicon(p, ev, cfg, key, fused="seq")
        lp, tp = snn_lib.forward_silicon(p, ev, cfg, key, fused="step")
        np.testing.assert_array_equal(np.asarray(lc), np.asarray(ls))
        np.testing.assert_array_equal(np.asarray(ls), np.asarray(lp))
        for name in ("adc_steps", "sops", "lif_updates"):
            np.testing.assert_array_equal(np.asarray(tc[name]),
                                          np.asarray(ts[name]),
                                          err_msg=f"telemetry {name}")
            np.testing.assert_array_equal(np.asarray(ts[name]),
                                          np.asarray(tp[name]),
                                          err_msg=f"telemetry {name}")
        np.testing.assert_array_equal(
            np.asarray(ts["skipped_block_ratio"]),
            np.asarray(tp["skipped_block_ratio"]))
        assert np.all(np.asarray(ts["skipped_block_ratio"]) >= 0.0)

    def test_noisy_seq_equals_step(self):
        cfg, p, ev, key = self._setup()
        noise = ima_lib.IMANoiseModel()
        ls, ts = snn_lib.forward_silicon(p, ev, cfg, key, fused="seq",
                                         noise=noise)
        lp, tp = snn_lib.forward_silicon(p, ev, cfg, key, fused="step",
                                         noise=noise)
        np.testing.assert_array_equal(np.asarray(ls), np.asarray(lp))
        np.testing.assert_array_equal(np.asarray(ts["adc_steps"]),
                                      np.asarray(tp["adc_steps"]))

    def test_forward_train_multi_runs_and_differs_per_depth(self):
        cfg, p, ev, _ = self._setup()
        logits = snn_lib.forward_train(p, ev, cfg)
        assert logits.shape == (4, 5)
        assert np.all(np.isfinite(np.asarray(logits)))

    def test_silicon_training_guard(self):
        from repro.train import silicon as silicon_lib
        cfg, p, ev, _ = self._setup()
        with pytest.raises(NotImplementedError):
            silicon_lib.forward_logits(p, ev, cfg, jnp.float32(0.0))

    def test_mac_telemetry_rejected_on_stacks(self):
        cfg, p, ev, key = self._setup()
        with pytest.raises(ValueError):
            snn_lib.forward_silicon(p, ev, cfg, key, fused="seq",
                                    mac_telemetry=True)


class TestSequenceLengthNormalization:
    """Logits must be invariant to cfg.n_steps when the events' T differs
    (the counts are normalized by events.shape[1]).  These pins fail on
    the pre-fix code, which divided by cfg.n_steps everywhere."""

    def _setup(self, **over):
        key = jax.random.PRNGKey(0)
        cfg = snn_lib.SNNConfig(n_in=64, n_hidden=48, n_classes=5,
                                n_steps=20, k=7, **over)
        p = snn_lib.init_params(cfg, key)
        ev = _tern(jax.random.fold_in(key, 7), (4, 5, 64),
                   0.25).astype(jnp.float32)
        return cfg, p, ev, jax.random.fold_in(key, 9)

    @pytest.mark.fast
    @pytest.mark.parametrize("fused", [False, "seq", "step"])
    def test_forward_silicon_invariant_to_cfg_n_steps(self, fused):
        cfg, p, ev, key = self._setup()
        cfg2 = dataclasses.replace(cfg, n_steps=12)
        a, ta = snn_lib.forward_silicon(p, ev, cfg, key, fused=fused)
        b, tb = snn_lib.forward_silicon(p, ev, cfg2, key, fused=fused)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        np.testing.assert_array_equal(np.asarray(ta["adc_steps"]),
                                      np.asarray(tb["adc_steps"]))

    @pytest.mark.fast
    def test_forward_train_invariant_to_cfg_n_steps(self):
        cfg, p, ev, _ = self._setup()
        cfg2 = dataclasses.replace(cfg, n_steps=12)
        np.testing.assert_array_equal(
            np.asarray(snn_lib.forward_train(p, ev, cfg)),
            np.asarray(snn_lib.forward_train(p, ev, cfg2)))

    def test_silicon_forward_logits_invariant_to_cfg_n_steps(self):
        from repro.train import silicon as silicon_lib
        cfg, p, ev, _ = self._setup()
        cfg2 = dataclasses.replace(cfg, n_steps=12)
        a = silicon_lib.forward_logits(p, ev, cfg, jnp.float32(0.0))
        b = silicon_lib.forward_logits(p, ev, cfg2, jnp.float32(0.0))
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    @pytest.mark.parametrize("fused", [False, "seq", "step"])
    def test_multilayer_invariant_to_cfg_n_steps(self, fused):
        cfg, p, ev, key = self._setup(hidden_layers=(48, 32))
        cfg2 = dataclasses.replace(cfg, n_steps=12)
        a, _ = snn_lib.forward_silicon(p, ev, cfg, key, fused=fused)
        b, _ = snn_lib.forward_silicon(p, ev, cfg2, key, fused=fused)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


class TestEngineRegressions:
    """energy_report population consistency + submission-order returns."""

    def _engine(self, mode="kwn"):
        from repro.serve.engine import SNNEventEngine
        cfg = snn_lib.SNNConfig(n_in=8, n_hidden=8, n_classes=2, mode=mode,
                                n_branches=2)
        p = snn_lib.init_params(cfg, jax.random.PRNGKey(0))
        return SNNEventEngine(cfg, p, batch_slots=2)

    @pytest.mark.fast
    def test_energy_report_single_population(self):
        """A completed request with a skip ratio but no adc_steps must not
        dilute mean_skipped_block_ratio — one population for all stats."""
        from repro.serve.engine import EventRequest
        engine = self._engine()
        engine.completed.extend([
            EventRequest(uid=0, events=None, adc_steps=10.0,
                         skipped_block_ratio=0.2),
            EventRequest(uid=1, events=None, adc_steps=12.0,
                         skipped_block_ratio=0.4),
            EventRequest(uid=2, events=None, adc_steps=None,
                         skipped_block_ratio=1.0),
        ])
        rep = engine.energy_report("nmnist")
        assert rep["requests"] == 2
        assert rep["mean_adc_steps"] == pytest.approx(11.0)
        assert rep["mean_skipped_block_ratio"] == pytest.approx(0.3)

    @pytest.mark.fast
    def test_energy_report_empty_contract(self):
        """{} for no measured requests, and in NLD mode for requests with
        no measured SOPs (NLD energy is priced at the traffic's measured
        spike rate)."""
        from repro.serve.engine import EventRequest
        assert self._engine().energy_report("nmnist") == {}
        nld = self._engine(mode="nld")
        nld.completed.append(EventRequest(uid=0, events=None, adc_steps=31.0))
        assert nld.energy_report("nmnist") == {}

    def test_run_returns_submission_order(self):
        from repro.serve.engine import EventRequest, SNNEventEngine
        key = jax.random.PRNGKey(0)
        cfg = snn_lib.SNNConfig(n_in=32, n_hidden=16, n_classes=3, n_steps=4,
                                k=4, use_snl=False)
        p = snn_lib.init_params(cfg, key)
        ev = _tern(jax.random.fold_in(key, 1), (6, 4, 32),
                   0.3).astype(jnp.float32)
        # densities vary per request; submit in an arbitrary fixed order
        uids = [3, 0, 5, 1, 4, 2]
        engine = SNNEventEngine(cfg, p, batch_slots=2, pack_by_density=True)
        for u in uids:
            engine.submit(EventRequest(uid=u, events=ev[u]))
        done = engine.run()
        assert [r.uid for r in done] == uids
        assert all(r.logits is not None for r in done)
