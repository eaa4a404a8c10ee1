"""End-to-end system tests: SNN learns + silicon modes behave per the paper's
claims; LM training loss decreases; serving engine completes batched
requests; analytical roofline model is validated against XLA cost_analysis on
an unrolled config."""

import dataclasses

import jax
import jax.numpy as jnp
import pytest

from repro.configs import ARCHS
from repro.configs.base import reduced
from repro.data import events as ev_lib
from repro.data.synthetic_lm import DataConfig, SyntheticLM
from repro.models import lm, snn
from repro.nn import module
from repro.serve.engine import BatchedEngine, Request
from repro.train import optim, train_loop


class TestSNNSystem:
    @pytest.fixture(scope="class")
    def trained(self):
        ds = ev_lib.EventDataset(ev_lib.NMNIST)
        cfg = snn.SNNConfig(n_in=512, n_steps=20, n_classes=10, mode="kwn",
                            k=12)
        p, losses = snn.train(cfg, ds, n_steps=200, batch=64, lr=0.08)
        return p, cfg, ds, losses

    def test_loss_decreases(self, trained):
        _, _, _, losses = trained
        assert losses[-1] < losses[0] * 0.8, (losses[0], losses[-1])

    def test_silicon_kwn_beats_chance(self, trained):
        p, cfg, ds, _ = trained
        acc, tele = snn.evaluate(p, cfg, ds, jax.random.PRNGKey(1),
                                 n_batches=3)
        assert acc > 0.5  # 10 classes, chance = 0.1
        assert tele["lif_updates"] == cfg.k  # Eq. 1 sparse update

    def test_early_stop_saves_ramp_steps(self, trained):
        p, cfg, ds, _ = trained
        _, tele = snn.evaluate(p, cfg, ds, jax.random.PRNGKey(2), n_batches=2)
        assert tele["adc_steps"] < 31  # early stop engaged

    def test_kwn_k_sweep_monotone_updates(self, trained):
        p, cfg, ds, _ = trained
        for k in (3, 12, 32):
            _, tele = snn.evaluate(p, cfg, ds, jax.random.PRNGKey(3),
                                   n_batches=1, k=k)
            assert tele["lif_updates"] == k


class TestLMTraining:
    def test_loss_decreases_smoke(self):
        cfg = reduced(ARCHS["smollm-135m"])
        ocfg = optim.AdamWConfig(lr=5e-3, warmup_steps=2, total_steps=12)
        params = module.materialize(lm.param_specs(cfg), jax.random.PRNGKey(0))
        opt = optim.adamw_init(params, ocfg)
        data = SyntheticLM(DataConfig(cfg.vocab_size, 64, 8, seed=1))
        step = jax.jit(train_loop.build_train_step(cfg, None, n_micro=2,
                                                   opt_cfg=ocfg))
        losses = []
        for i in range(10):
            params, opt, m = step(params, opt, data.batch_at(i, n_micro=2))
            losses.append(float(m["loss"]))
        assert losses[-1] < losses[0], losses

    def test_kwn_ffn_sparsity_trains(self):
        # Eq. (1) applied to FFN units: top-k winner masking must train
        cfg = dataclasses.replace(reduced(ARCHS["qwen2.5-32b"]), kwn_ffn_k=16)
        params = module.materialize(lm.param_specs(cfg), jax.random.PRNGKey(0))
        batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 32),
                                              0, cfg.vocab_size)}
        loss, grads = jax.value_and_grad(
            lambda p: lm.loss_fn(p, batch, cfg)[0])(params)
        assert bool(jnp.isfinite(loss))
        gn = optim.global_norm(grads)
        assert bool(jnp.isfinite(gn)) and float(gn) > 0

    def test_cim_linear_mode_trains(self):
        # paper C1/C2 as LM projections: ternary weights + NLQ activations
        cfg = dataclasses.replace(reduced(ARCHS["smollm-135m"]),
                                  cim_linear=True)
        params = module.materialize(lm.param_specs(cfg), jax.random.PRNGKey(0))
        batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 32),
                                              0, cfg.vocab_size)}
        loss, grads = jax.value_and_grad(
            lambda p: lm.loss_fn(p, batch, cfg)[0])(params)
        assert bool(jnp.isfinite(loss)) and float(optim.global_norm(grads)) > 0


class TestServing:
    def test_batched_engine_completes(self):
        cfg = reduced(ARCHS["smollm-135m"])
        params = module.materialize(lm.param_specs(cfg), jax.random.PRNGKey(0))
        eng = BatchedEngine(cfg, params, batch_slots=2, s_max=64)
        for uid in range(4):
            eng.submit(Request(uid=uid, prompt=[1, 2, 3], max_new_tokens=5))
        done = eng.run(max_rounds=64)
        assert len(done) == 4
        assert all(len(r.generated) == 5 for r in done)
        assert all(0 <= t < cfg.vocab_size for r in done for t in r.generated)

    def test_prefill_returns_cache_and_last_logits(self):
        cfg = reduced(ARCHS["recurrentgemma-9b"])
        params = module.materialize(lm.param_specs(cfg), jax.random.PRNGKey(0))
        tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                                    cfg.vocab_size)
        logits, aux, cache = lm.forward(params, {"tokens": tokens}, cfg,
                                        prefill=True)
        assert logits.shape == (2, cfg.padded_vocab)
        assert any(k.startswith("b") for k in cache)
        assert "tail0" in cache  # 38 = 12*3 + 2 tail blocks

    def test_prefill_cache_matches_decode_path(self):
        """Prefill-then-decode must equal pure step-by-step decode."""
        import numpy as np
        cfg = reduced(ARCHS["qwen2.5-32b"])
        params = module.materialize(lm.param_specs(cfg), jax.random.PRNGKey(3))
        toks = jax.random.randint(jax.random.PRNGKey(4), (1, 8), 0,
                                  cfg.vocab_size)
        # path A: teacher-forced decode for 8 tokens
        cache_a = lm.init_cache(cfg, 1, 8)
        for t in range(8):
            logits_a, cache_a = lm.decode_step(
                params, cache_a, toks[:, t:t + 1],
                jnp.full((1,), t, jnp.int32), cfg)
        # path B: prefill over the 8 tokens
        logits_b, _, cache_b = lm.forward(params, {"tokens": toks}, cfg,
                                          prefill=True)
        np.testing.assert_allclose(np.asarray(logits_a),
                                   np.asarray(logits_b), rtol=2e-3, atol=2e-3)


class TestRooflineModelValidation:
    def test_flops_model_matches_cost_analysis_unrolled(self):
        """On a 1-group config with n_micro=1 (no while loops hiding flops),
        the analytical flops model must agree with XLA's counter within 35%
        (XLA fuses/simplifies; the model includes what XLA may elide)."""
        from repro.roofline import flops_model
        base = ARCHS["smollm-135m"]
        cfg = dataclasses.replace(
            reduced(base, n_layers=1, d_model=128, vocab=512),
            remat=False, dtype="float32")
        params = module.materialize(lm.param_specs(cfg), jax.random.PRNGKey(0))
        b, s = 4, 256
        batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (b, s),
                                              0, cfg.vocab_size)}

        def train_like(p, bt):
            return jax.value_and_grad(lambda pp: lm.loss_fn(pp, bt, cfg)[0])(p)

        compiled = jax.jit(train_like).lower(params, batch).compile()
        ca = compiled.cost_analysis()
        hlo_flops = float(ca["flops"])

        fwd_i, _ = flops_model.fwd_flops_per_token(cfg, "train", s,
                                                   with_full_head=True)
        model_flops = fwd_i * b * s * 3.0   # fwd + bwd(2x), no remat
        ratio = model_flops / hlo_flops
        assert 0.65 < ratio < 1.5, (model_flops, hlo_flops, ratio)
