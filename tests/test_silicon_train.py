"""Silicon-in-the-loop training: forward exactness + surrogate-gradient
parity of the fused-VJP subsystem (``kernels.fused_macro_grad``,
``ops.fused_macro_seq_vjp``, ``train.silicon``).

The contract under test, from ISSUE 5:

* the custom-VJP **forward** is the silicon-exact fused kernel — bitwise-
  equal to ``ref.fused_macro_seq_ref`` (and the differentiable oracle's
  primal), clean and counter-PRNG noisy, across tile plans;
* the custom-VJP **backward** (the time-reversed Pallas kernel) matches
  ``jax.grad`` of the pure-JAX oracle ``ref.fused_macro_seq_vjp_ref`` —
  allclose for the surrogate pieces, across >=2 tile plans, clean and
  noisy, hard-gate and relaxed;
* the **remat** (recompute-MAC) backward is *bitwise* identical to the
  residual-stack backward (the MAC is exact integers);
* noisy gradients are a pure function of the seed (reproducible, and
  distinct seeds give distinct draws);
* a 20-step ``train(cfg, silicon=True)`` run decreases the silicon loss
  (the tier-1 train-smoke gate).

The fast-marked subset (one parity shape, determinism, the train smoke) is
what ``make train-smoke`` runs in CI; the tiled-plan sweeps and the reduced
Fig. 8 fine-tune experiment live in the default/slow tiers.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ima as ima_lib
from repro.kernels import ops, ref
from repro.models import snn
from repro.train import silicon as silicon_lib

KW = dict(k=12, drive_gain=0.25, use_snl=True, snl_amp=0.05)
STE = dict(ste_lo=-24.5, ste_hi=24.5)

# Two tile plans: single-tile (one macro column width, one K tile) and a
# 2x2 virtual macro grid (two K tiles x two column tiles, padded batch).
PLANS = {
    "single": dict(t=5, m=8, k_dim=256, n=128),
    "tiled": dict(t=4, m=12, k_dim=512, n=256),
}


def _operands(plan, seed=0):
    p = PLANS[plan]
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    t, m, k_dim, n = p["t"], p["m"], p["k_dim"], p["n"]
    tern = lambda kk, s: jax.random.randint(kk, s, -1, 2).astype(jnp.float32)
    x = tern(ks[0], (t, m, k_dim)) \
        * (jax.random.uniform(ks[5], (t, m, k_dim)) < 0.12)
    w = jax.random.randint(ks[1], (k_dim, n), -3, 4).astype(jnp.float32)
    cb = ima_lib.nlq_codebook(5, -24, 24)
    scale = jax.random.uniform(ks[3], (n,), minval=0.05, maxval=0.3)
    v = jax.random.normal(ks[4], (m, n)) * 0.5
    return x, w, cb, scale, v


def _spec(**kw):
    return ops.SeqVJPSpec(**{**KW, **STE, **kw})


_DUMMY = jnp.zeros((1,), jnp.float32)


def _vjp_outputs(spec, w, x, cb, scale, v, seed=7.0):
    return ops.fused_macro_seq_vjp(spec, w, x, cb.boundaries, cb.levels,
                                   scale, v, _DUMMY, jnp.float32(seed))


def _oracle_outputs(w, x, cb, scale, v, seed=7, **kw):
    return ref.fused_macro_seq_vjp_ref(w, x, cb.boundaries, cb.levels,
                                       scale, v, None, seed=seed,
                                       **{**KW, **STE, **kw})


def _noise_params(cb):
    return ima_lib.kernel_noise_params(ima_lib.IMANoiseModel(), cb)


def _grads(fn, w, v, g_spk, g_vfin):
    def loss(w, v):
        out = fn(w, v)
        return jnp.sum(out[0] * g_spk) + jnp.sum(out[1] * g_vfin)
    return jax.grad(loss, argnums=(0, 1))(w, v)


# ---------------------------------------------------------------------------
# Forward exactness
# ---------------------------------------------------------------------------

@pytest.mark.fast
@pytest.mark.parametrize("noisy", [False, True])
def test_vjp_forward_bitwise_vs_seq_ref(noisy):
    """The training forward IS the serving forward: primal spikes and final
    membrane equal ``fused_macro_seq_ref`` bitwise, clean and noisy."""
    from repro.core import ternary as ternary_lib
    x, w, cb, scale, v = _operands("single")
    kn = _noise_params(cb) if noisy else None
    spec = _spec(ima_noise=kn)
    spk, vfin = _vjp_outputs(spec, w, x, cb, scale, v)
    msb, lsb = ternary_lib.weight_decompose(w)
    want = ref.fused_macro_seq_ref(x, msb, lsb, cb.boundaries, cb.levels,
                                   scale, v, None, mode="kwn", seed=7,
                                   ima_noise=kn, **KW)
    assert jnp.array_equal(spk, want[2])
    assert jnp.array_equal(vfin, want[1])


@pytest.mark.parametrize("plan", list(PLANS))
@pytest.mark.parametrize("noisy", [False, True])
def test_vjp_forward_bitwise_vs_oracle(plan, noisy):
    """The differentiable oracle's primal is the kernel's primal — the STE
    identity terms vanish exactly — for every tile plan, clean and noisy."""
    x, w, cb, scale, v = _operands(plan)
    kn = _noise_params(cb) if noisy else None
    spec = _spec(ima_noise=kn, kwn_relax=0.1)
    spk, vfin = _vjp_outputs(spec, w, x, cb, scale, v)
    vfin_r, spk_r, _, _, _ = _oracle_outputs(w, x, cb, scale, v,
                                             ima_noise=kn, kwn_relax=0.1)
    assert jnp.array_equal(spk, spk_r)
    assert jnp.array_equal(vfin, vfin_r)


@pytest.mark.parametrize("noisy", [False, True])
def test_vtrace_matches_oracle(noisy):
    """The membrane-trace residual (pre-reset, post-saturation V_mem) the
    backward consumes equals the oracle's, bitwise."""
    x, w, cb, scale, v = _operands("single")
    from repro.core import ternary as ternary_lib
    kn = _noise_params(cb) if noisy else None
    msb, lsb = ternary_lib.weight_decompose(w)
    outs = ops.fused_macro_seq(
        x, msb.astype(jnp.int8), lsb.astype(jnp.int8), cb.boundaries,
        cb.levels, scale, v, None, mode="kwn", ima_noise=kn,
        mac_telemetry=False, train_trace=True, seed=7, **KW)
    vtrace_r = _oracle_outputs(w, x, cb, scale, v, ima_noise=kn)[4]
    assert jnp.array_equal(outs[5], vtrace_r)


# ---------------------------------------------------------------------------
# Gradient parity vs the oracle VJP
# ---------------------------------------------------------------------------

@pytest.mark.fast
def test_grad_parity_single_plan_clean():
    """Tier-1: fused-VJP gradients match jax.grad of the oracle."""
    _grad_parity_case("single", noisy=False, kwn_relax=0.1, remat=False)


@pytest.mark.parametrize("plan", list(PLANS))
@pytest.mark.parametrize("noisy", [False, True])
@pytest.mark.parametrize("kwn_relax", [0.0, 0.25])
def test_grad_parity_matrix(plan, noisy, kwn_relax):
    """Full matrix: both tile plans x clean/noisy x hard/relaxed gate."""
    _grad_parity_case(plan, noisy=noisy, kwn_relax=kwn_relax, remat=False)


def _grad_parity_case(plan, *, noisy, kwn_relax, remat):
    x, w, cb, scale, v = _operands(plan)
    kn = _noise_params(cb) if noisy else None
    spec = _spec(ima_noise=kn, kwn_relax=kwn_relax, remat=remat)
    shapes = _vjp_outputs(spec, w, x, cb, scale, v)
    g_spk = jax.random.normal(jax.random.PRNGKey(3), shapes[0].shape)
    g_vfin = jax.random.normal(jax.random.PRNGKey(4), shapes[1].shape)
    dw_k, dv_k = _grads(
        lambda w, v: _vjp_outputs(spec, w, x, cb, scale, v),
        w, v, g_spk, g_vfin)
    def oracle_fn(w, v):
        v_fin, spk_t, *_ = _oracle_outputs(w, x, cb, scale, v, ima_noise=kn,
                                           kwn_relax=kwn_relax)
        return spk_t, v_fin

    dw_r, dv_r = _grads(oracle_fn, w, v, g_spk, g_vfin)
    np.testing.assert_allclose(dw_k, dw_r, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(dv_k, dv_r, rtol=1e-5, atol=1e-6)


def test_remat_gradients_bitwise_equal_residual():
    """The recompute-MAC backward is *bitwise* the residual-stack backward:
    the MAC is small exact integers, so recomputation cannot move a bit."""
    x, w, cb, scale, v = _operands("tiled")
    kn = _noise_params(cb)
    g_spk = jax.random.normal(jax.random.PRNGKey(3),
                              (x.shape[0], x.shape[1], v.shape[-1]))
    g_vfin = jax.random.normal(jax.random.PRNGKey(4), v.shape)
    grads = {}
    for remat in (False, True):
        spec = _spec(ima_noise=kn, kwn_relax=0.1, remat=remat)
        grads[remat] = _grads(
            lambda w, v, spec=spec: _vjp_outputs(spec, w, x, cb, scale, v),
            w, v, g_spk, g_vfin)
    assert jnp.array_equal(grads[False][0], grads[True][0])
    assert jnp.array_equal(grads[False][1], grads[True][1])


@pytest.mark.fast
def test_noisy_gradients_seeded_deterministic():
    """Noisy-QAT gradients are a pure function of the counter seed."""
    x, w, cb, scale, v = _operands("single")
    spec = _spec(ima_noise=_noise_params(cb), kwn_relax=0.1)
    g_spk = jax.random.normal(jax.random.PRNGKey(3),
                              (x.shape[0], x.shape[1], v.shape[-1]))

    def dw(seed):
        return jax.grad(lambda w: jnp.sum(
            _vjp_outputs(spec, w, x, cb, scale, v, seed=seed)[0]
            * g_spk))(w)

    assert jnp.array_equal(dw(11.0), dw(11.0))
    assert not jnp.array_equal(dw(11.0), dw(12.0))


def test_gate_off_matches_gated_gradients():
    """Activity gating of the backward contraction is output-invariant."""
    x, w, cb, scale, v = _operands("single")
    g_spk = jax.random.normal(jax.random.PRNGKey(3),
                              (x.shape[0], x.shape[1], v.shape[-1]))
    grads = {}
    for gate in (False, True):
        spec = _spec(kwn_relax=0.1, gate=gate)
        grads[gate] = jax.grad(lambda w, spec=spec: jnp.sum(
            _vjp_outputs(spec, w, x, cb, scale, v)[0] * g_spk))(w)
    assert jnp.array_equal(grads[False], grads[True])


# ---------------------------------------------------------------------------
# Model layer
# ---------------------------------------------------------------------------

def _nmnist_setup(k=12, n_steps=12, n_in=256):
    from repro.data import events as ev_lib
    cfg = snn.SNNConfig(n_in=n_in, n_steps=n_steps, n_classes=10,
                        mode="kwn", k=k)
    dcfg = ev_lib.EventDataConfig("nmnist", n_in, n_steps, 10, 0.03,
                                  alpha=0.55)
    return cfg, ev_lib.EventDataset(dcfg)


def test_clean_training_forward_equals_serving_forward():
    """``silicon.forward_logits`` (clean) is bitwise the fused serving
    forward — trained models need no re-calibration for the serving path."""
    cfg, ds = _nmnist_setup()
    p = snn.init_params(cfg, jax.random.PRNGKey(0))
    ev, _ = ds.sample(jax.random.PRNGKey(2), 16)
    logits_serve, _ = snn.forward_silicon(p, ev, cfg, jax.random.PRNGKey(3),
                                          fused=True)
    logits_train = silicon_lib.forward_logits(p, ev, cfg, jnp.float32(0.0))
    assert jnp.array_equal(logits_serve, logits_train)


def test_silicon_loss_grad_reaches_both_params():
    cfg, ds = _nmnist_setup()
    p = snn.init_params(cfg, jax.random.PRNGKey(0))
    ev, lab = ds.sample(jax.random.PRNGKey(2), 16)
    g = jax.grad(snn.loss_fn)(p, ev, lab, cfg, jnp.float32(3.0),
                              silicon=True, noise=ima_lib.IMANoiseModel())
    assert float(jnp.max(jnp.abs(g["w_hid"]))) > 0.0
    assert float(jnp.max(jnp.abs(g["w_out"]))) > 0.0
    assert np.isfinite(np.asarray(g["w_hid"])).all()


def test_silicon_training_rejects_nld():
    cfg, ds = _nmnist_setup()
    cfg = snn.SNNConfig(n_in=cfg.n_in, n_steps=cfg.n_steps, mode="nld")
    p = snn.init_params(cfg, jax.random.PRNGKey(0))
    ev, lab = ds.sample(jax.random.PRNGKey(2), 4)
    with pytest.raises(ValueError, match="kwn"):
        snn.loss_fn(p, ev, lab, cfg, jnp.float32(0.0), silicon=True)


@pytest.mark.fast
def test_train_smoke_silicon_loss_decreases():
    """20 noise-aware QAT steps through the fused kernel: loss decreases.
    (The tier-1 train-smoke gate; fully seeded, so deterministic.)"""
    from repro.data import events as ev_lib
    ds = ev_lib.EventDataset(ev_lib.DATASETS["nmnist"])
    cfg = snn.SNNConfig(n_in=512, n_steps=20, n_classes=10, mode="kwn",
                        k=12)
    _, losses = snn.train(cfg, ds, n_steps=20, batch=64, lr=0.3,
                          silicon=True, noise=ima_lib.IMANoiseModel())
    assert len(losses) == 20 and all(np.isfinite(losses))
    assert np.mean(losses[-3:]) < np.mean(losses[:3]), losses


@pytest.mark.slow
def test_finetune_recovers_noisy_accuracy():
    """The reduced Fig. 8 robustness experiment (the acceptance criterion):
    software pre-train, then silicon fine-tune with noise-aware QAT; the
    fine-tuned model's *noisy* fused accuracy must be at least the
    software-trained baseline's on both event-dataset stand-ins.

    The fine-tune must move the model: at the silicon forward's
    near-uniform starting loss (~2.25), 60 steps at lr 0.01-0.04 leave the
    loss within 0.03 of where it started and the noisy accuracy within
    eval noise of the baseline, on either side.  100 steps at lr 0.3 lift
    it by 0.2-0.3 on both datasets, for fine-tune seeds 5, 6 and 7."""
    from repro.data import events as ev_lib
    nm = ima_lib.IMANoiseModel()
    for name, k in (("nmnist", 3), ("dvs_gesture", 12)):
        dcfg = ev_lib.DATASETS[name]
        ds = ev_lib.EventDataset(dcfg)
        cfg = snn.SNNConfig(n_in=dcfg.n_in, n_steps=dcfg.n_steps,
                            n_classes=dcfg.n_classes, mode="kwn", k=k)
        p, _ = snn.train(cfg, ds, n_steps=150, batch=64)
        base_noisy, _ = snn.evaluate(p, cfg, ds, jax.random.PRNGKey(1),
                                     n_batches=8, noise=nm, fused=True)
        p_ft, losses = snn.train(cfg, ds, n_steps=100, batch=64, lr=0.3,
                                 seed=5, silicon=True, noise=nm, params=p)
        ft_noisy, _ = snn.evaluate(p_ft, cfg, ds, jax.random.PRNGKey(1),
                                   n_batches=8, noise=nm, fused=True)
        assert np.isfinite(losses).all()
        assert ft_noisy >= base_noisy, (name, base_noisy, ft_noisy)


def test_train_losses_are_floats_once():
    """Satellite: ``train`` returns host floats built from one stacked
    device array (no per-step host sync), and warm-starting from an
    existing tree leaves the caller's buffers alive (donation safety)."""
    cfg, ds = _nmnist_setup(n_steps=8)
    p0 = snn.init_params(cfg, jax.random.PRNGKey(0))
    p, losses = snn.train(cfg, ds, n_steps=3, batch=8, params=p0)
    assert isinstance(losses, list) and len(losses) == 3
    assert all(isinstance(x, float) for x in losses)
    # p0 must still be usable after the donating train loop copied it
    assert np.isfinite(float(jnp.sum(p0["w_hid"])))
