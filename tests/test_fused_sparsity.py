"""Activity-gated sparse execution: gated == dense, bitwise, always.

The gated fused kernel (scalar-prefetched occupancy map, ``pl.when``-skipped
MAC blocks, bounded KWN ramp sweep, optional raw-MAC telemetry) is a pure
execution optimization: an all-zero activation block contributes an exactly
zero partial sum, and the bounded sweep only skips levels with no crossings
or no admission slots left.  So every output must equal the dense path — and
the ``ref.py`` oracles — bit for bit, at every event density, in both modes,
clean and noisy, for one time step and a whole sequence, for any tile plan.
This suite sweeps that whole matrix; a tolerance here is a bug.

A curated ``@pytest.mark.fast`` subset (one dense-vs-gated sweep point per
axis) keeps ``make smoke`` under its 60 s budget; the full matrix runs in
the default tier.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ima as ima_lib
from repro.core import macro as macro_lib
from repro.kernels import fused_macro as fused_kernel
from repro.kernels import ops, ref

DENSITIES = (0.0, 0.01, 0.1, 1.0)
OUT_NAMES = ("v_mem", "spikes", "mask", "adc_steps")

# >= 2 tile plans: the default planner pick, and an explicit multi-tile
# override that forces row/K/column tiling (finer activity granularity)
TILE_PLANS = ({}, {"bm": 8, "bk": 128, "bn": 64})


def _events(key, shape, density):
    """Ternary events at the given density; density 0.0 = fully silent."""
    vals = jax.random.randint(key, shape, -1, 2)
    sparse = jax.random.uniform(jax.random.fold_in(key, 1), shape) < density
    return (vals * sparse).astype(jnp.int8)


def _operands(mode, t=3, m=16, n_in=256, n_out=128, j=2, density=0.1,
              seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 7)
    nc = n_out if mode == "kwn" else j * n_out
    x = _events(keys[0], (t, m, n_in), density)
    msb = _events(keys[1], (n_in, nc), 0.5)
    lsb = _events(keys[2], (n_in, nc), 0.5)
    if mode == "kwn":
        cb = ima_lib.nlq_codebook(5, -24, 24)
        scale = jax.random.uniform(keys[3], (nc,), minval=0.05, maxval=0.3)
        w_dend = None
    else:
        cb = ima_lib.activation_codebook(5, ima_lib.quadratic, -4.0, 4.0)
        scale = jax.random.uniform(keys[3], (nc,), minval=0.01, maxval=0.05)
        w_dend = jax.random.normal(keys[4], (j, n_out)) / np.sqrt(j)
    v = jax.random.normal(keys[5], (m, n_out)) * 0.5
    noise = 0.05 * jnp.sign(jax.random.normal(keys[6], (t, m, n_out)))
    return x, msb, lsb, cb, scale, v, noise, w_dend


def _assert_equal(got, want, context):
    for name, a, b in zip(OUT_NAMES, got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=f"{name} mismatch ({context})")


def _run_pair(mode, density, t, noisy, tiles, seed=0):
    """Dense vs gated (and vs oracle) for one sweep point of ``t`` steps;
    at t = 1 the oracle is the one-step ``fused_macro_step_ref``."""
    x, msb, lsb, cb, scale, v, noise, w_dend = _operands(
        mode, t=t, density=density, seed=seed)
    kw = dict(mode=mode, k=12, drive_gain=0.25, **tiles)
    if noisy:
        kw.update(ima_noise=ima_lib.kernel_noise_params(
            ima_lib.IMANoiseModel(), cb), snl_amp=0.05, seed=7)
        noise = None
    args = (x, msb, lsb, cb.boundaries, cb.levels, scale, v, noise, w_dend)
    dense = ops.fused_macro_seq(*args, gate=False, **kw)
    gated = ops.fused_macro_seq(*args, gate=True, **kw)
    gated_dark = ops.fused_macro_seq(*args, gate=True, mac_telemetry=False,
                                     **kw)
    okw = {k: v_ for k, v_ in kw.items() if k not in ("bm", "bk", "bn")}
    if t == 1:
        want = jax.jit(functools.partial(ref.fused_macro_step_ref, **okw))(
            x[0], *args[1:7], None if noise is None else noise[0], w_dend)
        want = (want[0], want[1], want[2][None], want[3][None],
                want[4][None])
    else:
        want = jax.jit(functools.partial(ref.fused_macro_seq_ref, **okw))(
            *args)
    ctx = f"{mode}/t={t}/d={density}/noisy={noisy}/tiles={tiles}"
    # gated == dense, bitwise, including the raw MAC telemetry
    _assert_equal(gated[1:], dense[1:], ctx)
    np.testing.assert_array_equal(np.asarray(gated[0]), np.asarray(dense[0]),
                                  err_msg=f"mac mismatch ({ctx})")
    # telemetry-off returns mac=None but identical outputs
    assert gated_dark[0] is None
    _assert_equal(gated_dark[1:], dense[1:], ctx + "/mac_telemetry=False")
    # gated == composed oracle
    _assert_equal(gated[1:], (want[1], want[2], want[3], want[4][..., 0]),
                  ctx + "/oracle")


class TestGatedParitySweep:
    """The acceptance matrix: density x mode x length x noise x tiling."""

    @pytest.mark.parametrize("density", [
        pytest.param(0.0, marks=pytest.mark.fast), 0.01,
        pytest.param(0.1, marks=pytest.mark.fast), 1.0])
    def test_kwn_seq_clean_density_sweep(self, density):
        _run_pair("kwn", density, 3, noisy=False, tiles={})

    @pytest.mark.parametrize("density", DENSITIES)
    @pytest.mark.parametrize("mode", ["kwn", "nld"])
    @pytest.mark.parametrize("t", [pytest.param(1, id="step"),
                                   pytest.param(3, id="seq")])
    @pytest.mark.parametrize("noisy", [False, True])
    def test_full_matrix_default_tiles(self, density, mode, t, noisy):
        _run_pair(mode, density, t, noisy, tiles={})

    @pytest.mark.parametrize("density", [0.0, 0.1])
    @pytest.mark.parametrize("mode", ["kwn", "nld"])
    @pytest.mark.parametrize("noisy", [False, True])
    def test_multi_tile_plan(self, density, mode, noisy):
        _run_pair(mode, density, 3, noisy, tiles=TILE_PLANS[1])

    @pytest.mark.fast
    def test_fast_cross_section(self):
        """One noisy multi-tile point for the smoke tier (the remaining
        axes — nld, t = 1, full tile sweep — run in the default tier via
        the matrix above)."""
        _run_pair("kwn", 0.1, 3, noisy=True, tiles=TILE_PLANS[1])


class TestActivityMap:
    @pytest.mark.fast
    def test_map_matches_brute_force(self):
        x = _events(jax.random.PRNGKey(3), (5, 24, 300), 0.02)
        plan = fused_kernel.plan_tiles(24, 300, 128, 128, t=5, bm=8)
        xm = jnp.pad(x, ((0, 0), (0, plan.m_pad - 24),
                         (0, plan.k_pad - 300)))
        occ = np.asarray(ops.fused_activity_map(xm, plan))
        n_i, n_k = plan.m_pad // plan.bm, plan.k_pad // plan.bk
        assert occ.shape == (5, n_i, n_k)
        for t in range(5):
            for i in range(n_i):
                for kk in range(n_k):
                    blk = np.asarray(xm[t, i * plan.bm:(i + 1) * plan.bm,
                                        kk * plan.bk:(kk + 1) * plan.bk])
                    assert occ[t, i, kk] == int((blk != 0).any())

    @pytest.mark.fast
    def test_plan_activity_matches_ops_map(self):
        """macro.plan_activity must hand the kernel the exact map
        ops.fused_macro_seq would build itself (same tile plan)."""
        cfg_nc = 128
        keys = jax.random.split(jax.random.PRNGKey(4), 3)
        spikes = (jax.random.randint(keys[0], (4, 10, 300), -1, 2) *
                  (jax.random.uniform(keys[1], (4, 10, 300)) < 0.05))
        cb = ima_lib.nlq_codebook(5, -24, 24)
        fw = macro_lib.FusedMacroWeights(
            msb=jnp.zeros((300, cfg_nc), jnp.int8),
            lsb=jnp.zeros((300, cfg_nc), jnp.int8),
            scale=jnp.ones((cfg_nc,)), boundaries=cb.boundaries,
            levels=cb.levels, w_dend=None, mode="kwn")
        act = macro_lib.plan_activity(spikes, fw, cfg_nc)
        plan, _ = macro_lib.plan_fused_tiles(10, fw, cfg_nc, n_steps=4)
        xm = jnp.pad(spikes.astype(jnp.int8),
                     ((0, 0), (0, plan.m_pad - 10), (0, plan.k_pad - 300)))
        np.testing.assert_array_equal(np.asarray(act),
                                      np.asarray(ops.fused_activity_map(
                                          xm, plan)))

    @pytest.mark.fast
    def test_plan_prefers_aligned_k_tiles(self):
        """The activity-granularity heuristic: K < 256 takes the smallest
        lane-aligned tile instead of padding up to the macro row count."""
        assert fused_kernel.plan_tiles(16, 100, 128, 128).bk == 128
        assert fused_kernel.plan_tiles(16, 100, 128, 128).k_pad == 128
        assert fused_kernel.plan_tiles(16, 256, 128, 128).bk == 256
        assert fused_kernel.plan_tiles(16, 512, 128, 128).bk == 256
        plan = fused_kernel.plan_tiles(16, 256, 128, 128, t=7)
        assert plan.activity_shape == (7, 1, 1)
        assert plan.activity_bytes == 28


class TestModelAndServingTelemetry:
    def _setup(self):
        from repro.data import events as ev_lib
        from repro.models import snn
        dcfg = ev_lib.NMNIST
        ds = ev_lib.EventDataset(dcfg)
        cfg = snn.SNNConfig(n_in=dcfg.n_in, n_steps=dcfg.n_steps,
                            n_classes=dcfg.n_classes, mode="kwn", k=12)
        p = snn.init_params(cfg, jax.random.PRNGKey(0))
        ev, lab = ds.sample(jax.random.PRNGKey(1), 6)
        return snn, p, ev, lab, cfg

    def test_forward_silicon_reports_skipped_blocks(self):
        snn, p, ev, _, cfg = self._setup()
        _, tele = snn.forward_silicon(p, ev, cfg, jax.random.PRNGKey(2),
                                      fused="seq")
        r = np.asarray(tele["skipped_block_ratio"])
        assert r.shape == (6,)
        assert np.all((0.0 <= r) & (r <= 1.0))
        # silent streams skip every block
        _, tele0 = snn.forward_silicon(p, jnp.zeros_like(ev), cfg,
                                       jax.random.PRNGKey(2), fused="seq")
        np.testing.assert_allclose(
            np.asarray(tele0["skipped_block_ratio"]), 1.0)

    def test_mac_telemetry_opt_in_is_output_invariant(self):
        snn, p, ev, _, cfg = self._setup()
        key = jax.random.PRNGKey(2)
        l_off, t_off = snn.forward_silicon(p, ev, cfg, key, fused="seq")
        l_on, t_on = snn.forward_silicon(p, ev, cfg, key, fused="seq",
                                         mac_telemetry=True)
        np.testing.assert_array_equal(np.asarray(l_off), np.asarray(l_on))
        for name in t_off:
            np.testing.assert_array_equal(np.asarray(t_off[name]),
                                          np.asarray(t_on[name]),
                                          err_msg=f"telemetry {name}")

    def test_engine_packs_by_density(self):
        from repro.serve.engine import EventRequest, SNNEventEngine
        snn, p, ev, lab, cfg = self._setup()
        engine = SNNEventEngine(cfg, p, batch_slots=2, seed=5)
        # submit busy-then-quiet so FIFO order is density-inverted
        dens = np.asarray(jnp.mean(jnp.abs(ev) > 0, axis=(1, 2)))
        order = list(np.argsort(dens)[::-1])
        for i in order:
            engine.submit(EventRequest(uid=int(i), events=ev[int(i)],
                                       label=int(lab[int(i)])))
        done = engine.run()
        assert len(done) == 6
        # results come back in SUBMISSION order (here density-descending);
        # the density sort only reorders the internal batches
        got_dens = [r.density for r in done]
        assert got_dens == sorted(got_dens, reverse=True)
        assert [r.uid for r in done] == [int(i) for i in order]
        assert all(r.skipped_block_ratio is not None for r in done)
        rep = engine.energy_report("nmnist")
        assert 0.0 <= rep["mean_skipped_block_ratio"] <= 1.0

    def test_engine_density_packing_is_output_invariant(self):
        """Packing moves requests between batches; every request's logits
        must not change.  SNL off: the PRBS rescue stream is threaded
        across the whole batch (row position keys the draw — silicon
        behaviour), so only the noiseless LIF path is batch-composition
        invariant."""
        from repro.serve.engine import EventRequest, SNNEventEngine
        snn, p, ev, lab, cfg = self._setup()
        import dataclasses
        cfg = dataclasses.replace(cfg, use_snl=False)
        results = {}
        for pack in (False, True):
            engine = SNNEventEngine(cfg, p, batch_slots=2, seed=5,
                                    pack_by_density=pack)
            for i in range(6):
                engine.submit(EventRequest(uid=i, events=ev[i],
                                           label=int(lab[i])))
            results[pack] = {r.uid: r for r in engine.run()}
        for uid in range(6):
            np.testing.assert_array_equal(
                np.asarray(results[False][uid].logits),
                np.asarray(results[True][uid].logits),
                err_msg=f"uid {uid}")
            assert results[False][uid].pred == results[True][uid].pred
