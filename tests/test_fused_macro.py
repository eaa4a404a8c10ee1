"""Fused macro kernel vs the composed ref.py oracle.

The fused kernel (MAC -> IMA ramp -> KWN/NLD head -> LIF in one Pallas
kernel, interpret=True on CPU CI), run for one time step, must match
``ref.fused_macro_step_ref``
*bitwise* at f32 accumulation: the MAC partials are small exact integers and
the head mirrors the oracle operation-for-operation.  The oracle is jitted so
both sides get identical XLA arithmetic contraction (FMA) treatment.

Covers: both modes (kwn/nld), all three IMA curves (linear / NLQ /
NL-activation), odd shapes (n_in not a multiple of 256, n_out not a multiple
of 128, batch not a multiple of 8), SNL on/off, multi-macro tiling (layers
wider than 256x128 stay fused — no composed-path fallback), time-major
sequences (T folded into the kernel grid, membrane carried in VMEM), and the
model/serving layers built on top (forward_silicon(fused=True/"seq"),
SNNEventEngine batching).  The exhaustive shape sweeps live in
tests/test_fused_macro_properties.py.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ima as ima_lib
from repro.core import macro as macro_lib
from repro.kernels import ops, ref


def _tern(key, shape, rate=0.2):
    sparse = jax.random.uniform(jax.random.fold_in(key, 1), shape) < rate
    vals = jax.random.randint(key, shape, -1, 2)
    return (vals * sparse).astype(jnp.int8)


def _codebook(kind, bits=5, rng=24.0):
    if kind == "lin":
        return ima_lib.linear_codebook(bits, -rng, rng)
    if kind == "nlq":
        return ima_lib.nlq_codebook(bits, -rng, rng)
    return ima_lib.activation_codebook(bits, ima_lib.quadratic, -rng, rng)


def _one_step(x, msb, lsb, boundaries, levels, scale, v, noise=None,
              w_dend=None, **kw):
    """One time step through the sequence kernel: a T = 1 launch with the
    time axis indexed back off."""
    mac, v_out, spk, mask, steps = ops.fused_macro_seq(
        x[None], msb, lsb, boundaries, levels, scale, v,
        None if noise is None else noise[None], w_dend, **kw)
    return (None if mac is None else mac[0], v_out, spk[0], mask[0],
            steps[0])


def _ref_jit(**static):
    return jax.jit(functools.partial(ref.fused_macro_step_ref, **static))


def _assert_bitwise(out, want, n):
    names = ("mac", "v_mem", "spikes", "mask", "adc_steps")
    want = list(want)
    want[4] = want[4][..., 0]
    for name, a, b in zip(names, out, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=f"{name} mismatch")


class TestFusedKwnParity:
    @pytest.mark.parametrize("m,n_in,n_out", [
        (16, 256, 128),           # one physical macro
        (128, 512, 128),          # two row tiles
        (9, 256, 128),            # batch padding
        (16, 300, 130),           # n_in % 256 != 0, n_out % 128 != 0
        (5, 100, 40),             # tiny odd everything
    ])
    @pytest.mark.parametrize("curve", ["lin", "nlq"])
    def test_matches_ref(self, m, n_in, n_out, curve):
        keys = jax.random.split(jax.random.PRNGKey(m * 31 + n_in + n_out), 6)
        x = _tern(keys[0], (m, n_in))
        msb, lsb = _tern(keys[1], (n_in, n_out)), _tern(keys[2], (n_in, n_out))
        cb = _codebook(curve)
        scale = jax.random.uniform(keys[3], (n_out,), minval=0.05, maxval=0.3)
        v = jax.random.normal(keys[4], (m, n_out)) * 0.5
        noise = 0.05 * jnp.sign(jax.random.normal(keys[5], (m, n_out)))
        k = min(12, n_out)
        kw = dict(mode="kwn", k=k, drive_gain=0.25)
        out = _one_step(x, msb, lsb, cb.boundaries, cb.levels, scale, v,
                        noise, **kw)
        want = _ref_jit(**kw)(x, msb, lsb, cb.boundaries, cb.levels, scale,
                              v, noise)
        _assert_bitwise(out, want, n_out)

    @pytest.mark.parametrize("k", [1, 3, 12, 127])
    def test_k_sweep(self, k):
        keys = jax.random.split(jax.random.PRNGKey(k), 6)
        x = _tern(keys[0], (16, 256))
        msb, lsb = _tern(keys[1], (256, 128)), _tern(keys[2], (256, 128))
        cb = _codebook("nlq")
        scale = jax.random.uniform(keys[3], (128,), minval=0.05, maxval=0.3)
        v = jax.random.normal(keys[4], (16, 128)) * 0.5
        noise = 0.05 * jnp.sign(jax.random.normal(keys[5], (16, 128)))
        kw = dict(mode="kwn", k=k, drive_gain=0.25)
        out = _one_step(x, msb, lsb, cb.boundaries, cb.levels, scale, v,
                        noise, **kw)
        want = _ref_jit(**kw)(x, msb, lsb, cb.boundaries, cb.levels, scale,
                              v, noise)
        _assert_bitwise(out, want, 128)
        assert bool(jnp.all(out[3].sum(-1) == k))

    @pytest.mark.parametrize("use_snl", [True, False])
    def test_snl_toggle(self, use_snl):
        keys = jax.random.split(jax.random.PRNGKey(7), 6)
        x = _tern(keys[0], (16, 256))
        msb, lsb = _tern(keys[1], (256, 128)), _tern(keys[2], (256, 128))
        cb = _codebook("nlq")
        scale = jax.random.uniform(keys[3], (128,), minval=0.1, maxval=0.3)
        # park membranes inside the SNL band so the toggle matters
        v = 0.8 * jnp.ones((16, 128))
        noise = 0.3 * jnp.sign(jax.random.normal(keys[5], (16, 128)))
        kw = dict(mode="kwn", k=12, drive_gain=0.25, use_snl=use_snl)
        out = _one_step(x, msb, lsb, cb.boundaries, cb.levels, scale, v,
                        noise, **kw)
        want = _ref_jit(**kw)(x, msb, lsb, cb.boundaries, cb.levels, scale,
                              v, noise)
        _assert_bitwise(out, want, 128)

    def test_batched_leading_dims(self):
        keys = jax.random.split(jax.random.PRNGKey(3), 6)
        x = _tern(keys[0], (2, 5, 256))
        msb, lsb = _tern(keys[1], (256, 128)), _tern(keys[2], (256, 128))
        cb = _codebook("nlq")
        scale = jnp.full((128,), 0.1)
        v = jax.random.normal(keys[4], (2, 5, 128)) * 0.5
        noise = jnp.zeros((2, 5, 128))
        out = _one_step(x, msb, lsb, cb.boundaries, cb.levels, scale, v,
                        noise, mode="kwn", k=12)
        assert out[1].shape == (2, 5, 128) and out[4].shape == (2, 5)
        flat = _one_step(x.reshape(10, 256), msb, lsb, cb.boundaries,
                         cb.levels, scale, v.reshape(10, 128),
                         noise.reshape(10, 128), mode="kwn", k=12)
        np.testing.assert_array_equal(np.asarray(out[1]).reshape(10, 128),
                                      np.asarray(flat[1]))


class TestFusedSeqParity:
    """Tiled multi-macro + time-major acceptance: big layers and long
    streams run through the fused path bitwise-equal to the seq oracle."""

    def _operands(self, t, m, n_in, n_out, seed=0):
        keys = jax.random.split(jax.random.PRNGKey(seed), 6)
        x = _tern(keys[0], (t, m, n_in))
        msb = _tern(keys[1], (n_in, n_out))
        lsb = _tern(keys[2], (n_in, n_out))
        cb = _codebook("nlq")
        scale = jax.random.uniform(keys[3], (n_out,), minval=0.05,
                                   maxval=0.3)
        v = jax.random.normal(keys[4], (m, n_out)) * 0.5
        noise = 0.05 * jnp.sign(jax.random.normal(keys[5], (t, m, n_out)))
        return x, msb, lsb, cb, scale, v, noise

    def _assert_seq(self, t, m, n_in, n_out):
        x, msb, lsb, cb, scale, v, noise = self._operands(t, m, n_in, n_out)
        kw = dict(mode="kwn", k=12, drive_gain=0.25)
        out = ops.fused_macro_seq(x, msb, lsb, cb.boundaries, cb.levels,
                                  scale, v, noise, **kw)
        want = jax.jit(functools.partial(ref.fused_macro_seq_ref, **kw))(
            x, msb, lsb, cb.boundaries, cb.levels, scale, v, noise)
        want = list(want)
        want[4] = want[4][..., 0]
        for name, a, b in zip(("mac", "v_mem", "spikes", "mask",
                               "adc_steps"), out, want):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          err_msg=f"{name} mismatch")

    def test_large_layer_stays_fused(self):
        """M>128 (two row tiles), K>256 (two K tiles), N>128 (two col
        tiles): the whole virtual macro grid runs inside one kernel."""
        self._assert_seq(t=2, m=144, n_in=512, n_out=256)

    def test_long_stream_time_major(self):
        """T=16 event stream in a single launch, membrane carried in
        VMEM."""
        self._assert_seq(t=16, m=16, n_in=256, n_out=128)

    def test_long_stream_large_layer(self):
        """Both at once: the acceptance shape for this PR."""
        self._assert_seq(t=16, m=8, n_in=512, n_out=256)

    def test_t1_seq_equals_step(self):
        """T=1 degenerate: the sequence kernel equals the one-step oracle
        bitwise."""
        x, msb, lsb, cb, scale, v, noise = self._operands(1, 16, 256, 128)
        kw = dict(mode="kwn", k=12, drive_gain=0.25)
        seq = ops.fused_macro_seq(x, msb, lsb, cb.boundaries, cb.levels,
                                  scale, v, noise, **kw)
        want = _ref_jit(**kw)(x[0], msb, lsb, cb.boundaries, cb.levels,
                              scale, v, noise[0])
        _assert_bitwise((seq[0][0], seq[1], seq[2][0], seq[3][0],
                         seq[4][0]), want, 128)


class TestTilePlanner:
    """plan_tiles / plan_fused_tiles: padded geometry the kernel asserts
    on, branch-aligned NLD padding, and the macro accounting the energy
    model consumes."""

    def test_single_macro_is_one_tile(self):
        from repro.kernels import fused_macro
        plan = fused_macro.plan_tiles(16, 256, 128, 128, t=4)
        assert plan.grid == (1, 4, 1, 1)
        assert plan.bn == 128 and plan.nc_pad == 128 and plan.n_pad == 128

    def test_large_layer_grid_and_divisibility(self):
        from repro.kernels import fused_macro
        plan = fused_macro.plan_tiles(144, 512, 256, 256, t=2)
        assert plan.grid == (2, 2, 2, 2)
        assert plan.m_pad % plan.bm == 0
        assert plan.k_pad % plan.bk == 0
        assert plan.nc_pad % plan.bn == 0
        assert plan.n_valid == 256
        assert plan.vmem_bytes > 0

    def test_nld_padding_is_branch_aligned(self):
        from repro.kernels import fused_macro
        # J=3 branches, n=130: nc=390 > 128 so columns tile; padding must
        # keep J * n_pad a multiple of bn so tiles never split a ragged pad
        plan = fused_macro.plan_tiles(8, 256, 390, 130, mode="nld",
                                      n_branches=3)
        assert plan.nc_pad == 3 * plan.n_pad
        assert plan.nc_pad % plan.bn == 0
        assert plan.n_pad >= 130

    def test_macro_plan_counts_physical_macros(self):
        cb = _codebook("nlq")
        fw = macro_lib.FusedMacroWeights(
            msb=jnp.zeros((512, 256), jnp.int8),
            lsb=jnp.zeros((512, 256), jnp.int8),
            scale=jnp.ones((256,)), boundaries=cb.boundaries,
            levels=cb.levels, w_dend=None, mode="kwn")
        plan, geo = macro_lib.plan_fused_tiles(128, fw, 256, n_steps=16)
        assert geo.n_macros == 4                 # 2 row x 2 col 256x128 tiles
        assert plan.grid == (1, 16, 2, 2)


class TestFusedNldParity:
    @pytest.mark.parametrize("m,n_in,n_out,j", [
        (16, 256, 128, 2),
        (16, 300, 130, 2),        # odd shapes
        (9, 256, 64, 3),          # three branches, batch padding
    ])
    @pytest.mark.parametrize("act", ["quadratic", "relu"])
    def test_matches_ref(self, m, n_in, n_out, j, act):
        keys = jax.random.split(jax.random.PRNGKey(m + n_out + j), 7)
        x = _tern(keys[0], (m, n_in))
        msb = _tern(keys[1], (n_in, j * n_out))
        lsb = _tern(keys[2], (n_in, j * n_out))
        cb = ima_lib.activation_codebook(
            5, ima_lib.DENDRITE_ACTIVATIONS[act], -4.0, 4.0)
        scale = jax.random.uniform(keys[3], (j * n_out,), minval=0.01,
                                   maxval=0.05)
        w_dend = jax.random.normal(keys[4], (j, n_out)) / np.sqrt(j)
        v = jax.random.normal(keys[5], (m, n_out)) * 0.5
        noise = jnp.zeros((m, n_out))
        kw = dict(mode="nld", drive_gain=0.25)
        out = _one_step(x, msb, lsb, cb.boundaries, cb.levels, scale, v,
                        noise, w_dend=w_dend, **kw)
        want = _ref_jit(**kw)(x, msb, lsb, cb.boundaries, cb.levels, scale,
                              v, noise, w_dend)
        _assert_bitwise(out, want, n_out)
        # NLD: dense LIF update, full ramp every step
        np.testing.assert_array_equal(np.asarray(out[3]),
                                      np.ones((m, n_out), np.float32))
        np.testing.assert_array_equal(np.asarray(out[4]),
                                      np.full((m,), 31, np.int32))


class TestForwardSiliconFused:
    """The model-level wiring: fused scan body == composed scan body."""

    def _setup(self, mode):
        from repro.data import events as ev_lib
        from repro.models import snn
        dcfg = ev_lib.NMNIST
        ds = ev_lib.EventDataset(dcfg)
        cfg = snn.SNNConfig(n_in=dcfg.n_in, n_steps=dcfg.n_steps,
                            n_classes=dcfg.n_classes, mode=mode, k=12)
        p = snn.init_params(cfg, jax.random.PRNGKey(0))
        ev, _ = ds.sample(jax.random.PRNGKey(1), 8)
        return snn, p, ev, cfg

    @pytest.mark.parametrize("use_snl", [True, False])
    def test_kwn_bitwise_vs_composed(self, use_snl):
        snn, p, ev, cfg = self._setup("kwn")
        key = jax.random.PRNGKey(2)
        lc, tc = snn.forward_silicon(p, ev, cfg, key, use_snl=use_snl)
        lf, tf = snn.forward_silicon(p, ev, cfg, key, use_snl=use_snl,
                                     fused=True)
        np.testing.assert_array_equal(np.asarray(lc), np.asarray(lf))
        for name in tc:
            np.testing.assert_array_equal(np.asarray(tc[name]),
                                          np.asarray(tf[name]),
                                          err_msg=f"telemetry {name}")

    def test_nld_runs_and_reports_full_ramp(self):
        snn, p, ev, cfg = self._setup("nld")
        logits, tele = snn.forward_silicon(p, ev, cfg, jax.random.PRNGKey(2),
                                           fused=True)
        assert logits.shape == (8, cfg.n_classes)
        np.testing.assert_allclose(np.asarray(tele["adc_steps"]), 31.0)
        np.testing.assert_allclose(np.asarray(tele["lif_updates"]), 128.0)

    def test_noise_model_stays_fused(self):
        """noise=IMANoiseModel() no longer forces the composed path: the
        noisy fused forward is bitwise the counter-based sequence oracle
        run on the model's packed weights with the model's seed word, and
        the draws actually perturb the clean result.  Full noisy-oracle
        parity at the kernel level: tests/test_ima_noise.py."""
        from repro.core import lif as lif_lib
        from repro.core import ternary as ternary_lib
        snn, p, ev, cfg = self._setup("kwn")
        noisy = ima_lib.IMANoiseModel()
        key = jax.random.PRNGKey(2)
        lb, tb = snn.forward_silicon(p, ev, cfg, key, noise=noisy,
                                     fused="seq")
        mcfg = macro_lib.CIMMacroConfig(code_bits=cfg.code_bits,
                                        mac_range=cfg.mac_range,
                                        ima_noise=noisy)
        fw = snn._pack_fused(p, cfg, "kwn", mcfg)
        lif_p = lif_lib.LIFParams(beta=cfg.beta, v_th1=cfg.v_th1,
                                  v_th2=cfg.v_th2, noise_amp=cfg.noise_amp)
        kw = dict(mode="kwn", k=cfg.k, drive_gain=cfg.drive_gain,
                  beta=cfg.beta, v_th1=cfg.v_th1, v_th2=cfg.v_th2,
                  v_reset=lif_p.v_reset,
                  v_lim=lif_lib.vmem_limit(lif_p.vmem_bits),
                  use_snl=cfg.use_snl,
                  ima_noise=macro_lib.fused_kernel_noise(fw, mcfg),
                  snl_amp=lif_p.noise_amp)
        x = ternary_lib.ternary_input_encode(jnp.moveaxis(ev, 1, 0))
        v0 = jnp.zeros((ev.shape[0], cfg.n_hidden))
        _, _, spk, _, steps = jax.jit(functools.partial(
            ref.fused_macro_seq_ref, **kw))(
            x, fw.msb, fw.lsb, fw.boundaries, fw.levels, fw.scale, v0, None,
            seed=snn._noise_seed(key))
        t = ev.shape[1]
        want = (jnp.sum(spk, axis=0) / t) @ p["w_out"]
        np.testing.assert_array_equal(np.asarray(lb), np.asarray(want))
        np.testing.assert_array_equal(
            np.asarray(tb["adc_steps"]),
            np.asarray(jnp.sum(steps[..., 0].astype(jnp.float32), 0) / t))
        clean, _ = snn.forward_silicon(p, ev, cfg, key, fused="seq")
        assert not np.array_equal(np.asarray(lb), np.asarray(clean))


class TestSNNEventEngine:
    def test_serves_queue_matches_direct_forward(self):
        from repro.data import events as ev_lib
        from repro.models import snn
        from repro.serve.engine import EventRequest, SNNEventEngine
        dcfg = ev_lib.NMNIST
        ds = ev_lib.EventDataset(dcfg)
        cfg = snn.SNNConfig(n_in=dcfg.n_in, n_steps=dcfg.n_steps,
                            n_classes=dcfg.n_classes, mode="kwn", k=12)
        p = snn.init_params(cfg, jax.random.PRNGKey(0))
        ev, lab = ds.sample(jax.random.PRNGKey(1), 10)

        # pack_by_density=False: this test pins the FIFO batch composition
        # so the direct-forward recomputation below sees the same batch
        # (density packing itself is covered in tests/test_fused_sparsity.py);
        # continuous=False pins the legacy drain path's per-batch key stream
        engine = SNNEventEngine(cfg, p, batch_slots=4, seed=5,
                                pack_by_density=False, continuous=False)
        for i in range(10):   # 2 full batches + 1 partial (padding path)
            engine.submit(EventRequest(uid=i, events=ev[i], label=int(lab[i])))
        done = engine.run()
        assert len(done) == 10 and not engine.pending
        assert all(r.pred is not None and 0 <= r.pred < cfg.n_classes
                   for r in done)
        assert all(0.0 <= r.adc_steps <= 31.0 for r in done)
        assert all(0.0 <= r.skipped_block_ratio <= 1.0 for r in done)
        assert all(0.0 <= r.density <= 1.0 for r in done)

        # padded dummy rows must not perturb real requests: recompute one
        # batch directly with the same key sequence
        key = jax.random.split(jax.random.PRNGKey(5))[1]
        full = jnp.stack([jnp.asarray(ev[i]) for i in range(4)])
        logits, _ = jax.jit(lambda pp, e, kk: snn.forward_silicon(
            pp, e, cfg, kk, fused=True))(p, full, key)
        np.testing.assert_array_equal(np.asarray(logits[0]),
                                      np.asarray(done[0].logits))

        rep = engine.energy_report("nmnist")
        assert rep["requests"] == 10 and rep["pj_per_sop"] > 0
