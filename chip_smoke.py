"""Run the fused macro's main path once on one TPU chip and check it against
the ``kernels/ref.py`` oracles, run by XLA on the same chip.

    python chip_smoke.py

The model is the N-MNIST stand-in (``data/events.py``: 512 inputs, 20 time
steps, 10 classes) on a KWN hidden layer of 128 columns with k = 3, the
configuration ``examples/train_snn_events.py`` trains.  Weights and events
come from fixed seeds.  Five phases, in order:

  a. silicon training: ``snn.train(silicon=True, noise=IMANoiseModel())``
     at batch 64, through the fused forward and the BPTT kernel;
  b. ``SNNEventEngine`` at its default 64 slots serving 96 requests, clean
     and then noisy (the last round holds a partial batch);
  c. NLD ``forward_silicon(fused="seq")`` on a batch of 64 (2 x 128);
  d. the KWN stack ``hidden_layers=(256, 128)`` through ``forward_silicon``
     and the engine's drain path;
  e. NLD at the DVS128 width (32768 inputs, 2 x 128 ReLU branches, T = 30)
     served by ``SNNEventEngine`` on its continuous slots: 96 requests,
     each compared with a one-shot ``forward_silicon(fused="seq")``.

Each phase prints its compile time (set-up, the first call), its steady
time, the tile plan, and its parity against the oracle.  Clean outputs must
be bitwise equal.  The noisy comparison is stated where it is made (phase
b).  The last line of standard output is one JSON object naming the device.
The script needs a TPU: with none, or outside a checkout of the repository,
it exits non-zero and prints no result.  It runs in one process.
"""

from __future__ import annotations

import functools
import json
import os
import sys
import time

SEED = 0
BATCH = 64
REQUESTS = 96


def _say(phase: str, **fields) -> None:
    print(f"[{phase}] " + "  ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


def _timed(fn, *args):
    """(result, seconds) with the device work finished inside the clock."""
    import jax
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return out, time.perf_counter() - t0


def _plan_str(plan) -> str:
    return (f"bm={plan.bm},bk={plan.bk},bn={plan.bn},grid={plan.grid},"
            f"vmem_est_kib={plan.vmem_bytes // 1024}")


class Smoke:
    def __init__(self):
        import jax
        from repro.core import ima, lif, macro
        from repro.data import events
        from repro.models import snn

        self.jax, self.snn, self.macro, self.lif = jax, snn, macro, lif
        self.dcfg = events.NMNIST
        self.ds = events.EventDataset(self.dcfg)
        self.noise = ima.IMANoiseModel()
        self.cfg = snn.SNNConfig(n_in=self.dcfg.n_in,
                                 n_steps=self.dcfg.n_steps,
                                 n_classes=self.dcfg.n_classes, k=3)
        self.failures: list[str] = []

    # -- shared helpers -----------------------------------------------------

    def check(self, phase: str, name: str, ok: bool, detail="") -> None:
        _say(phase, check=name, result="PASS" if ok else "FAIL",
             **({"detail": detail} if detail else {}))
        if not ok:
            self.failures.append(f"{phase}:{name}")

    def lif_params(self, cfg):
        return self.lif.LIFParams(
            beta=cfg.beta, v_th1=cfg.v_th1, v_th2=cfg.v_th2,
            noise_amp=cfg.noise_amp if cfg.use_snl else 0.0)

    def prbs_stack(self, shape, t):
        """The clean SNL stream a one-shot run of batch shape ``shape``
        draws: one LFSR from ``lif_init``, ``shape`` bits per step."""
        import jax
        from repro.core import prbs
        amp = self.lif_params(self.cfg).noise_amp
        s0 = self.lif.lif_init(shape).prbs_state
        return jax.lax.scan(lambda s, _: prbs.prbs_noise(s, shape, amp),
                            s0, None, length=t)[1]

    def kwn_ref_kwargs(self, cfg):
        lp = self.lif_params(cfg)
        return dict(drive_gain=cfg.drive_gain, beta=cfg.beta,
                    v_th1=cfg.v_th1, v_th2=cfg.v_th2, v_reset=lp.v_reset,
                    v_lim=self.lif.vmem_limit(lp.vmem_bits))

    def events(self, seed, n):
        return self.ds.sample(self.jax.random.PRNGKey(seed), n)

    # -- phase a: silicon training --------------------------------------------

    def phase_train(self, p_init):
        import jax
        import jax.numpy as jnp
        import numpy as np
        from repro.core import ternary
        from repro.kernels import fused_macro, ref
        from repro.train import silicon

        snn, cfg = self.snn, self.cfg
        t0 = time.perf_counter()
        p, first = snn.train(cfg, self.ds, n_steps=1, batch=BATCH, seed=1,
                             silicon=True, noise=self.noise, params=p_init)
        setup = time.perf_counter() - t0
        t0 = time.perf_counter()
        p, losses = snn.train(cfg, self.ds, n_steps=4, batch=BATCH, seed=2,
                              silicon=True, noise=self.noise, params=p)
        steady = time.perf_counter() - t0
        plan = fused_macro.plan_tiles(BATCH, cfg.n_in, cfg.n_hidden,
                                      cfg.n_hidden, cfg.n_steps)
        _say("a", setup_compile_s=f"{setup:.3f}",
             steady_s_per_step=f"{steady / 4:.4f}", plan=_plan_str(plan),
             losses=[round(x, 5) for x in first + losses])
        self.check("a", "losses_finite",
                   bool(np.isfinite(first + losses).all()))

        # Parity on one batch of 64 at the trained weights, clean: the
        # training forward (primal and the BPTT kernel's gradients) against
        # the plain sequence oracle and jax.grad of the differentiable
        # oracle, whose matmuls run at f32.  Both sides build the ramp
        # codebook inside their jitted programs, as the training step does:
        # XLA folds it to constants on the host, and the host's pow/linspace
        # round differently from the chip's, so a codebook computed eagerly
        # on the chip is not the one the jitted kernel path uses.  Every
        # other f32 operand enters as an argument for the same reason.
        ev, _ = self.events(11, BATCH)
        x = jnp.moveaxis(ev, 1, 0)
        w, scale = silicon.quantized_weight_ste(p["w_hid"])
        w, scale = jax.lax.stop_gradient(w), jax.lax.stop_gradient(scale)
        mcfg = self.macro.CIMMacroConfig(code_bits=cfg.code_bits,
                                         mac_range=cfg.mac_range)
        nz = self.prbs_stack((BATCH, cfg.n_hidden), cfg.n_steps)
        v0 = jnp.zeros((BATCH, cfg.n_hidden), jnp.float32)
        kw = self.kwn_ref_kwargs(cfg)
        relax = silicon.DEFAULT_KWN_RELAX
        key = jax.random.PRNGKey(12)
        g_spk = jax.random.normal(key, (cfg.n_steps, BATCH, cfg.n_hidden))
        g_v = jax.random.normal(jax.random.fold_in(key, 1), v0.shape)
        ops_a = (x, scale, nz)

        def kernel(w, v, x, scale, nz):
            return self.macro.fused_seq_vjp(
                x, w, scale, mcfg, v, k=cfg.k, use_snl=cfg.use_snl,
                noise=nz, kwn_relax=relax, **kw)

        def oracle(w, v, x, scale, nz):
            nlq = snn._nlq_cb(cfg)
            out = ref.fused_macro_seq_vjp_ref(
                w, ternary.ternary_input_encode(x), nlq.boundaries,
                nlq.levels, scale, v, nz, k=cfg.k, use_snl=cfg.use_snl,
                kwn_relax=relax, ste_lo=-cfg.mac_range - 0.5,
                ste_hi=cfg.mac_range + 0.5, **kw)
            return out[1], out[0]

        def seq_oracle(w, v, x, scale, nz):
            nlq = snn._nlq_cb(cfg)
            msb, lsb = ternary.weight_decompose(w)
            _, v_fin, spk, _, _ = ref.fused_macro_seq_ref(
                ternary.ternary_input_encode(x), msb, lsb, nlq.boundaries,
                nlq.levels, scale, v, nz, k=cfg.k, use_snl=cfg.use_snl,
                **kw)
            return spk, v_fin

        def with_grads(fn):
            def loss(w, v, *ops):
                spk, vo = fn(w, v, *ops)
                return jnp.sum(spk * g_spk) + jnp.sum(vo * g_v)
            return jax.jit(jax.grad(loss, argnums=(0, 1)))

        spk_k, vo_k = jax.jit(kernel)(w, v0, *ops_a)
        spk_r, vo_r = jax.jit(seq_oracle)(w, v0, *ops_a)
        self.check("a", "vjp_primal_bitwise",
                   bool(jnp.array_equal(spk_k, spk_r)
                        and jnp.array_equal(vo_k, vo_r)))
        gw_k, gv_k = with_grads(kernel)(w, v0, *ops_a)
        with jax.default_matmul_precision("float32"):
            gw_r, gv_r = with_grads(oracle)(w, v0, *ops_a)
        err = max(float(jnp.max(jnp.abs(a - b)))
                  for a, b in ((gw_k, gw_r), (gv_k, gv_r)))
        self.check("a", "vjp_grads_bitwise",
                   bool(jnp.array_equal(gw_k, gw_r)
                        and jnp.array_equal(gv_k, gv_r)),
                   f"max_abs_diff={err:.3e}")
        return p

    # -- phase b: the serving engine, clean then noisy ------------------------

    def _ref_request(self, fw, kn, noisy: bool):
        """Batch-1 oracle for one served request -> (spike counts, ADC sum)."""
        import jax
        import jax.numpy as jnp
        from repro.kernels import ref

        cfg = self.cfg
        lp = self.lif_params(cfg)
        kw = self.kwn_ref_kwargs(cfg)

        @jax.jit
        def run(ev, seed, v0, msb, lsb, bounds, levels, scale):
            nz = None if noisy else self.prbs_stack((1, cfg.n_hidden),
                                                    ev.shape[0])
            _, _, spk, _, steps = ref.fused_macro_seq_ref(
                ev[:, None, :], msb, lsb, bounds, levels, scale, v0, nz,
                k=cfg.k, use_snl=cfg.use_snl,
                ima_noise=kn if noisy else None,
                snl_amp=lp.noise_amp if noisy else 0.0, seed=seed, **kw)
            return jnp.sum(spk, axis=0)[0], jnp.sum(
                steps.astype(jnp.float32))

        v0 = jnp.zeros((1, cfg.n_hidden), jnp.float32)
        return lambda ev, seed: run(ev, seed, v0, fw.msb, fw.lsb,
                                    fw.boundaries, fw.levels, fw.scale)

    def phase_engine(self, p):
        import jax
        import jax.numpy as jnp
        import numpy as np
        from repro.kernels import fused_macro
        from repro.serve.engine import EventRequest, SNNEventEngine

        snn, cfg = self.snn, self.cfg
        ev, lab = self.events(21, REQUESTS)
        ev_np = np.asarray(ev)
        mcfg = self.macro.CIMMacroConfig(code_bits=cfg.code_bits,
                                         mac_range=cfg.mac_range)
        fw = snn._pack_fused(p, cfg, "kwn", mcfg)
        kn = self.macro.fused_kernel_noise(
            fw, mcfg._replace(ima_noise=self.noise))
        t_len = float(cfg.n_steps)
        for tag, noise in (("clean", None), ("noisy", self.noise)):
            eng = SNNEventEngine(cfg, p, noise=noise)
            eng.submit(EventRequest(uid=-1, events=ev_np[0]))
            t0 = time.perf_counter()
            eng.run()
            setup = time.perf_counter() - t0
            reqs = [eng.submit(EventRequest(uid=i, events=ev_np[i],
                                            label=int(lab[i])))
                    for i in range(REQUESTS)]
            rounds = eng.metrics.counter("rounds_total")
            r0 = rounds.value
            t0 = time.perf_counter()
            done = eng.run()
            steady = time.perf_counter() - t0
            plan = fused_macro.plan_tiles(eng.b, cfg.n_in, cfg.n_hidden,
                                          cfg.n_hidden, eng.round_steps)
            _say("b", mode=tag, slots=eng.b, requests=len(done),
                 setup_compile_s=f"{setup:.3f}", steady_s=f"{steady:.3f}",
                 rounds=int(rounds.value - r0),
                 plan=_plan_str(plan),
                 acc=f"{np.mean([r.pred == r.label for r in done]):.3f}")
            self.check("b", f"{tag}_all_completed",
                       len(done) == REQUESTS
                       and all(r.logits is not None for r in reqs))
            one = self._ref_request(fw, kn, noisy=noise is not None)
            bad = 0
            for r in reqs:
                seed = int(snn._noise_seed(r.key)) if noise is not None \
                    else 0
                counts, adc = one(jnp.asarray(ev_np[r.uid]), seed)
                # the engine's readout, op for op (batch-1, eager), with
                # the ramp-step mean divided on the device as it is there
                logits = (counts[None] / t_len) @ p["w_out"]
                adc_mean = float(adc / t_len)
                if not (jnp.array_equal(logits[0], r.logits)
                        and adc_mean == r.adc_steps):
                    bad += 1
            self.check("b", f"{tag}_bitwise_vs_ref", bad == 0,
                       f"requests_differing={bad}/{REQUESTS}")
        self.check_noise_draws()

    def check_noise_draws(self):
        """Does the in-kernel Box-Muller draw equal XLA's, bit for bit?

        The noisy oracle calls the same ``ctrprng`` function as the kernel;
        the only thing that can differ between them on the chip is how
        Mosaic and XLA evaluate ``log``/``sqrt``/``cos``.  This compares the
        Gaussian draws directly, over the 64 x 128 grid of one step.
        """
        import jax
        import jax.numpy as jnp
        from jax.experimental import pallas as pl
        from repro.core import ctrprng
        from repro.kernels import ops

        shape = (BATCH, self.cfg.n_hidden)

        def draw():
            rows = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
            cols = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
            return ctrprng.counter_normal(jnp.int32(12345), jnp.int32(7),
                                          rows, cols, ctrprng.TAG_IMA)

        def kernel(o_ref):
            o_ref[...] = draw()

        in_kernel = pl.pallas_call(
            kernel, out_shape=jax.ShapeDtypeStruct(shape, jnp.float32),
            interpret=ops.interpret_mode())()
        in_xla = jax.jit(draw)()
        n_diff = int(jnp.sum(in_kernel != in_xla))
        max_diff = float(jnp.max(jnp.abs(in_kernel - in_xla)))
        _say("b", noise_draws="mosaic_vs_xla", elements=in_xla.size,
             differing=n_diff, max_abs_diff=f"{max_diff:.3e}")

    # -- phase c: NLD ---------------------------------------------------------

    def phase_nld(self):
        import jax
        import jax.numpy as jnp
        from repro.kernels import ref

        snn = self.snn
        cfg = snn.SNNConfig(n_in=self.dcfg.n_in, n_steps=self.dcfg.n_steps,
                            n_classes=self.dcfg.n_classes, mode="nld")
        p = snn.init_params(cfg, jax.random.PRNGKey(SEED + 3))
        ev, _ = self.events(31, BATCH)
        key = jax.random.PRNGKey(32)
        fwd = jax.jit(lambda p, ev, k: snn.forward_silicon(
            p, ev, cfg, k, fused="seq"))
        (logits, tele), setup = _timed(fwd, p, ev, key)
        _, steady = _timed(fwd, p, ev, key)
        mcfg = self.macro.CIMMacroConfig(code_bits=cfg.code_bits,
                                         mac_range=cfg.dend_range)
        fw = snn._pack_fused(p, cfg, "nld", mcfg)
        plan, _ = self.macro.plan_fused_tiles(BATCH, fw, cfg.n_hidden,
                                              cfg.n_steps)
        _say("c", setup_compile_s=f"{setup:.3f}", steady_s=f"{steady:.4f}",
             plan=_plan_str(plan))
        x = jnp.moveaxis(ev, 1, 0)
        v0 = jnp.zeros((BATCH, cfg.n_hidden), jnp.float32)
        zeros = jnp.zeros((cfg.n_steps,) + v0.shape, jnp.float32)
        kw = dict(self.kwn_ref_kwargs(cfg), use_snl=False)
        arrays = fw[:-1]                       # every field but the mode
        v_k, spk_k, _, steps_k, _ = jax.jit(
            lambda x, arrays, v, zeros: self.macro.fused_seq(
                x, fw._make(arrays + ("nld",)), v, zeros,
                drive_gain=cfg.drive_gain, beta=cfg.beta, v_th1=cfg.v_th1,
                v_th2=cfg.v_th2, v_reset=kw["v_reset"], v_lim=kw["v_lim"],
                use_snl=False, mac_telemetry=False))(x, arrays, v0, zeros)

        @jax.jit
        def oracle(x, arrays, v, zeros, w_out):
            msb, lsb, scale, bounds, levels, w_dend = arrays
            _, v_fin, spk, _, steps = ref.fused_macro_seq_ref(
                x, msb, lsb, bounds, levels, scale, v, zeros, w_dend,
                mode="nld", **kw)
            return v_fin, spk, steps, (jnp.sum(spk, 0) / cfg.n_steps) @ w_out

        v_r, spk_r, steps_r, logits_r = oracle(x, arrays, v0, zeros,
                                               p["w_out"])
        self.check("c", "kernel_bitwise_vs_ref",
                   bool(jnp.array_equal(v_k, v_r)
                        and jnp.array_equal(spk_k, spk_r)
                        and jnp.array_equal(steps_k, steps_r[..., 0])))
        self.check("c", "forward_silicon_logits_bitwise",
                   bool(jnp.array_equal(logits, logits_r)),
                   f"max_abs_diff={float(jnp.max(jnp.abs(logits - logits_r))):.3e}")

    # -- phase d: the KWN stack -----------------------------------------------

    def phase_stack(self):
        import jax
        import jax.numpy as jnp
        import numpy as np
        from repro.kernels import fused_macro, ref
        from repro.serve.engine import EventRequest, SNNEventEngine

        snn = self.snn
        cfg = snn.SNNConfig(n_in=self.dcfg.n_in, n_steps=self.dcfg.n_steps,
                            n_classes=self.dcfg.n_classes, k=3,
                            hidden_layers=(256, 128))
        p = snn.init_params(cfg, jax.random.PRNGKey(SEED + 4))
        widths = cfg.layer_widths
        mcfg = self.macro.CIMMacroConfig(code_bits=cfg.code_bits,
                                         mac_range=cfg.mac_range)
        stack = snn._pack_fused_stack(p, cfg, mcfg)
        kw = dict(self.kwn_ref_kwargs(cfg), use_snl=cfg.use_snl)

        planes = [(fw.msb, fw.lsb, fw.boundaries, fw.levels, fw.scale)
                  for fw in stack]

        @functools.partial(jax.jit, static_argnames=("b",))
        def oracle(ev, planes, vs, w_out, b):
            x = jnp.moveaxis(ev, 1, 0)
            noises = [self.prbs_stack((b, w), cfg.n_steps) for w in widths]
            _, spk, _, _, _ = ref.fused_macro_multi_seq_ref(
                x, planes, vs, noises, ks=cfg.layer_k,
                seeds=jnp.zeros((len(widths),), jnp.int32), **kw)
            return spk, (jnp.sum(spk, 0) / cfg.n_steps) @ w_out

        def zeros_v(b):
            return [jnp.zeros((b, w), jnp.float32) for w in widths]

        ev, _ = self.events(41, BATCH)
        key = jax.random.PRNGKey(42)
        fwd = jax.jit(lambda p, ev, k: snn.forward_silicon(
            p, ev, cfg, k, fused="seq"))
        (logits, _), setup = _timed(fwd, p, ev, key)
        _, steady = _timed(fwd, p, ev, key)
        plan = fused_macro.plan_tiles(BATCH, cfg.n_in, widths[0], widths[0],
                                      cfg.n_steps)
        _say("d", layers=widths, setup_compile_s=f"{setup:.3f}",
             steady_s=f"{steady:.4f}", plan_layer0=_plan_str(plan))
        spk_r, logits_r = oracle(ev, planes, zeros_v(BATCH), p["w_out"],
                                 b=BATCH)
        arrays = [fw[:-1] for fw in stack]     # every field but the mode
        spk_k = jax.jit(lambda x, arrays, vs: self.macro.fused_multi_seq(
            x, [stack[0]._make(a + ("kwn",)) for a in arrays], vs,
            [self.prbs_stack((BATCH, w), cfg.n_steps) for w in widths],
            ks=cfg.layer_k, seeds=jnp.zeros((len(widths),), jnp.int32),
            **kw).spikes)(jnp.moveaxis(ev, 1, 0), arrays, zeros_v(BATCH))
        self.check("d", "kernel_bitwise_vs_ref",
                   bool(jnp.array_equal(spk_k, spk_r)))
        self.check("d", "forward_silicon_logits_bitwise",
                   bool(jnp.array_equal(logits, logits_r)))

        # The engine serves stacks through its drain path: whole sequences
        # in batches of its 64 slots, ordered by event density.
        ev, lab = self.events(43, REQUESTS)
        ev_np = np.asarray(ev)
        eng = SNNEventEngine(cfg, p)
        reqs = [eng.submit(EventRequest(uid=i, events=ev_np[i],
                                        label=int(lab[i])))
                for i in range(REQUESTS)]
        t0 = time.perf_counter()
        done = eng.run()
        served = time.perf_counter() - t0
        _say("d", engine="drain", continuous=eng.continuous,
             requests=len(done), serve_s=f"{served:.3f}")
        order = sorted(reqs, key=lambda r: (r.density, r.uid))
        bad = 0
        for i0 in range(0, REQUESTS, eng.b):
            chunk = order[i0:i0 + eng.b]
            batch = np.zeros((eng.b,) + ev_np.shape[1:], np.float32)
            for j, r in enumerate(chunk):
                batch[j] = ev_np[r.uid]
            _, lr = oracle(jnp.asarray(batch), planes, zeros_v(eng.b),
                           p["w_out"], b=eng.b)
            bad += sum(not bool(jnp.array_equal(lr[j], r.logits))
                       for j, r in enumerate(chunk))
        self.check("d", "engine_drain_bitwise_vs_ref",
                   bad == 0 and len(done) == REQUESTS,
                   f"requests_differing={bad}/{REQUESTS}")


    # -- phase e: NLD served continuously at the DVS128 width ----------------

    def phase_nld_engine(self):
        import dataclasses

        import jax
        import jax.numpy as jnp
        import numpy as np
        from repro.data import events
        from repro.serve.engine import EventRequest, SNNEventEngine

        snn = self.snn
        dcfg = dataclasses.replace(events.DVS_GESTURE, n_in=32768)
        cfg = snn.SNNConfig(n_in=dcfg.n_in, n_steps=dcfg.n_steps,
                            n_classes=dcfg.n_classes, mode="nld",
                            n_branches=2, activation="relu")
        p = snn.init_params(cfg, jax.random.PRNGKey(SEED + 5))
        ev, _ = events.EventDataset(dcfg).sample(jax.random.PRNGKey(51),
                                                 REQUESTS)
        ev_np = np.asarray(ev)
        eng = SNNEventEngine(cfg, p)
        eng.submit(EventRequest(uid=-1, events=ev_np[0]))
        t0 = time.perf_counter()
        eng.run()
        setup = time.perf_counter() - t0
        reqs = [eng.submit(EventRequest(uid=i, events=ev_np[i]))
                for i in range(REQUESTS)]
        t0 = time.perf_counter()
        done = eng.run()
        steady = time.perf_counter() - t0
        _say("e", n_in=cfg.n_in, slots=eng.b, continuous=eng.continuous,
             requests=len(done), setup_compile_s=f"{setup:.3f}",
             steady_s=f"{steady:.3f}",
             conversions=eng.metrics.value("ima_conversions_total"))
        bad = silent = 0
        for r in reqs:
            # eager, as a caller makes a one-shot request: the readout is
            # then the batch-1 product the engine's readout reproduces
            logits, tele = snn.forward_silicon(
                p, jnp.asarray(ev_np[r.uid])[None], cfg, r.key, fused="seq")
            bad += not (bool(jnp.array_equal(logits[0], r.logits))
                        and float(tele["adc_steps"][0]) == r.adc_steps)
            silent += not bool(jnp.any(r.logits != 0))
        self.check("e", "engine_continuous", eng.continuous
                   and len(done) == REQUESTS)
        self.check("e", "engine_bitwise_vs_one_shot", bad == 0,
                   f"requests_differing={bad}/{REQUESTS} "
                   f"silent={silent}/{REQUESTS}")


def main() -> int:
    here = os.path.dirname(os.path.abspath(__file__))
    sys.path.insert(0, os.path.join(here, "src"))
    from repro import compile_cache

    cache_dir = compile_cache.enable()
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {dev.platform!r}",
              file=sys.stderr)
        return 1
    _say("device", platform=dev.platform, kind=dev.device_kind,
         count=len(devices), compile_cache=cache_dir)

    smoke = Smoke()
    p0 = smoke.snn.init_params(smoke.cfg, jax.random.PRNGKey(SEED))
    p = smoke.phase_train(p0)
    smoke.phase_engine(p)
    smoke.phase_nld()
    smoke.phase_stack()
    smoke.phase_nld_engine()
    if smoke.failures:
        print(f"chip_smoke: failed checks: {smoke.failures}",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
