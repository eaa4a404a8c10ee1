"""Chip compiles of the main-path kernels for a described TPU v5e.

The Pallas interpreter accepts kernels the TPU compiler refuses (a lane
``cumsum``, a uint32 -> f32 cast, a 3-D branch reduction, more scoped VMEM
than the default limit), so every CPU parity test can pass while the chip
fails on its first launch.  Each test here lowers one entry point at the
shape users drive it with, with the kernels out of interpret mode, and
compiles it with Mosaic for a v5e that is described, not attached.  Nothing
runs: these say the program compiles, not what it computes.

The topology is described inside the module fixture, never at import or
collection: only one process may load the TPU library, and every
pytest-xdist worker imports this file.  Interpret mode is steered per test
with ``monkeypatch``; jit caches are cleared afterwards so no chip-traced
program leaks into the CPU tests that share the worker.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import ima as ima_lib
from repro.kernels import fused_macro, ops
from repro.models import snn

N_IN, N_HIDDEN, T = 512, 128, 20          # the N-MNIST stand-in


@pytest.fixture(scope="module")
def v5e():
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        from jax.experimental import topologies
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — no TPU compiler here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return topo.devices[0]


@pytest.fixture
def chip(v5e, monkeypatch):
    """A sharding on the described chip, with the kernels compiling for it
    (interpret mode off).  Eager arrays stay on the CPU; only the compile
    sees the v5e as its default device (``_compile``)."""
    monkeypatch.setattr(ops, "interpret_mode", lambda: False)
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        yield SingleDeviceSharding(v5e)
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_on)
        jax.clear_caches()


def _on(sharding, tree):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _compile(chip, fn, *args):
    """Compile ``fn`` for the chip, with ``pltpu.get_tpu_info`` sizing the
    v5e; the compiled text must hold a Mosaic kernel."""
    with jax.default_device(next(iter(chip.device_set))):
        compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _cfg(**kw):
    return snn.SNNConfig(n_in=N_IN, n_steps=T, n_classes=10, k=3, **kw)


_KEY = jax.ShapeDtypeStruct((2,), jnp.uint32)


def _params(chip, cfg):
    return _on(chip, jax.eval_shape(lambda k: snn.init_params(cfg, k), _KEY))


@pytest.mark.parametrize("noisy", [False, True], ids=["clean", "noisy"])
def test_engine_round_64_slots(chip, noisy):
    """One continuous-batching round of ``SNNEventEngine`` at its default
    64 slots: the gated KWN kernel, noisy with the per-slot ``row_ctl``."""
    cfg = _cfg()
    noise = ima_lib.IMANoiseModel() if noisy else None
    state = _on(chip, jax.eval_shape(lambda: snn.silicon_stream_init(cfg, 64)))
    events = _on(chip, jax.ShapeDtypeStruct((8, 64, N_IN), jnp.float32))
    _compile(chip, lambda p, ev, st: snn.forward_silicon_stream(
        p, ev, cfg, st, noise=noise), _params(chip, cfg), events, state)


def _seq_operands(chip, m, k_dim, n):
    return _on(chip, (jax.ShapeDtypeStruct((T, m, k_dim), jnp.float32),
                      jax.ShapeDtypeStruct((k_dim, n), jnp.int8),
                      jax.ShapeDtypeStruct((k_dim, n), jnp.int8),
                      jax.ShapeDtypeStruct((n,), jnp.float32),
                      jax.ShapeDtypeStruct((m, n), jnp.float32)))


def test_kwn_dense_64_rows(chip):
    """The ungated (dense) KWN kernel at 64 rows x 512 x 128."""
    cb = ima_lib.nlq_codebook(5, -24.0, 24.0)
    _compile(chip, lambda x, msb, lsb, scale, v: ops.fused_macro_seq(
        x, msb, lsb, cb.boundaries, cb.levels, scale, v,
        jnp.zeros((T,) + v.shape), k=3, gate=False, mac_telemetry=False),
        *_seq_operands(chip, 64, N_IN, N_HIDDEN))


def test_nld_2x128_forward(chip):
    """NLD ``forward_silicon(fused="seq")`` on a batch of 64, two branches
    of 128 columns."""
    cfg = _cfg(mode="nld")
    events = _on(chip, jax.ShapeDtypeStruct((64, T, N_IN), jnp.float32))
    key = _on(chip, _KEY)
    _compile(chip, lambda p, ev, k: snn.forward_silicon(p, ev, cfg, k,
                                                  fused="seq"),
             _params(chip, cfg), events, key)


def test_nld_engine_round_dvs_width(chip):
    """One continuous-batching round of NLD at the DVS128 sensor width:
    32768 inputs into 2 x 128 branch-major columns, 64 slots, 8 steps —
    the NLD kernel at K = 32768, with the round's weight packing."""
    cfg = snn.SNNConfig(n_in=32768, n_hidden=128, n_classes=11, n_steps=30,
                        mode="nld", n_branches=2, activation="relu")
    with jax.default_device(next(iter(chip.device_set))):
        plan = fused_macro.plan_tiles(64, cfg.n_in, 256, 128, 8, mode="nld",
                                      n_branches=2)
        assert plan.vmem_bytes <= fused_macro.vmem_limit_bytes()
    state = _on(chip, jax.eval_shape(lambda: snn.silicon_stream_init(cfg, 64)))
    events = _on(chip, jax.ShapeDtypeStruct((8, 64, cfg.n_in), jnp.float32))
    _compile(chip, lambda p, ev, st: snn.forward_silicon_stream(
        p, ev, cfg, st), _params(chip, cfg), events, state)


def test_stack_256_128_at_32_rows(chip):
    """The stacked KWN kernel, layers (256, 128), at 32 rows, noisy."""
    cfg = _cfg(hidden_layers=(256, 128))
    events = _on(chip, jax.ShapeDtypeStruct((32, T, N_IN), jnp.float32))
    key = _on(chip, _KEY)
    _compile(chip, lambda p, ev, k: snn.forward_silicon(
        p, ev, cfg, k, fused="seq", noise=ima_lib.IMANoiseModel()),
        _params(chip, cfg), events, key)


def test_stack_dvs_width_64_rows(chip):
    """The ``dvs-stack`` cell's drain-path batch: the stacked KWN kernel at
    the DVS128 sensor width, 32768 inputs into layers (256, 128) with
    k = 12, 64 rows, T = 30, clean."""
    cfg = snn.SNNConfig(n_in=32768, hidden_layers=(256, 128), n_classes=11,
                        n_steps=30, k=12)
    events = _on(chip, jax.ShapeDtypeStruct((64, 30, cfg.n_in), jnp.float32))
    key = _on(chip, _KEY)
    _compile(chip, lambda p, ev, k: snn.forward_silicon(p, ev, cfg, k,
                                                  fused="seq"),
             _params(chip, cfg), events, key)


def test_silicon_vjp_batch_64(chip):
    """One silicon training gradient at batch 64: the fused forward with
    its training trace, then the BPTT kernel, noise-aware."""
    cfg = _cfg()
    events = _on(chip, jax.ShapeDtypeStruct((64, T, N_IN), jnp.float32))
    labels = _on(chip, jax.ShapeDtypeStruct((64,), jnp.int32))
    seed = _on(chip, jax.ShapeDtypeStruct((), jnp.float32))
    _compile(chip, jax.grad(lambda p, ev, lab, s: snn.loss_fn(
        p, ev, lab, cfg, s, silicon=True, noise=ima_lib.IMANoiseModel())),
        _params(chip, cfg), events, labels, seed)


def test_wide_layer_1024x512(chip):
    """A 1024 x 512 layer (four column tiles, four K tiles) at 128 rows,
    noisy; its plan must fit the chip's VMEM limit."""
    cb = ima_lib.nlq_codebook(5, -24.0, 24.0)
    kn = ima_lib.kernel_noise_params(ima_lib.IMANoiseModel(), cb)
    with jax.default_device(next(iter(chip.device_set))):
        plan = fused_macro.plan_tiles(128, 1024, 512, 512, T)
        assert plan.vmem_bytes <= fused_macro.vmem_limit_bytes()
    _compile(chip, lambda x, msb, lsb, scale, v: ops.fused_macro_seq(
        x, msb, lsb, cb.boundaries, cb.levels, scale, v, None, k=12,
        ima_noise=kn, snl_amp=0.05, mac_telemetry=False, seed=3),
        *_seq_operands(chip, 128, 1024, 512))


def test_plan_tiles_bounds_rows_by_vmem(chip):
    """A layer too wide for 128 rows in the chip's VMEM gets a smaller row
    tile instead of a plan Mosaic would refuse."""
    with jax.default_device(next(iter(chip.device_set))):
        plan = fused_macro.plan_tiles(128, 256, 16384, 16384, 4)
        assert plan.bm < 128
        assert plan.vmem_bytes <= fused_macro.vmem_limit_bytes()

