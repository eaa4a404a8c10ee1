"""Multi-pod dry-run: lower + compile every (architecture x input-shape x mesh)
cell with ShapeDtypeStruct stand-ins (no allocation), record memory_analysis /
cost_analysis / collective wire bytes for the roofline.

  PYTHONPATH=src python -m repro.launch.dryrun --arch smollm-135m --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both

train_4k lowers train_step (grad-accum microbatches + AdamW update);
prefill_32k lowers the serving prefill (last-logits + cache fill);
decode_32k / long_500k lower serve_step (one token against the full cache).
"""

import argparse
import json
import os
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs import SHAPE_SKIPS, cells, get_config
from repro.dist import sharding
from repro.launch.mesh import make_production_mesh
from repro.models import lm
from repro.nn import module
from repro.roofline import analysis, flops_model
from repro.serve import engine
from repro.train import optim, train_loop

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "dryrun_results")


def opt_profile(cfg: lm.LMConfig) -> tuple[optim.AdamWConfig, object]:
    """Optimizer memory profile by model scale (documented in EXPERIMENTS.md):
    >100B params: bf16 moments; >400B: bf16 grad accumulation too."""
    n = cfg.param_count()
    ocfg = optim.AdamWConfig(
        moment_dtype="bfloat16" if n > 100e9 else "float32")
    grad_dtype = jnp.bfloat16 if n > 400e9 else jnp.float32
    return ocfg, grad_dtype


def n_micro_for(cfg: lm.LMConfig, shape: str, mesh) -> int:
    if shape != "train_4k":
        return 1
    gb = lm.SHAPES[shape]["batch"]
    dp = mesh.shape.get("data", 1) * mesh.shape.get("pod", 1)
    per_dev_seqs = gb // dp
    # target 1-2 sequences per device-row per microbatch
    return max(1, min(per_dev_seqs, 8))


def lower_cell(arch: str, shape: str, multi_pod: bool, do_compile: bool = True):
    cfg = get_config(arch)
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = mesh.devices.size
    sh = lm.SHAPES[shape]
    kind = sh["kind"]
    rules = sharding.rules_for(cfg)

    param_specs = lm.param_specs(cfg)
    params_abs = module.abstract(param_specs, dtype=cfg.compute_dtype)
    param_sh = module.shardings(param_specs, mesh, rules)
    batch_abs = lm.batch_specs(cfg, shape)
    batch_sh = sharding.batch_shardings(cfg, mesh, shape)
    rep = sharding.replicated(mesh)

    with mesh:
        if shape == "train_4k":
            ocfg, grad_dtype = opt_profile(cfg)
            nm = n_micro_for(cfg, shape, mesh)
            step = train_loop.build_train_step(cfg, mesh, n_micro=nm,
                                               opt_cfg=ocfg,
                                               grad_dtype=grad_dtype)
            # pre-microbatched batch: (n_micro, mb, ...), dim-1 batch-sharded
            batch_abs = jax.tree.map(
                lambda s: jax.ShapeDtypeStruct(
                    (nm, s.shape[0] // nm) + s.shape[1:], s.dtype), batch_abs)
            from jax.sharding import NamedSharding
            batch_sh = {
                k: NamedSharding(mesh, module.partition_spec(
                    tuple(batch_abs[k].shape),
                    (None,) + lm.batch_axes(cfg, shape)[k], mesh, rules))
                for k in batch_abs}
            opt_abs = jax.eval_shape(lambda p: optim.adamw_init(p, ocfg),
                                     params_abs)
            opt_sh = sharding.opt_shardings(cfg, mesh, param_sh)
            jitted = jax.jit(step,
                             in_shardings=(param_sh, opt_sh, batch_sh),
                             out_shardings=(param_sh, opt_sh, rep),
                             donate_argnums=(0, 1))
            lowered = jitted.lower(params_abs, opt_abs, batch_abs)
            n_tokens = sh["batch"] * sh["seq"]
            extra = {"n_micro": nm, "moment_dtype": ocfg.moment_dtype,
                     "grad_dtype": str(grad_dtype.__name__)}
        elif kind == "prefill":
            def prefill_step(params, batch):
                logits, _, cache = lm.forward(params, batch, cfg, mesh,
                                              prefill=True)
                return logits, cache
            cache_sh, _ = None, None
            jitted = jax.jit(prefill_step,
                             in_shardings=(param_sh, batch_sh))
            lowered = jitted.lower(params_abs, batch_abs)
            n_tokens = sh["batch"] * sh["seq"]
            extra = {}
        else:  # decode / long-context decode
            s_max = sh["seq"]
            b = sh["batch"]
            cache_sh, cache_abs = sharding.cache_shardings(cfg, mesh, b, s_max)
            serve = engine.build_serve_step(cfg, mesh)

            def serve_step(params, cache, tokens, pos):
                return serve(params, cache, tokens, pos, None)

            jitted = jax.jit(
                serve_step,
                in_shardings=(param_sh, cache_sh, batch_sh["tokens"],
                              batch_sh["pos"]),
                out_shardings=(batch_sh["tokens"], rep, cache_sh),
                donate_argnums=(1,))
            lowered = jitted.lower(params_abs, cache_abs,
                                   batch_abs["tokens"], batch_abs["pos"])
            n_tokens = b
            extra = {"cache_seq_len": s_max}

        result = {
            "arch": arch, "shape": shape,
            "mesh": "2x16x16" if multi_pod else "16x16",
            "n_devices": n_dev, "kind": kind,
            "params_total": cfg.param_count(),
            "params_active": cfg.active_param_count(),
            **extra,
        }
        if not do_compile:
            result["lowered_only"] = True
            return result

        t0 = time.time()
        compiled = lowered.compile()
        result["compile_s"] = round(time.time() - t0, 1)

        mem = compiled.memory_analysis()
        for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                     "temp_size_in_bytes", "alias_size_in_bytes",
                     "generated_code_size_in_bytes"):
            v = getattr(mem, attr, None)
            if v is not None:
                result[attr] = int(v)
        result["bytes_per_device"] = (
            result.get("argument_size_in_bytes", 0)
            + result.get("temp_size_in_bytes", 0)
            - result.get("alias_size_in_bytes", 0))

        mf = analysis.model_flops(cfg.active_param_count(), n_tokens,
                                  "train" if kind == "train" else "serve",
                                  n_dev)
        roof = analysis.from_compiled(compiled, model_flops_per_device=mf)
        result["roofline_hlo"] = roof.as_dict()
        result["collectives_hlo"] = analysis.collective_bytes(compiled.as_text())

        # PRIMARY roofline: analytical model (cost_analysis counts while-loop
        # bodies once; see roofline/flops_model.py docstring).
        ocfg, grad_dtype = opt_profile(cfg)
        result["roofline"] = flops_model.analyze(
            cfg, shape, flops_model.mesh_for(multi_pod),
            n_micro=extra.get("n_micro", 1),
            grad_bytes=2 if grad_dtype == jnp.bfloat16 else 4,
            moment_bytes=2 if ocfg.moment_dtype == "bfloat16" else 4)
        return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None,
                    choices=list(lm.SHAPES) + [None])
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=RESULTS_DIR)
    ap.add_argument("--no-compile", action="store_true")
    args = ap.parse_args()

    os.makedirs(args.out, exist_ok=True)
    if args.all:
        todo = cells()
    else:
        assert args.arch and args.shape, "--arch + --shape (or --all)"
        if (args.arch, args.shape) in SHAPE_SKIPS:
            print(f"SKIP {args.arch} x {args.shape}: "
                  f"{SHAPE_SKIPS[(args.arch, args.shape)]}")
            return
        todo = [(args.arch, args.shape)]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    failures = []
    for arch, shape in todo:
        for multi in meshes:
            tag = f"{arch}__{shape}__{'multi' if multi else 'single'}"
            out_path = os.path.join(args.out, tag + ".json")
            if os.path.exists(out_path):
                print(f"[cached] {tag}")
                continue
            print(f"[lower+compile] {tag} ...", flush=True)
            try:
                t0 = time.time()
                res = lower_cell(arch, shape, multi,
                                 do_compile=not args.no_compile)
                res["wall_s"] = round(time.time() - t0, 1)
                with open(out_path, "w") as f:
                    json.dump(res, f, indent=1)
                r = res.get("roofline", {})
                print(f"  OK {res['wall_s']}s  dominant={r.get('dominant')} "
                      f"compute={r.get('compute_s', 0):.4f}s "
                      f"memory={r.get('memory_s', 0):.4f}s "
                      f"coll={r.get('collective_s', 0):.4f}s "
                      f"mem/dev={res.get('bytes_per_device', 0)/2**30:.2f}GiB",
                      flush=True)
            except Exception as e:
                failures.append((tag, repr(e)))
                print(f"  FAIL {tag}: {e}", flush=True)
                traceback.print_exc()
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for t, e in failures:
            print(" ", t, e)
        raise SystemExit(1)
    print("\nall cells green")


if __name__ == "__main__":
    # 512 host devices for the production meshes, before the backend starts
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    main()
