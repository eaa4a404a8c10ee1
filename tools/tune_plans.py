"""Regenerate the persistent tile-plan cache (PLAN_CACHE_fused_macro.json).

The CLI face of ``repro.tune``: autotune the canonical cells (or one cell
given explicitly) and persist the winners where
``kernels.fused_macro.plan_tiles`` will find them.  Porting to a new
backend is exactly one run of this on that backend — the cache is keyed on
device kind, so entries for other devices survive (``--no-merge`` to start
fresh).  See docs/TILE_PLANS.md for the cache contract.

Usage:
  PYTHONPATH=src python tools/tune_plans.py                    # make tune
  PYTHONPATH=src python tools/tune_plans.py --objective pj_per_sop
  PYTHONPATH=src python tools/tune_plans.py \\
      --cell 128x256x128x128x32 --density 0.05                 # one cell
  PYTHONPATH=src python tools/tune_plans.py --smoke \\
      --out /tmp/plan_cache.json                               # tune-smoke
"""

from __future__ import annotations

import argparse
import sys


def parse_cell(shape: str, density: float, mode: str, k: int):
    from repro.tune import autotune
    dims = [int(d) for d in shape.split("x")]
    if len(dims) != 5:
        raise SystemExit(f"--cell wants MxKxNCxNxT, got {shape!r}")
    return autotune.TuneCell(*dims, density=density, mode=mode, k=k)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--objective", default="ms",
                    choices=("ms", "pj_per_sop", "blend"),
                    help="what the winner minimizes: median latency, the "
                         "modeled kernel-energy proxy, or a geometric blend")
    ap.add_argument("--blend-weight", type=float, default=0.5,
                    help="blend objective: weight on pJ/SOP (0 = pure ms)")
    ap.add_argument("--iters", type=int, default=9,
                    help="timed calls per candidate (median taken)")
    ap.add_argument("--patience", type=int, default=None,
                    help="stop a cell after this many consecutive "
                         "non-improving candidates (default: measure all)")
    ap.add_argument("--cell", default=None, metavar="MxKxNCxNxT",
                    help="tune one launch shape instead of the canonical set")
    ap.add_argument("--density", type=float, default=0.05,
                    help="event density for --cell (default 0.05)")
    ap.add_argument("--mode", default="kwn", choices=("kwn",),
                    help="macro mode for --cell")
    ap.add_argument("--k", type=int, default=None,
                    help="KWN winner count for --cell (default: bench K)")
    ap.add_argument("--out", default=None,
                    help="cache file to write (default: repo-root "
                         "PLAN_CACHE_fused_macro.json, or "
                         "$REPRO_PLAN_CACHE_PATH)")
    ap.add_argument("--no-merge", action="store_true",
                    help="drop existing cache entries instead of merging")
    ap.add_argument("--smoke", action="store_true",
                    help="CI smoke: one tiny cell, 2 timed iters, then "
                         "assert the written cache round-trips to a lookup "
                         "hit that plan_tiles consumes")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="export a Perfetto trace of the tuning run (one "
                         "span per candidate measurement)")
    args = ap.parse_args(argv)

    if args.trace_out:
        from repro.obs import trace as obs_trace
        obs_trace.set_tracer(obs_trace.Tracer(enabled=True))

    from repro import compile_cache
    from repro.tune import autotune, cache

    compile_cache.enable()

    if args.smoke:
        cells = (autotune.TuneCell(16, 128, 128, 128, 4, 0.05),)
        entries, path = autotune.tune(
            cells, objective=args.objective, iters=2,
            path=args.out, merge=not args.no_merge)
        cache.clear_memo()
        cell = cells[0]
        hit = cache.lookup(cell.m, cell.k_dim, cell.nc, cell.n, cell.t,
                           mode=cell.mode, density=cell.density, path=path)
        assert hit is not None, "smoke: written cache did not round-trip"
        from repro.kernels import fused_macro
        import os
        os.environ[cache.ENV_PATH] = path
        cache.clear_memo()
        plan = fused_macro.plan_tiles(cell.m, cell.k_dim, cell.nc, cell.n,
                                      cell.t, mode=cell.mode)
        assert (plan.bm, plan.bk, plan.bn) == tuple(hit), \
            f"smoke: plan_tiles {plan[:3]} != cached {tuple(hit)}"
        print(f"tune-smoke OK: {len(entries)} entries, round-trip hit "
              f"{tuple(hit)} @ {path}")
        _export_trace(args.trace_out)
        return 0

    if args.cell:
        k = args.k if args.k is not None else autotune.K_WIN
        cells = (parse_cell(args.cell, args.density, args.mode, k),)
    else:
        cells = autotune.CANONICAL_CELLS
    autotune.tune(cells, objective=args.objective,
                  blend_weight=args.blend_weight, iters=args.iters,
                  patience=args.patience, path=args.out,
                  merge=not args.no_merge)
    _export_trace(args.trace_out)
    return 0


def _export_trace(path: str | None) -> None:
    if not path:
        return
    from repro.obs import trace as obs_trace
    n = obs_trace.get_tracer().export(path)
    print(f"wrote {n} spans to {path}")


if __name__ == "__main__":
    sys.exit(main())
