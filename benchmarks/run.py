"""Benchmark harness: one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only fig8_accuracy]

Prints ``name,us_per_call,derived`` CSV (derived = compact JSON of the
reproduced numbers) and a human-readable block per benchmark.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

from repro import compile_cache

from benchmarks import (ablation_k_sweep, ablation_kwn_lm,
                        fig3d_weight_impl, fig5b_snl, fig6c_nlq, fig7_ima,
                        fig8_accuracy, fig9_energy, latency_kwn,
                        roofline_report, table1_comparison)

BENCHES = {
    "fig3d_weight_impl": fig3d_weight_impl,
    "fig7_ima": fig7_ima,
    "fig9_energy": fig9_energy,
    "latency_kwn": latency_kwn,
    "fig5b_snl": fig5b_snl,
    "fig6c_nlq": fig6c_nlq,
    "fig8_accuracy": fig8_accuracy,
    "table1_comparison": table1_comparison,
    "ablation_kwn_lm": ablation_kwn_lm,
    "ablation_k_sweep": ablation_k_sweep,
    "roofline_report": roofline_report,
}


def main() -> None:
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    args = ap.parse_args()

    names = [args.only] if args.only else list(BENCHES)
    results_dir = os.path.join(os.path.dirname(__file__), ".cache", "results")
    os.makedirs(results_dir, exist_ok=True)
    print("name,us_per_call,derived")
    failures = 0
    for name in names:
        mod = BENCHES[name]
        t0 = time.time()
        try:
            derived = mod.run()
            us = (time.time() - t0) * 1e6
            with open(os.path.join(results_dir, f"{name}.json"), "w") as f:
                json.dump(derived, f, indent=1, default=str)
            compact = json.dumps(derived, separators=(",", ":"),
                                 default=str)
            if len(compact) > 6000:
                compact = json.dumps(
                    {k: v for k, v in derived.items() if k != "rows"},
                    separators=(",", ":"), default=str)
            print(f"{name},{us:.0f},{compact}")
            print(f"--- {name} ---", file=sys.stderr)
            print(json.dumps(derived, indent=1, default=str)[:4000],
                  file=sys.stderr)
        except Exception as e:  # pragma: no cover
            failures += 1
            print(f"{name},FAILED,{e!r}")
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
