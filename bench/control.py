"""The correctness control: the plain reference in the program's place,
computed in bfloat16 (the precision below the configurations' float32).

    python3 bench/control.py --workload <cell> --seeds <n> [<n> ...]

For each seed it prints, as one JSON line, the numbers the cell's check
compares, with the control's answers in place of the program's, and
whether the cell's limits catch it.  ``--fault half_batch`` (training)
puts the reference run on the first half of each batch in the program's
place instead: the fault of a step that leaves half its batch out.  Serving
cells answer the run's whole request pool (as many requests as a run
compares); the training cell runs the checked steps on the run's own
first batches.  The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import os
import sys


def readings(cell, seed: int, fault: str | None = None) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bench.traffic import generator

    drv, cfg, tr = cell.driver, cell.cfg, cell.traffic
    if cell.spec["driver"] == "train":
        data = drv.make_data(cfg, tr, seed)
        batches = [jax.device_get(drv.feed(*data, i))
                   for i in range(tr["checked_steps"])]
        ref = drv.reference(cfg, seed, batches, tr["lr"])
        if fault == "half_batch":
            half = [(ev[:len(ev) // 2], lab[:len(lab) // 2], s)
                    for ev, lab, s in batches]
            ctl = drv.reference(cfg, seed, half, tr["lr"])
        else:
            ctl = drv.reference(cfg, seed, batches, tr["lr"],
                                dt=jnp.bfloat16)
        return drv.compare(ctl[0], ctl[1], ctl[2], *ref)
    pool = generator.pool(cfg, seed, tr["pool"])
    slots = cell.spec["engine"]["batch_slots"]
    rows = None if len(cfg["hidden_layers"]) == 1 else \
        np.arange(len(pool)) % slots
    which = np.arange(len(pool))
    ref = drv.reference(cfg, seed, pool, which, rows, slots)
    ctl = drv.reference(cfg, seed, pool, which, rows, slots,
                        dt=jnp.bfloat16)
    return drv.compare(ctl[0], ctl[1], *ref, cfg["n_steps"])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--fault", choices=("half_batch",), default=None)
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from bench import harness

    harness.setup_jax()
    cell = harness.Cell(args.workload)
    limits = cell.spec["limits"]
    for seed in args.seeds:
        got = readings(cell, seed, args.fault)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "fault": args.fault, "control": got,
                          "fails": any(v > limits[k]
                                       for k, v in got.items())}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
