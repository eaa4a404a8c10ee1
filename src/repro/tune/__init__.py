"""Tile-plan autotuning for the fused macro pipeline.

Three pieces (see ``docs/TILE_PLANS.md`` for the full contract):

* ``repro.tune.cache``    — the persistent plan cache: a JSON file of tuned
  ``(bm, bk, bn)`` tile plans keyed on (op, shape, density bucket, mode,
  device kind), consumed transparently by ``kernels.fused_macro.plan_tiles``
  with the PR 4 heuristic as the fallback.
* ``repro.tune.measure``  — the tuner's timing loop (median-of-iters wall
  time) and the bursty event-stream generator its candidates run on.
* ``repro.tune.autotune`` — the search: enumerate candidate plans, prune
  with the roofline prior, measure each candidate's latency (and modeled
  kernel-energy pJ/SOP), pick the winner under the requested objective,
  and persist it.  The heuristic plan is always in the candidate set, so a
  tuned plan can only meet or beat it at selection time.

``tools/tune_plans.py`` (``make tune`` / ``make tune-smoke``) is the CLI
that regenerates the cache; re-measuring for a new backend is a cache
regeneration, not a code change.
"""

from repro.tune import autotune, cache, measure  # noqa: F401
