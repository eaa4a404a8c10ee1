"""Persistent tile-plan cache: tuned (bm, bk, bn) per (op, shape, density
bucket, mode, device kind).

The cache is a single JSON file (default ``PLAN_CACHE_fused_macro.json`` at
the repo root) written by the autotuner
(``repro.tune.autotune`` / ``tools/tune_plans.py``) and consumed by
``kernels.fused_macro.plan_tiles`` as a lookup fast path in front of the
lane-alignment heuristic.  Design constraints, in order:

1. **A cache problem can never become a launch problem.**  Every failure
   mode — missing file, corrupt JSON, wrong format/version, a stale entry
   whose blocks no longer satisfy the kernel's alignment rules — degrades
   to the heuristic with a one-shot ``RuntimeWarning``, never an exception.
   Cached plans only choose *which* bitwise-equivalent launch geometry
   runs, so a fallback is a perf event, not a correctness event.
2. **Lookups are deterministic.**  Two call sites that plan the same
   logical launch (e.g. ``plan_activity`` building the occupancy map and
   ``ops.fused_macro_seq`` planning the kernel) must resolve to the same
   plan, or the map's shape would not match the grid.  Given identical
   arguments the lookup is a pure function of the cache file contents.
3. **Cheap on the hot path.**  The file is parsed once and memoized;
   subsequent lookups are a dict probe.  The memo is invalidated on
   (path, mtime, size) change, so ``make tune`` takes effect without a
   process restart.

File schema (``CACHE_VERSION`` gates compatibility — bump it whenever key
semantics or required entry fields change, and old files degrade to the
heuristic instead of being misread)::

    {
      "format": "neudw-plan-cache",
      "version": 1,
      "entries": [
        {"op": "fused_macro_seq",
         "shape": "128x256x128x128x32",          # MxKxNCxNxT
         "mode": "kwn",
         "density_bucket": "d02-07",             # see density_bucket()
         "device_kind": "cpu:interpret",         # see device_kind()
         "plan": {"bm": 128, "bk": 256, "bn": 128},
         "objective": "ms",
         "score": 11.4,                          # objective value (winner)
         "median_ms": 11.4,
         "pj_per_sop": 1.38,                     # modeled kernel-energy proxy
         "heuristic_median_ms": 12.9,
         "speedup_vs_heuristic": 1.13,
         "n_candidates": 6},
        ...
      ]
    }

Key semantics (the contract ``docs/TILE_PLANS.md`` documents for the TPU
port): an entry matches a ``lookup()`` when op, shape string, mode, and
device kind are equal AND the density bucket matches.  With
``density=None`` (the serving paths — event density is data-dependent and
not worth a host sync) the bucket ``"any"`` is preferred; failing that the
group's entry with the highest ``speedup_vs_heuristic`` wins (that field is
normalized per-bucket, so it is the one cross-bucket-comparable score),
ties broken by bucket name for determinism.
"""

from __future__ import annotations

import json
import os
import tempfile
import warnings
from typing import NamedTuple

CACHE_FORMAT = "neudw-plan-cache"
CACHE_VERSION = 1
DEFAULT_BASENAME = "PLAN_CACHE_fused_macro.json"

ENV_PATH = "REPRO_PLAN_CACHE_PATH"     # override the cache file location
ENV_DISABLE = "REPRO_PLAN_CACHE"       # "0"/"off"/"" disables all lookups

# Density buckets: half-open [lo, hi) ranges over the measured |event| rate.
# Edges follow the bench's density sweep structure (1/5/10/25/50/100 %) so
# each benched point lands in its own bucket; names are stable cache-key
# material — changing them invalidates every persisted entry, so treat them
# like a schema field (bump CACHE_VERSION if they ever move).
DENSITY_EDGES = (0.02, 0.075, 0.15, 0.35, 0.75)
DENSITY_BUCKETS = ("d00-02", "d02-07", "d07-15", "d15-35", "d35-75",
                   "d75-100")
ANY_BUCKET = "any"

REQUIRED_ENTRY_FIELDS = ("op", "shape", "mode", "density_bucket",
                         "device_kind", "plan")


class PlanBlocks(NamedTuple):
    """The tuned block sizes an entry carries — what ``plan_tiles`` needs."""

    bm: int
    bk: int
    bn: int


def density_bucket(density: float) -> str:
    """Map a measured event density in [0, 1] to its stable bucket name."""
    d = float(density)
    if not 0.0 <= d <= 1.0:
        raise ValueError(f"density {d} not in [0, 1]")
    for edge, name in zip(DENSITY_EDGES, DENSITY_BUCKETS):
        if d < edge:
            return name
    return DENSITY_BUCKETS[-1]


def shape_key(m: int, k_dim: int, nc: int, n: int, t: int) -> str:
    """Canonical shape string: batch x K x NC x N x T (logical, unpadded)."""
    return f"{m}x{k_dim}x{nc}x{n}x{t}"


def device_kind() -> str:
    """Cache-key device identity: JAX device kind + the interpret mode.

    Interpret-mode timings (CPU validation) and real-hardware timings live
    in different performance regimes, so an interpret-tuned plan must never
    be served to a Mosaic-lowered launch: the suffix keeps them apart.  On
    real TPU this returns e.g. ``"tpu v5 lite"``; regenerating the cache
    there (``make tune``) is the whole TPU-port story for tile planning.
    """
    import jax
    kind = jax.devices()[0].device_kind.lower()
    from repro.kernels import ops as _ops   # late: avoid import cycles
    return f"{kind}:interpret" if _ops.interpret_mode() else kind


def default_path() -> str:
    """Resolve the cache file path: env override, else the repo root."""
    env = os.environ.get(ENV_PATH)
    if env:
        return env
    here = os.path.dirname(os.path.abspath(__file__))
    root = os.path.dirname(os.path.dirname(os.path.dirname(here)))  # src/../
    return os.path.join(root, DEFAULT_BASENAME)


def disabled() -> bool:
    return os.environ.get(ENV_DISABLE, "1").lower() in ("0", "off", "")


# --- load / memoize --------------------------------------------------------

# path -> (mtime_ns, size, index | None).  index maps the 5-tuple key to the
# raw entry dict; None records a load failure so we neither retry the parse
# nor re-warn on every plan_tiles call.
_MEMO: dict = {}
_WARNED: set = set()


def clear_memo() -> None:
    """Drop the in-process cache state (tests; after in-place rewrites)."""
    _MEMO.clear()
    _WARNED.clear()


def _warn_once(path: str, reason: str) -> None:
    if (path, reason) not in _WARNED:
        _WARNED.add((path, reason))
        warnings.warn(
            f"tile-plan cache {path!r}: {reason}; falling back to the "
            f"heuristic planner (regenerate with `make tune`)",
            RuntimeWarning, stacklevel=3)


def _entry_key(e: dict) -> tuple:
    return (e["op"], e["shape"], e["mode"], e["device_kind"],
            e["density_bucket"])


def _valid_blocks(plan: dict, nc: int | None = None) -> bool:
    """Alignment rules a cached plan must still satisfy (stale-entry gate).

    Mirrors what the heuristic guarantees: row tiles on the f32 sublane
    (bm % 8), K tiles on the lane (bk % 128) so activity blocks stay
    lane-aligned, and column tiles either lane-aligned or wide enough that
    ``plan_tiles`` collapses the layer to a single unpadded tile
    (``bn >= nc``).  An entry tuned under older rules that no longer
    passes degrades to the heuristic rather than reaching Pallas.
    """
    try:
        bm, bk, bn = int(plan["bm"]), int(plan["bk"]), int(plan["bn"])
    except (KeyError, TypeError, ValueError):
        return False
    if not (bm > 0 and bm % 8 == 0 and bk > 0 and bk % 128 == 0 and bn > 0):
        return False
    return bn % 128 == 0 or nc is None or bn >= nc


def _index(doc: object, path: str):
    if not isinstance(doc, dict) or doc.get("format") != CACHE_FORMAT:
        _warn_once(path, "not a plan-cache file")
        return None
    if doc.get("version") != CACHE_VERSION:
        _warn_once(path, f"version {doc.get('version')!r} != "
                         f"{CACHE_VERSION} (stale format)")
        return None
    entries = doc.get("entries")
    if not isinstance(entries, list):
        _warn_once(path, "entries: want a list")
        return None
    idx = {}
    for e in entries:
        if not isinstance(e, dict) or \
                any(f not in e for f in REQUIRED_ENTRY_FIELDS):
            _warn_once(path, "entry missing required fields (skipped)")
            continue
        idx[_entry_key(e)] = e
    return idx


def _load(path: str):
    """Memoized parse of the cache file; None on any failure (warned once)."""
    try:
        st = os.stat(path)
        stamp = (st.st_mtime_ns, st.st_size)
    except OSError:
        return None                      # no file: a silent miss, not an error
    memo = _MEMO.get(path)
    if memo is not None and memo[0] == stamp:
        return memo[1]
    try:
        with open(path) as f:
            doc = json.load(f)
        idx = _index(doc, path)
    except (OSError, ValueError) as e:
        _warn_once(path, f"unreadable ({e.__class__.__name__})")
        idx = None
    _MEMO[path] = (stamp, idx)
    return idx


# --- lookup ----------------------------------------------------------------

def lookup(m: int, k_dim: int, nc: int, n: int, t: int, *,
           mode: str = "kwn", density: float | None = None,
           op: str = "fused_macro_seq",
           path: str | None = None) -> PlanBlocks | None:
    """Tuned blocks for a launch, or None (-> heuristic).  Never raises."""
    try:
        if disabled():
            return None
        path = path or default_path()
        idx = _load(path)
        if not idx:
            return None
        group = (op, shape_key(m, k_dim, nc, n, t), mode, device_kind())
        entry = None
        if density is not None:
            entry = idx.get(group + (density_bucket(density),)) \
                or idx.get(group + (ANY_BUCKET,))
        else:
            entry = idx.get(group + (ANY_BUCKET,))
            if entry is None:
                in_group = [e for k, e in sorted(idx.items())
                            if k[:4] == group]
                if in_group:
                    entry = max(
                        in_group,
                        key=lambda e: (float(e.get("speedup_vs_heuristic",
                                                   0.0)),
                                       e["density_bucket"]))
        if entry is None:
            return None
        if not _valid_blocks(entry["plan"], nc=nc):
            _warn_once(path, f"stale plan for {group} (alignment rules)")
            return None
        p = entry["plan"]
        return PlanBlocks(int(p["bm"]), int(p["bk"]), int(p["bn"]))
    except Exception as e:   # noqa: BLE001 — the cache must never break planning
        _warn_once(path or "<unresolved>",
                   f"lookup failed ({e.__class__.__name__}: {e})")
        return None


# --- write -----------------------------------------------------------------

def save_entries(entries: list[dict], path: str | None = None,
                 merge: bool = True) -> str:
    """Persist tuner results; merge-by-key with any existing file by default.

    Each entry must carry ``REQUIRED_ENTRY_FIELDS``; ``merge=False`` starts
    the file fresh (drops every previously persisted plan).  Returns the
    path written.  The in-process memo is invalidated so the next
    ``lookup`` sees the new contents.

    The write is **atomic**: the document is serialized to a temp file in
    the same directory, fsynced, and ``os.replace``d over the target.  A
    crash (or a concurrent reader) mid-write can therefore never leave a
    truncated/corrupt cache on disk — readers see either the old complete
    file or the new complete file.  (A corrupt cache would only cost the
    heuristic fallback, but a half-written file on every ``make tune``
    interrupt is still a self-inflicted wound worth designing out.)
    """
    path = path or default_path()
    for e in entries:
        missing = [f for f in REQUIRED_ENTRY_FIELDS if f not in e]
        if missing:
            raise ValueError(f"entry missing fields {missing}: {e}")
        nc = int(str(e["shape"]).split("x")[2])
        if not _valid_blocks(e["plan"], nc=nc):
            raise ValueError(f"entry plan violates alignment rules: {e}")
    merged: dict = {}
    if merge:
        existing = _load(path)
        if existing:
            merged.update(existing)
    for e in entries:
        merged[_entry_key(e)] = e
    doc = {"format": CACHE_FORMAT, "version": CACHE_VERSION,
           "entries": [merged[k] for k in sorted(merged)]}
    # same-directory temp file: os.replace must not cross filesystems
    fd, tmp = tempfile.mkstemp(
        prefix=os.path.basename(path) + ".", suffix=".tmp",
        dir=os.path.dirname(os.path.abspath(path)) or ".")
    try:
        with os.fdopen(fd, "w") as f:
            json.dump(doc, f, indent=1)
            f.write("\n")
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
    except BaseException:
        # never leave a stray temp file next to the cache
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise
    _MEMO.pop(path, None)
    return path
