"""Pallas TPU kernel: the fused macro step (MAC -> IMA -> mode head -> LIF),
tiled over a virtual macro grid and batched over time.

The paper's efficiency story (0.8 pJ/SOP, -30 % IMA latency, 10x LIF latency)
comes from never leaving the macro: the analog MAC result stays on the RBLs,
the IMA converts it in place, the KWN controller gates which LIF updates run.
A chain of separate MAC, ramp, top-K and LIF kernels would round-trip every
intermediate through HBM — the exact anti-pattern event-driven CIM
accelerators exist to avoid.  This kernel is the TPU-native equivalent of
staying inside the macro: one grid step per
(row-tile, time-step, col-tile, K-tile) performs

  1. twin-cell ternary MAC (int8 MSB/LSB planes decoded in VMEM, MXU f32
     accumulation across the K grid axis into the column tile's slice of the
     full-width ``mac`` accumulator — digital partial-sum accumulation, the
     way the silicon adds converted row-tile partials across macro
     instances);
  2. on the last (col-tile, K-tile) of a time step, IMA ramp conversion of
     the whole accumulator against the in-VMEM boundary set (linear / NLQ /
     NL-activation — the codebook is data, so one kernel serves all three
     ramp programs), optionally injecting the Fig. 7 silicon error model
     (INL + comparator offset + Gaussian thermal noise, in code LSBs)
     with per-step per-column draws generated *in kernel* by the
     counter-based Threefry PRNG (``core.ctrprng``) — no pre-drawn noise
     tensor, no composed-path fallback, and, because every draw is a pure
     function of ``(seed, step, absolute row, logical column)``, the noisy
     output is launch-shape-invariant and bitwise-equal to the
     ``kernels/ref.py`` oracle;
  3. the mode head: KWN descending-ramp top-K with early-stop step counts
     (``kwn`` mode) or the per-branch NL-activation + soma combine (``nld``
     mode);
  4. the digital LIF membrane update (leak/integrate/SNL/compare/reset),
     with the membrane carried in VMEM across the whole T axis,

all on VREG/VMEM-resident state.  Only the per-step (spikes, mask,
adc_steps) — and, when requested, the raw MAC for telemetry — touch HBM;
the LIF membrane is written back once per row tile, after the last time
step.

Activity-gated sparse execution
-------------------------------
The silicon's 0.8 pJ/SOP comes from *not* spending energy on inactive rows:
event tensors are a few percent dense, and the macro only charges RBLs for
rows that fire.  The kernel reproduces that with an ``activity`` occupancy
map: a cheap host-side pass over the ternary ``(T, M, K)`` input marks each
``(step, row-tile, K-tile)`` block that contains at least one event, and the
map rides into the kernel as a scalar-prefetch operand (SMEM-resident, read
before the block's compute issues).  An all-zero activation block can only
contribute an exactly-zero partial sum, so the int8 plane decode + MXU
contraction for it are ``pl.when``-skipped without changing a single output
bit — clean *and* noisy outputs stay equal to the ``kernels/ref.py``
oracles, because the Fig. 7 noise draws key on ``(seed, step, row, col)``
and are consumed at the ramp stage, which still runs every step.  The gated
path additionally turns the KWN early stop from telemetry into compute: the
descending one-hot sweep starts at the highest code actually present in the
tile and exits as soon as every row has its K winners (a bounded
``while_loop`` instead of the fixed 2^code_bits ``fori_loop``; skipped
levels have no crossings or no admission slots left, so mask/steps are
bit-identical).  Raw-MAC telemetry is opt-out (``mac_telemetry=False``
keeps the accumulator in VMEM scratch and never writes the ``(T, M, NC)``
stack to HBM — the serving default).

Kernel layout / VMEM budget
---------------------------
Grid is ``(M/bm, T, NC/bn, K/bk)`` with K innermost, then column tiles, then
time.  Per grid step the streamed working set is the ``bm x bk`` int8
activation block and two ``bk x bn`` int8 weight planes (the Pallas pipeline
double-buffers these across grid steps, so weight-plane DMA overlaps the MXU
contraction); resident across a time step are the full-width ``(bm, NC)``
f32 MAC accumulator (an HBM-backed output block when ``mac_telemetry`` is
on, a VMEM scratch buffer when off — same footprint either way), the
2^code_bits-entry codebook, and the ``(bm, N)`` f32 LIF membrane (resident
across the whole T axis).  The activity map adds ``T * (M/bm) * (K/bk)``
int32 words of SMEM (scalar prefetch) — a few KB even for long streams,
never a VMEM tenant.  At the defaults (bm=128, bk=256, bn=128) a
single-macro layer (NC=N=128) costs

    x        128*256      int8   =  32 KB   (x2 double buffered)
    planes 2*256*128      int8   =  64 KB   (x2 double buffered)
    mac      128*128      f32    =  64 KB   (output block or scratch)
    v + noise + outputs ~6*128*128 f32 ~ 384 KB

~0.7 MB, and each additional column tile adds only 64 KB of accumulator +
the same streamed 64 KB plane window.  The head works on 2-D ``(bm, NC)``
planes only — one compare per ramp boundary, one select per LUT code, the
KWN priority count as a 0/1 triangular contraction — so its temporaries
grow with ``bm * NC`` (``TilePlan.vmem_bytes`` counts them).  On a TPU
every launch compiles with ``vmem_limit_bytes()`` and ``plan_tiles``
shrinks the row tile until the estimate fits.  Folding T into the grid
adds *no* VMEM (one time step is resident at a time); it removes the
per-step kernel launch + weight-plane re-staging that dominates short-step
event-stream serving.

Tile-shape / activity-granularity heuristic
-------------------------------------------
Occupancy is tracked per ``(step, row-tile, K-tile)`` block, so the tile
plan *is* the gating granularity: a block is skippable only if every one of
its ``bm x bk`` entries is zero.  ``plan_tiles`` therefore prefers the
smallest lane-aligned K tile that covers the layer (``bk =
ceil_to_128(K)`` when K < 256, the physical macro row count otherwise):
padding K up to an oversized tile would dilute real events across dead
zero columns and make blocks look occupied-by-construction, while an
aligned tile keeps every activity block dense with real rows.  Row tiles
follow the batch (``bm = min(128, ceil_to_8(M))``) so a batch the serving
engine packs by measured event density maps quiet requests onto quiet —
skippable — row tiles.

When to prefer the fused step
-----------------------------
Inference hot loops (the SNN scan body, event-stream serving): everything the
composed path writes to HBM between stages is dead weight there.  Prefer the
composed path when you need the intermediates themselves (calibration sweeps,
the Fig. 6/7 codebook studies) or gradients (training uses the STE jnp path,
not these kernels).  ``kernels/ref.py::fused_macro_step_ref`` (one step) and
``fused_macro_seq_ref`` (time-major) are the oracles: bitwise-identical at
f32 accumulation because every MAC partial is a small integer (exactly
representable, associativity-free — so row/col tiling cannot change the sum)
and the head is compare/select/LUT arithmetic mirrored
operation-for-operation.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import ctrprng

DEFAULT_BM = 128
DEFAULT_BK = 256  # the macro's row count: one K-tile == one physical macro
DEFAULT_BN = 128  # the macro's column count: one col-tile == one macro width

_LIF_STATICS = ("beta", "v_th1", "v_th2", "v_reset", "v_lim")

# The ramp boundaries and LUT levels: whole 1-D arrays in SMEM, read as
# scalars by the per-code loops of the head.
_SMEM_SPEC = pl.BlockSpec(memory_space=pltpu.SMEM)

# Live (bm, NC) 32-bit temporaries of the fused head (``TilePlan.vmem_bytes``).
HEAD_TEMPORARIES = 16


def vmem_limit_bytes() -> int:
    """Scoped-VMEM limit the fused kernels compile with on a TPU.

    Three quarters of one TensorCore's VMEM on the present chip, as
    ``pltpu.get_tpu_info`` reports it; that call raises for a TPU kind it
    cannot size, and there is no default.  The last quarter stays free for
    Mosaic's internal scratch.  Left unset, Mosaic would hold every kernel
    to its 16 MiB default scope.
    """
    return pltpu.get_tpu_info().vmem_capacity_bytes * 3 // 4


def compiler_params(interpret: bool):
    """Mosaic compiler parameters (None in the interpreter)."""
    if interpret:
        return None
    return pltpu.CompilerParams(vmem_limit_bytes=vmem_limit_bytes())


def plan_vmem_budget() -> int | None:
    """VMEM bytes a plan may take: the limit on a TPU, none when the kernels
    run in the interpreter (the lazy import keeps ``ops`` -> this module
    acyclic at import time)."""
    from repro.kernels import ops
    return None if ops.interpret_mode() else vmem_limit_bytes()


# ---------------------------------------------------------------------------
# Tile planning
# ---------------------------------------------------------------------------

class TilePlan(NamedTuple):
    """Padded geometry + grid for one fused kernel launch.

    n_pad/nc_pad are the padded per-neuron / per-column widths (nc_pad ==
    n_branches * n_pad, branch-major).  ``n_valid`` is the number of real
    columns the KWN sweep may admit (padded columns are excluded from the
    ramp inside the kernel).  ``vmem_bytes`` estimates the scoped VMEM one
    launch needs on a TPU (see the property); the activity map is
    SMEM-resident (``activity_bytes``) and not counted.
    ``activity_shape`` is the occupancy-map geometry the gated kernel
    prefetches: one int32 word per (step, row-tile, K-tile) block.
    """

    bm: int
    bk: int
    bn: int
    m_pad: int
    k_pad: int
    n_pad: int
    nc_pad: int
    n_valid: int
    grid: tuple[int, int, int, int]   # (M/bm, T, NC/bn, K/bk)

    @property
    def vmem_bytes(self) -> int:
        """Estimated scoped VMEM of one launch, in bytes, on a TPU.

        Every block is counted at its native tile padding (f32 rows to 8,
        int8 rows to 32, lanes to 128) and double-buffered by the Pallas
        pipeline: the streamed int8 x block and weight planes, the (bm, N)
        row blocks (membrane in/out, spikes, mask, SNL noise, training
        trace, step counts, row_ctl) and the full-width (bm, NC) MAC
        accumulator.  The head adds ``HEAD_TEMPORARIES`` live (bm, NC)
        32-bit temporaries (ramp codes, counter-PRNG words, KWN sweep
        state, LUT value, drive).  Calibrated against Mosaic's own
        accounting; ``plan_tiles`` keeps it under ``vmem_limit_bytes()``.
        """
        rows = _ceil_mult(self.bm, 8)
        lanes = lambda w: _ceil_mult(w, 128)
        streamed = (_ceil_mult(self.bm, 32) * lanes(self.bk)
                    + 2 * _ceil_mult(self.bk, 32) * lanes(self.bn))
        row_blocks = 4 * rows * (6 * lanes(self.n_pad) + 2 * 128)
        mac = 4 * rows * lanes(self.nc_pad)
        head = HEAD_TEMPORARIES * 4 * rows * lanes(self.nc_pad)
        return 2 * (streamed + row_blocks + mac) + head

    @property
    def activity_shape(self) -> tuple[int, int, int]:
        """(T, row-tiles, K-tiles): one occupancy word per gateable block."""
        return (self.grid[1], self.grid[0], self.grid[3])

    @property
    def activity_bytes(self) -> int:
        """SMEM bytes the scalar-prefetched occupancy map occupies."""
        t, n_i, n_k = self.activity_shape
        return 4 * t * n_i * n_k


def _ceil_mult(n: int, m: int) -> int:
    return max(m, ((n + m - 1) // m) * m)


def cached_plan_blocks(m: int, k_dim: int, nc: int, n: int, t: int, *,
                       mode: str, density: float | None = None):
    """Tuned (bm, bk, bn) from the persistent plan cache, or None.

    The lazy import keeps the planner importable (and the heuristic fully
    functional) even if the tune package is broken or absent; any cache
    failure is the cache's to warn about and degrades to None here.
    """
    try:
        from repro.tune import cache as _plan_cache
        return _plan_cache.lookup(m, k_dim, nc, n, t, mode=mode,
                                  density=density)
    except Exception:   # noqa: BLE001 — the cache must never break planning
        return None


def plan_tiles(m: int, k_dim: int, nc: int, n: int, t: int = 1, *,
               mode: str = "kwn", n_branches: int = 1,
               bm: int | None = None, bk: int | None = None,
               bn: int | None = None, density: float | None = None,
               use_cache: bool = True) -> TilePlan:
    """Pick (bm, bk, bn) and padded shapes for a fused launch.

    The single tile-planning entry point: ``ops.fused_macro_seq`` (and its
    VJP), ``core.macro.plan_fused_tiles`` / ``plan_activity`` /
    ``plan_fused_stack``, and the autotuner all plan through here, so the
    occupancy map a host-side planner builds always matches the grid the
    kernel launches with.  See ``docs/TILE_PLANS.md`` for the full field
    and cache contract.

    Parameters
    ----------
    m, k_dim, nc, n : logical launch geometry — flattened batch rows, the
        contraction (input-event) width, total weight columns, and
        per-neuron output width.  ``nc == n`` in KWN mode; in NLD mode
        ``nc == n_branches * n`` (branch-major column planes).
    t : number of time steps folded into the kernel grid (1 = single step).
    mode : ``"kwn"`` or ``"nld"`` — NLD changes the column-padding rule.
    n_branches : dendritic branches per neuron (NLD only).
    bm, bk, bn : explicit block-size overrides.  Any non-None override
        pins that axis and **disables the cache lookup entirely** — an
        explicit plan is an explicit plan (the bench and tuner rely on
        this to measure exactly the plan they asked for).
    density : optional measured event density in [0, 1]; refines the cache
        key to a density bucket.  Callers that share a plan with a
        separately built activity map (the model/serving paths) must pass
        the same value at both sites — they pass None — because the cache
        entry chosen may differ per bucket.
    use_cache : False bypasses the persistent cache (tuner internals,
        A/B tests).  Cache misses and every cache failure mode fall
        through to the heuristic below; a cached plan can only change
        speed, never output bits (kernel parity contract).

    Returns a ``TilePlan``: the chosen blocks, padded shapes, ``n_valid``
    and the launch ``grid`` (see the class docstring).

    Heuristic (the fallback, and the baseline every tuned plan is gated
    against) — column tiling rules: a layer that fits one macro width
    (nc <= bn) runs a single unpadded column tile; wider layers tile at
    ``bn`` (default 128, the physical macro column count) with zero-padded
    tail columns.  In ``nld`` mode padding must not straddle the
    branch-major column layout, so the per-branch width n is padded to the
    smallest n_pad with ``n_branches * n_pad % bn == 0`` and the planes
    are re-packed per branch.  Zero weight columns are MAC-neutral; the
    KWN sweep additionally masks padded columns out of the ramp
    (``n_valid``) so they can never steal winner slots.

    K tiling aligns with the activity-map granularity (see the module
    docstring): layers narrower than the 256-row physical macro take the
    smallest lane-aligned tile that covers them (``ceil_to_128(K)``), so an
    occupancy block is never padded-zero by construction and per-block
    gating stays meaningful; layers at or past 256 rows tile at the
    physical macro row count.

    On a TPU every returned plan fits the kernels' VMEM limit
    (``TilePlan.vmem_bytes`` <= ``vmem_limit_bytes()``): the row tile, then
    the column tile, is halved until it does — overrides and cached plans
    included.  In the interpreter there is no VMEM, and no bound.
    """
    if use_cache and bm is None and bk is None and bn is None:
        cached = cached_plan_blocks(m, k_dim, nc, n, t, mode=mode,
                                    density=density)
        if cached is not None:
            bm, bk, bn = cached
    bk_ = bk or (DEFAULT_BK if k_dim >= DEFAULT_BK else _ceil_mult(k_dim, 128))

    def make(bm_: int, bn_req: int) -> TilePlan:
        if nc <= bn_req:
            bn_ = nc
            n_pad, nc_pad = n, nc
        elif mode == "nld" and n_branches > 1:
            bn_ = bn_req
            step = bn_ // math.gcd(bn_, n_branches)
            n_pad = _ceil_mult(n, step)
            nc_pad = n_branches * n_pad
        else:
            bn_ = bn_req
            nc_pad = _ceil_mult(nc, bn_)
            n_pad = nc_pad
        m_pad = _ceil_mult(m, bm_)
        k_pad = _ceil_mult(k_dim, bk_)
        return TilePlan(bm=bm_, bk=bk_, bn=bn_, m_pad=m_pad, k_pad=k_pad,
                        n_pad=n_pad, nc_pad=nc_pad, n_valid=nc,
                        grid=(m_pad // bm_, t, nc_pad // bn_, k_pad // bk_))

    plan = make(bm or min(DEFAULT_BM, _ceil_mult(m, 8)), bn or DEFAULT_BN)
    budget = plan_vmem_budget()
    # On a TPU, halve the row tile (then the column tile) until the launch
    # fits the chip's VMEM limit; a layer too wide for one 8-row launch is
    # an error here, not a Mosaic refusal later.
    while budget is not None and plan.vmem_bytes > budget:
        if plan.bm > 8:
            plan = make(_ceil_mult(plan.bm // 2, 8), plan.bn)
        elif plan.bn > 128 and plan.bn % 256 == 0:
            plan = make(plan.bm, plan.bn // 2)
        else:
            raise ValueError(
                f"fused launch {m}x{k_dim}x{nc} needs ~{plan.vmem_bytes} "
                f"bytes of VMEM at 8 rows, over the {budget}-byte limit; "
                f"split the layer")
    return plan


# ---------------------------------------------------------------------------
# Kernel bodies
# ---------------------------------------------------------------------------

def _accumulate_mac_tile(x_ref, msb_ref, lsb_ref, mac_ref, *, ratio: float,
                         bn: int, occ=None):
    """Twin-cell decode + MXU MAC into this column tile's accumulator slice.

    With ``occ`` (the scalar-prefetched occupancy word for this
    (step, row-tile, K-tile) block), the decode + contraction are
    ``pl.when``-skipped for all-zero activation blocks: a skipped block's
    partial sum is exactly zero, so the (always-run) zero-init at the first
    K tile plus occupied-block adds reproduce the dense accumulator value
    bit-for-bit (every partial is a small exact integer; f32 addition of
    exact zeros is the identity).
    """
    j, kk = pl.program_id(2), pl.program_id(3)
    # one column tile spans the whole accumulator: a static full slice
    # (Mosaic needs no lane-offset proof for it); wider layers slice at a
    # multiple of bn, which plan_tiles keeps lane-aligned
    single = mac_ref.shape[-1] == bn
    col = (slice(None), slice(None),
           slice(None) if single else pl.ds(pl.multiple_of(j * bn, bn), bn))

    def _decoded_part():
        x = x_ref[0].astype(jnp.float32)
        w = ratio * msb_ref[...].astype(jnp.float32) \
            + lsb_ref[...].astype(jnp.float32)
        return jax.lax.dot_general(
            x, w, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)[None]

    if occ is None:                       # dense path: decode + MAC always
        part = _decoded_part()

        @pl.when(kk == 0)
        def _init():
            mac_ref[col] = jnp.zeros_like(part) + part

        @pl.when(kk > 0)
        def _accumulate():
            mac_ref[col] = mac_ref[col] + part
        return

    @pl.when(kk == 0)
    def _zero():
        mac_ref[col] = jnp.zeros((1, x_ref.shape[1], bn), jnp.float32)

    @pl.when(occ > 0)
    def _mac():
        mac_ref[col] = mac_ref[col] + _decoded_part()


def _ramp_codes(x: jax.Array, bounds_ref) -> jax.Array:
    """Ramp conversion: code = #boundaries crossed (ripple-counter value).

    One (rows, cols) compare-and-count per boundary, each boundary a scalar
    read from SMEM: the count is the same integer a (rows, cols, n_codes)
    compare-and-sum gives, without a lane-padded 3-D temporary in VMEM.
    """
    codes = jnp.zeros(x.shape, jnp.int32)
    for b in range(bounds_ref.shape[0]):
        codes = codes + (x > bounds_ref[b]).astype(jnp.int32)
    return codes


def _lut_reconstruct(codes: jax.Array, levels_ref) -> jax.Array:
    """LUT map-back: ``levels[code]`` by one select per code (no gather).

    Codes outside [0, n_codes) (masked padded columns) map to 0.
    """
    out = jnp.zeros(codes.shape, jnp.float32)
    for c in range(levels_ref.shape[0]):
        out = jnp.where(codes == c, levels_ref[c], out)
    return out


def _lane_prefix_count(flags: jax.Array) -> jax.Array:
    """Inclusive prefix count of 0/1 ``flags`` (rows, n) along the lanes.

    Each 128-lane chunk is one contraction with an upper-triangular 0/1
    matrix on the MXU, plus the running total of the chunks before it.
    Every operand and partial sum is a small integer, exact in f32 (and in
    bf16 operands), so the count equals ``cumsum`` bitwise; Mosaic has no
    lane ``cumsum``.
    """
    n = flags.shape[-1]
    w0 = min(n, 128)
    upper = (jax.lax.broadcasted_iota(jnp.int32, (w0, w0), 0)
             <= jax.lax.broadcasted_iota(jnp.int32, (w0, w0), 1)
             ).astype(jnp.float32)
    f = flags.astype(jnp.float32)
    total = jnp.zeros((f.shape[0], 1), jnp.float32)
    parts = []
    for c0 in range(0, n, w0):
        w = min(w0, n - c0)
        chunk = f[:, c0:c0 + w]
        parts.append(jax.lax.dot_general(
            chunk, upper[:w, :w], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) + total)
        total = total + jnp.sum(chunk, axis=-1, keepdims=True)
    out = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=-1)
    return out.astype(jnp.int32)


def _kwn_sweep(codes: jax.Array, k: int, n_codes: int, bounded: bool = False):
    """Descending-ramp priority-encoded top-K (``ref.kwn_topk_ref``).

    ``bounded=True`` is the activity-gated variant: the sweep starts at the
    highest code actually present in the tile and exits once every row has
    its K winners — a data-bounded ``while_loop`` instead of the fixed
    2^code_bits ``fori_loop``.  Skipped head levels have no crossings and
    skipped tail levels have no admission slots left (``n_found == k``
    blocks every admit), so mask and early-stop step counts are
    bit-identical to the full sweep; only the work changes.
    """
    bm, n = codes.shape

    def descend(level, carry):
        n_found, mask, steps = carry
        crossing = (codes == level) & (mask == 0)
        order = _lane_prefix_count(crossing)
        admit = crossing & ((n_found + order) <= k)       # priority encoder
        mask = mask + admit.astype(jnp.int32)
        n_found = n_found + jnp.sum(admit.astype(jnp.int32), axis=-1,
                                    keepdims=True)
        done_now = (n_found >= k) & (steps < 0)
        steps = jnp.where(done_now, n_codes - 1 - level, steps)
        return n_found, mask, steps

    init = (jnp.zeros((bm, 1), jnp.int32), jnp.zeros((bm, n), jnp.int32),
            jnp.full((bm, 1), -1, jnp.int32))
    if bounded:
        def body(carry):
            level, n_found, mask, steps = carry
            n_found, mask, steps = descend(level, (n_found, mask, steps))
            return level - 1, n_found, mask, steps

        def cond_fn(carry):
            level, n_found = carry[0], carry[1]
            return (level >= 0) & jnp.any(n_found < k)

        top = jnp.max(codes)              # occupied code range upper bound
        _, _, mask, steps = jax.lax.while_loop(cond_fn, body, (top, *init))
    else:
        _, mask, steps = jax.lax.fori_loop(
            0, n_codes,
            lambda step, carry: descend(n_codes - 1 - step, carry), init)
    return mask.astype(jnp.float32), jnp.where(steps < 0, n_codes - 1, steps)


def _lif_update(v, drive, mask, noise, *, beta, v_th1, v_th2, v_reset, v_lim,
                use_snl):
    """Eq. (1): winners leak+integrate, non-winners hold; SNL kick; compare.

    Returns (v_out, spike, v_clip): ``v_clip`` is the post-saturation,
    pre-reset membrane — the value the spike comparator actually reads.
    Training saves it per step (``train_trace``) because the SuperSpike
    surrogate and the saturation gradient gate are both functions of it.
    """
    v_new = jnp.where(mask > 0, beta * v + drive, v)
    if use_snl:
        snl = (v_new > v_th2) & (v_new < v_th1)
        v_new = jnp.where(snl, v_new + noise, v_new)
    v_new = jnp.clip(v_new, -v_lim, v_lim)      # 12-bit register saturation
    spike = (v_new >= v_th1).astype(jnp.float32)
    return jnp.where(spike > 0, v_reset, v_new), spike, v_new


def _mask_padded_columns(codes: jax.Array, n_valid: int) -> jax.Array:
    """Padded columns never cross the ramp (code -1 < every sweep level)."""
    if n_valid >= codes.shape[-1]:
        return codes
    col = jax.lax.broadcasted_iota(jnp.int32, codes.shape, 1)
    return jnp.where(col < n_valid, codes, -1)


def _noise_ids(shape, row0, per_branch: int, logical_n: int, rows=None):
    """Global (row, logical-column) counter words for the noise streams.

    Rows are absolute batch rows (``row0`` = row-tile offset, computed from
    ``program_id`` at kernel top level — interpret mode cannot lower
    ``program_id`` inside a ``pl.when`` sub-jaxpr).  Columns are *logical*:
    a padded branch-major layout stores branch j of column p at
    ``j * per_branch + p``, but the counter uses ``j * logical_n + p`` so
    the draw a real column receives is invariant to the tile plan's padding
    (``per_branch`` changes with (bn, J); ``logical_n`` never does).

    ``rows`` overrides the absolute-row basis with an explicit (bm, 1)
    per-row id vector (``row_ctl`` path: each batch row replays the stream
    of an arbitrary virtual row, e.g. row 0 of a batch-1 launch).
    """
    if rows is None:
        rows = jax.lax.broadcasted_iota(jnp.int32, shape, 0) + row0
    col = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    lcol = (col // per_branch) * logical_n + col % per_branch
    return rows, lcol


def _ima_noisy_codes(codes, x, seed, step, *, row0, per_branch, logical_n,
                     ima_noise, n_codes, rows=None):
    """Counter-PRNG Fig. 7 error injection on the full-width code plane."""
    rows, cols = _noise_ids(codes.shape, row0, per_branch, logical_n,
                            rows=rows)
    return ctrprng.noisy_ima_codes(codes, x, rows, cols, seed, step,
                                   ima_noise, n_codes)


def _lif_noise(noise_ref, rest_shape, seed, step, *, row0, logical_n,
               snl_amp, use_snl, rows=None):
    """SNL noise operand: streamed input (clean path, PRBS parity) or
    in-kernel counter sign noise (noisy path — nothing pre-drawn, nothing
    staged through HBM)."""
    if noise_ref is not None:
        return noise_ref[0]
    if not use_snl or snl_amp == 0.0:
        return jnp.zeros(rest_shape, jnp.float32)
    rows, cols = _noise_ids(rest_shape, row0, rest_shape[-1], logical_n,
                            rows=rows)
    sign = ctrprng.counter_sign(seed, step, rows, cols, ctrprng.TAG_SNL)
    return jnp.float32(snl_amp) * sign


def _row_stream_ids(ctl_ref, rc_ref, t):
    """Per-launch (scalar ctl) or per-row (row_ctl) noise-stream words.

    With ``row_ctl`` present each batch row carries its own
    ``(seed, step_offset, row_id)`` — seed/step come back as (bm, 1)
    columns that broadcast through the counter PRNG exactly like the
    scalar path, and ``row_id`` overrides the absolute-row coordinate so
    a slot can reproduce the stream of a batch-1 run bit-for-bit.
    """
    if rc_ref is None:
        return ctl_ref[0, 0], ctl_ref[0, 1] + t, None
    rc = rc_ref[...]
    return rc[:, 0:1], rc[:, 1:2] + t, rc[:, 2:3]


def _unpack_refs(refs, *, gated, has_noise_ref, has_w_dend, mac_out,
                 train_trace=False, has_row_ctl=False):
    """Positional-ref unpacking shared by both mode kernels.

    Ref order is (scalar prefetch), inputs, outputs, scratch:
    ``[occ?] x msb lsb bounds levels scale ctl [row_ctl?] [w_dend?] v0
    [noise?] [mac(out)?] v spike mask steps [vtrace?] [mac(scratch)?]``.
    """
    refs = list(refs)
    occ_ref = refs.pop(0) if gated else None
    names = ["x", "msb", "lsb", "bounds", "levels", "scale", "ctl"]
    if has_row_ctl:
        names.append("row_ctl")
    if has_w_dend:
        names.append("w_dend")
    names.append("v0")
    ins = dict(zip(names, refs[:len(names)]))
    rest = refs[len(names):]
    noise_ref = rest.pop(0) if has_noise_ref else None
    mac_ref = rest.pop(0) if mac_out else None
    v_ref, spike_ref, mask_ref, steps_ref = rest[:4]
    rest = rest[4:]
    vtrace_ref = rest.pop(0) if train_trace else None
    if not mac_out:
        mac_ref = rest.pop(0)                    # VMEM scratch accumulator
    return (occ_ref, ins, noise_ref, mac_ref, v_ref, spike_ref, mask_ref,
            steps_ref, vtrace_ref)


def _block_occupancy(occ_ref, *, i, t, kk, n_i, n_k):
    """This grid step's scalar-prefetched occupancy word (or None)."""
    if occ_ref is None:
        return None
    return occ_ref[(t * n_i + i) * n_k + kk]


def _seq_kwn_kernel(*refs, ratio, bm, bn, n_i, n_j, n_k, n_valid, k,
                    n_codes, beta, v_th1, v_th2, v_reset, v_lim, use_snl,
                    drive_gain, ima_noise, snl_amp, logical_n, has_noise_ref,
                    gated, mac_out, train_trace, has_row_ctl=False):
    (occ_ref, ins, noise_ref, mac_ref, v_ref, spike_ref, mask_ref,
     steps_ref, vtrace_ref) = _unpack_refs(refs, gated=gated,
                                           has_noise_ref=has_noise_ref,
                                           has_w_dend=False, mac_out=mac_out,
                                           train_trace=train_trace,
                                           has_row_ctl=has_row_ctl)
    x_ref, msb_ref, lsb_ref = ins["x"], ins["msb"], ins["lsb"]
    bounds_ref, levels_ref = ins["bounds"], ins["levels"]
    scale_ref, ctl_ref, v0_ref = ins["scale"], ins["ctl"], ins["v0"]
    i, t = pl.program_id(0), pl.program_id(1)
    j, kk = pl.program_id(2), pl.program_id(3)
    row0 = i * bm
    occ = _block_occupancy(occ_ref, i=i, t=t, kk=kk, n_i=n_i, n_k=n_k)

    @pl.when((t == 0) & (j == 0) & (kk == 0))
    def _load_membrane():
        v_ref[...] = v0_ref[...]

    _accumulate_mac_tile(x_ref, msb_ref, lsb_ref, mac_ref, ratio=ratio,
                         bn=bn, occ=occ)

    @pl.when((j == n_j - 1) & (kk == n_k - 1))
    def _head():
        seed, step, row_ids = _row_stream_ids(ctl_ref, ins.get("row_ctl"), t)
        mac = mac_ref[0]                                  # (bm, N) int-valued
        codes = _ramp_codes(mac, bounds_ref)
        if ima_noise is not None:
            # The NLQ ramp sees integer-unit MACs; inject the Fig. 7 error
            # (INL + offset + Gaussian, in code LSBs) before the sweep, so
            # winner selection, early stop, and the LUT map-back all see
            # the same noisy ripple-counter value the silicon registers.
            codes = _ima_noisy_codes(codes, mac, seed, step, row0=row0,
                                     per_branch=codes.shape[-1],
                                     logical_n=logical_n,
                                     ima_noise=ima_noise, n_codes=n_codes,
                                     rows=row_ids)
        codes = _mask_padded_columns(codes, n_valid)
        maskf, steps = _kwn_sweep(codes, k, n_codes, bounded=gated)
        recon = _lut_reconstruct(codes, levels_ref)
        # Winner drive: LUT value x per-column weight scale, losers exactly 0.
        drive = recon * scale_ref[...] * maskf * drive_gain
        nz = _lif_noise(noise_ref, v_ref.shape, seed, step, row0=row0,
                        logical_n=logical_n, snl_amp=snl_amp, use_snl=use_snl,
                        rows=row_ids)
        v_new, spike, v_clip = _lif_update(
            v_ref[...], drive, maskf, nz, beta=beta, v_th1=v_th1,
            v_th2=v_th2, v_reset=v_reset, v_lim=v_lim, use_snl=use_snl)
        v_ref[...] = v_new
        spike_ref[0] = spike
        mask_ref[0] = maskf
        steps_ref[0] = steps
        if vtrace_ref is not None:
            vtrace_ref[0] = v_clip


def _seq_nld_kernel(*refs, ratio, bm, bn, n_i, n_j, n_k, n_codes,
                    n_branches, beta, v_th1, v_th2, v_reset, v_lim,
                    drive_gain, ima_noise, logical_n, has_noise_ref, gated,
                    mac_out, has_row_ctl=False):
    (occ_ref, ins, _, mac_ref, v_ref, spike_ref, mask_ref,
     steps_ref, _) = _unpack_refs(refs, gated=gated,
                                  has_noise_ref=has_noise_ref,
                                  has_w_dend=True, mac_out=mac_out,
                                  has_row_ctl=has_row_ctl)
    x_ref, msb_ref, lsb_ref = ins["x"], ins["msb"], ins["lsb"]
    bounds_ref, levels_ref = ins["bounds"], ins["levels"]
    scale_ref, ctl_ref = ins["scale"], ins["ctl"]
    w_dend_ref, v0_ref = ins["w_dend"], ins["v0"]
    i, t = pl.program_id(0), pl.program_id(1)
    j, kk = pl.program_id(2), pl.program_id(3)
    row0 = i * bm
    occ = _block_occupancy(occ_ref, i=i, t=t, kk=kk, n_i=n_i, n_k=n_k)

    @pl.when((t == 0) & (j == 0) & (kk == 0))
    def _load_membrane():
        v_ref[...] = v0_ref[...]

    _accumulate_mac_tile(x_ref, msb_ref, lsb_ref, mac_ref, ratio=ratio,
                         bn=bn, occ=occ)

    @pl.when((j == n_j - 1) & (kk == n_k - 1))
    def _head():
        seed, step, row_ids = _row_stream_ids(ctl_ref, ins.get("row_ctl"), t)
        mac = mac_ref[0] * scale_ref[...]                 # (bm, J*N) float
        codes = _ramp_codes(mac, bounds_ref)
        if ima_noise is not None:
            # NL-activation ramp: same conversion error, float-unit range.
            codes = _ima_noisy_codes(codes, mac, seed, step, row0=row0,
                                     per_branch=codes.shape[-1] // n_branches,
                                     logical_n=logical_n,
                                     ima_noise=ima_noise, n_codes=n_codes,
                                     rows=row_ids)
        act = _lut_reconstruct(codes, levels_ref)
        bm_rows = act.shape[0]
        n = v_ref.shape[-1]
        w_dend = w_dend_ref[...]                          # (J, N)
        # soma combine over the branch-major planes: one static slice per
        # branch, summed in branch order from zero (the oracle's reduction)
        drive = jnp.zeros((bm_rows, n), jnp.float32)
        for jb in range(n_branches):
            drive = drive + act[:, jb * n:(jb + 1) * n] * w_dend[jb:jb + 1]
        drive = drive * drive_gain
        ones = jnp.ones((bm_rows, n), jnp.float32)        # dense LIF update
        v_new, spike, _ = _lif_update(
            v_ref[...], drive, ones, jnp.zeros((bm_rows, n), jnp.float32),
            beta=beta, v_th1=v_th1, v_th2=v_th2, v_reset=v_reset,
            v_lim=v_lim, use_snl=False)
        v_ref[...] = v_new
        spike_ref[0] = spike
        mask_ref[0] = ones
        steps_ref[0] = jnp.full((bm_rows, 1), n_codes - 1, jnp.int32)


# ---------------------------------------------------------------------------
# Public entry points
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=(
    "mode", "k", "ratio", "drive_gain", "use_snl", "bm", "bk", "bn",
    "n_valid", "ima_noise", "snl_amp", "logical_n", "mac_telemetry",
    "train_trace", "interpret") + _LIF_STATICS)
def fused_macro_seq(x: jax.Array, msb: jax.Array, lsb: jax.Array,
                    boundaries: jax.Array, levels: jax.Array,
                    scale: jax.Array, v: jax.Array,
                    noise: jax.Array | None = None,
                    w_dend: jax.Array | None = None,
                    activity: jax.Array | None = None,
                    row_ctl: jax.Array | None = None, *,
                    mode: str = "kwn", k: int = 12, ratio: float = 2.0,
                    drive_gain: float = 1.0, beta: float = 0.9,
                    v_th1: float = 1.0, v_th2: float = 0.6,
                    v_reset: float = 0.0, v_lim: float = 8.0,
                    use_snl: bool = True, bm: int = DEFAULT_BM,
                    bk: int = DEFAULT_BK, bn: int | None = None,
                    n_valid: int | None = None, ima_noise=None,
                    snl_amp: float = 0.0, logical_n: int | None = None,
                    mac_telemetry: bool = True, train_trace: bool = False,
                    seed=0, step_offset=0, interpret: bool):
    """A whole fused event sequence: T macro time steps in one kernel.

    x:           (T, M, K) int8 ternary inputs (time-major encoded events).
    msb/lsb:     (K, NC) int8 twin-cell planes.  ``kwn``: NC == N columns;
                 ``nld``: NC == J*N with branch-major column packing
                 (column j*N + p is branch j of output neuron p).
    boundaries:  (n_codes-1,) ramp decision thresholds.
    levels:      (n_codes,) LUT (KWN: 8-bit map-back values in integer MAC
                 units; NLD: f(x) samples).
    scale:       (NC,) per-column weight quantization scale.  Applied to the
                 winner drive after conversion in ``kwn`` mode (the ramp sees
                 integer-unit MACs); applied to the MAC before conversion in
                 ``nld`` mode (the activation ramp sees float-unit MACs).
    v:           (M, N) f32 initial membrane state (carried across T in
                 VMEM).
    noise:       (T, M, N) f32 pre-drawn per-step SNL noise, or None to
                 generate SNL noise in-kernel from the counter PRNG
                 (amplitude ``snl_amp``) — the noisy-silicon path streams
                 *nothing* per step.
    w_dend:      (J, N) soma combine weights (``nld`` only).
    bn:          column tile width (None = full NC width, single tile).
    n_valid:     number of real (non-padded) columns for the KWN sweep.
    ima_noise:   ``ima.IMAKernelNoise`` (static, hashable) enabling the
                 Fig. 7 conversion-error model at the ramp stage: per-step
                 per-column Gaussian draws are generated *inside* the kernel
                 by the counter PRNG (``core.ctrprng``), keyed on
                 ``(seed, step_offset + t, absolute row, logical column)``
                 so the stream is invariant to the launch tiling and
                 bitwise-reproducible by ``ref.fused_macro_seq_ref``.
                 (The hardware ``pltpu.prng_random_bits`` stream is *not*
                 used precisely because it has neither property.)
    snl_amp:     in-kernel SNL noise amplitude (used only when noise=None).
    logical_n:   unpadded per-branch column count — the counter's column
                 coordinate basis (defaults to the padded width).
    activity:    (T, M/bm, K/bk) int32 occupancy map (nonzero = block has at
                 least one event), or None for dense execution.  Delivered
                 to the kernel via scalar prefetch; all-zero activation
                 blocks skip the plane decode + MXU contraction, and the
                 KWN ramp sweep is bounded to the occupied code range — both
                 without changing any output bit (see module docstring).
    mac_telemetry: emit the raw (T, M, NC) integer-unit MAC stack to HBM
                 (True, the historical default — needed by calibration and
                 codebook studies).  False keeps the accumulator in VMEM
                 scratch: nothing but the per-step (spikes, mask,
                 adc_steps) leaves the kernel — the serving default — and
                 the returned mac is None.
    train_trace: additionally emit the per-step membrane trace vtrace
                 (T, M, N) — the post-saturation, pre-reset V_mem the spike
                 comparator reads.  This is the residual the surrogate
                 backward (``kernels.fused_macro_grad``) consumes: the
                 SuperSpike derivative and the saturation gradient gate are
                 both functions of it.  KWN mode only.
    seed:        traced int32 scalar keying both noise streams.
    step_offset: traced int32 added to the grid time index, so a launch
                 that starts mid-stream (T = 1 launches with the membrane
                 carried between them) draws the one-launch stream.
    row_ctl:     optional (M, 3) int32 per-row stream control
                 ``[seed, step_offset, row_id]``.  When present it
                 *replaces* the scalar ``seed``/``step_offset`` and the
                 absolute-row counter coordinate for that row, so every
                 batch row replays an independent noise stream — e.g. the
                 continuous-batching engine gives each slot the
                 ``(seed, steps_done, 0)`` of its request and the slot's
                 draws match a batch-1 one-shot run bit-for-bit.

    Returns (mac (T, M, NC) f32 or None, v_out (M, N) f32,
    spikes (T, M, N) f32, mask (T, M, N) f32, adc_steps (T, M, 1) i32),
    plus a trailing vtrace (T, M, N) f32 element when ``train_trace``.
    """
    t_steps, m, kdim = x.shape
    kdim2, nc = msb.shape
    n = v.shape[-1]
    bn = nc if bn is None else bn
    n_valid = nc if n_valid is None else n_valid
    logical_n = (nc if mode == "kwn" else n) if logical_n is None else \
        logical_n
    assert kdim == kdim2 and msb.shape == lsb.shape
    assert m % bm == 0 and kdim % bk == 0 and nc % bn == 0, \
        (m, kdim, nc, bm, bk, bn)
    assert v.shape == (m, n)
    assert noise is None or noise.shape == (t_steps, m, n)
    n_codes = levels.shape[0]
    assert boundaries.shape[0] == n_codes - 1
    grid = (m // bm, t_steps, nc // bn, kdim // bk)
    n_i, n_j, n_k = grid[0], grid[2], grid[3]
    has_noise_ref = noise is not None
    gated = activity is not None
    if gated:
        assert activity.shape == (t_steps, n_i, n_k), \
            (activity.shape, (t_steps, n_i, n_k))

    # Index maps take a trailing scalar-prefetch ref on the gated path.
    row_spec = lambda shape: pl.BlockSpec(shape,
                                          lambda i, t, j, kk, *_: (i, 0))
    step_spec = lambda shape: pl.BlockSpec(
        shape, lambda i, t, j, kk, *_: (t, i, 0))
    const_spec = lambda shape: pl.BlockSpec(shape,
                                            lambda i, t, j, kk, *_: (0, 0))
    ctl = jnp.stack([jnp.asarray(seed, jnp.int32),
                     jnp.asarray(step_offset, jnp.int32)]).reshape(1, 2)
    in_specs = [
        pl.BlockSpec((1, bm, bk), lambda i, t, j, kk, *_: (t, i, kk)),  # x
        pl.BlockSpec((bk, bn), lambda i, t, j, kk, *_: (kk, j)),        # msb
        pl.BlockSpec((bk, bn), lambda i, t, j, kk, *_: (kk, j)),        # lsb
        _SMEM_SPEC,                                                  # bounds
        _SMEM_SPEC,                                                  # levels
        const_spec((1, nc)),                                         # scale
        const_spec((1, 2)),                                          # ctl
    ]
    inputs = [x.astype(jnp.int8), msb.astype(jnp.int8), lsb.astype(jnp.int8),
              boundaries.astype(jnp.float32).reshape(-1),
              levels.astype(jnp.float32).reshape(-1),
              scale.astype(jnp.float32).reshape(1, -1),
              ctl]
    has_row_ctl = row_ctl is not None
    if has_row_ctl:
        assert row_ctl.shape == (m, 3), (row_ctl.shape, m)
        in_specs.append(row_spec((bm, 3)))                           # row_ctl
        inputs.append(row_ctl.astype(jnp.int32))

    if mode == "kwn":
        assert nc == n, (nc, n)
        kernel = functools.partial(
            _seq_kwn_kernel, ratio=ratio, bm=bm, bn=bn, n_i=n_i, n_j=n_j,
            n_k=n_k, n_valid=n_valid, k=k, n_codes=n_codes, beta=beta,
            v_th1=v_th1, v_th2=v_th2, v_reset=v_reset, v_lim=v_lim,
            use_snl=use_snl, drive_gain=drive_gain, ima_noise=ima_noise,
            snl_amp=snl_amp, logical_n=logical_n,
            has_noise_ref=has_noise_ref, gated=gated,
            mac_out=mac_telemetry, train_trace=train_trace,
            has_row_ctl=has_row_ctl)
    elif mode == "nld":
        assert not train_trace, "train_trace is KWN-only (silicon training)"
        assert w_dend is not None and nc % n == 0, (nc, n)
        n_branches = nc // n
        assert w_dend.shape == (n_branches, n)
        in_specs.append(const_spec((n_branches, n)))                 # w_dend
        inputs.append(w_dend.astype(jnp.float32))
        kernel = functools.partial(
            _seq_nld_kernel, ratio=ratio, bm=bm, bn=bn, n_i=n_i, n_j=n_j,
            n_k=n_k, n_codes=n_codes, n_branches=n_branches, beta=beta,
            v_th1=v_th1, v_th2=v_th2, v_reset=v_reset, v_lim=v_lim,
            drive_gain=drive_gain, ima_noise=ima_noise,
            logical_n=logical_n, has_noise_ref=has_noise_ref, gated=gated,
            mac_out=mac_telemetry, has_row_ctl=has_row_ctl)
    else:
        raise ValueError(f"unknown mode {mode!r}")

    in_specs.append(row_spec((bm, n)))                               # v0
    inputs.append(v.astype(jnp.float32))
    if has_noise_ref:
        in_specs.append(step_spec((1, bm, n)))                       # noise
        inputs.append(noise.astype(jnp.float32))

    out_specs = [
        row_spec((bm, n)),                               # carried V_mem
        step_spec((1, bm, n)), step_spec((1, bm, n)),
        step_spec((1, bm, 1)),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((m, n), jnp.float32),
        jax.ShapeDtypeStruct((t_steps, m, n), jnp.float32),
        jax.ShapeDtypeStruct((t_steps, m, n), jnp.float32),
        jax.ShapeDtypeStruct((t_steps, m, 1), jnp.int32),
    ]
    if train_trace:
        out_specs.append(step_spec((1, bm, n)))          # membrane trace
        out_shape.append(
            jax.ShapeDtypeStruct((t_steps, m, n), jnp.float32))
    scratch_shapes = []
    if mac_telemetry:
        out_specs.insert(0, step_spec((1, bm, nc)))      # mac telemetry
        out_shape.insert(0,
                         jax.ShapeDtypeStruct((t_steps, m, nc), jnp.float32))
    else:
        # accumulator never leaves VMEM: same footprint, zero HBM traffic
        scratch_shapes = [pltpu.VMEM((1, bm, nc), jnp.float32)]

    if gated:
        outs = pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=grid, in_specs=in_specs,
                out_specs=out_specs, scratch_shapes=scratch_shapes),
            out_shape=out_shape,
            interpret=interpret,
            compiler_params=compiler_params(interpret),
        )(activity.reshape(-1).astype(jnp.int32), *inputs)
    else:
        outs = pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=in_specs,
            out_specs=out_specs,
            out_shape=out_shape,
            scratch_shapes=scratch_shapes,
            interpret=interpret,
            compiler_params=compiler_params(interpret),
        )(*inputs)
    outs = list(outs)
    mac = outs.pop(0) if mac_telemetry else None
    if train_trace:
        v_out, spikes, mask, steps, vtrace = outs
        return mac, v_out, spikes, mask, steps, vtrace
    v_out, spikes, mask, steps = outs
    return mac, v_out, spikes, mask, steps


# ---------------------------------------------------------------------------
# Stacked multi-layer sequence kernel
# ---------------------------------------------------------------------------

class LayerSpec(NamedTuple):
    """Static per-layer geometry of the stacked sequence kernel.

    Hashable (jit-static).  ``k_dim`` is the input width this layer's weight
    planes see: for layer 0 that is the padded event width the launch
    streams; for deeper layers it is the *unpadded* previous layer's column
    count — inter-layer spikes live in registers, so the stacked kernel
    needs no column padding at all.  ``bk``/``bn`` are the in-kernel MAC
    tile sizes (static Python loops over ragged-tail slices): ``bk`` is
    also the occupancy-gating granularity, mirroring the single-layer
    kernel's (step, row-tile, K-tile) blocks.
    """

    k_dim: int     # input rows of this layer's weight planes
    n: int         # output columns (== NC; the KWN stack is unpadded)
    k: int         # KWN winner count for this layer
    bk: int        # K-tile size (gating granularity; ragged tail allowed)
    bn: int        # column-tile size of the in-kernel MAC loop

    @property
    def n_k(self) -> int:
        """Number of K tiles (occupancy words per (step, row-tile))."""
        return -(-self.k_dim // self.bk)


def stack_vmem_bytes(specs, bm: int) -> int:
    """Scoped-VMEM estimate of one stacked launch, in bytes, on a TPU.

    Counted as ``TilePlan.vmem_bytes`` counts one layer: the layer-0 int8
    event block and every layer's int8 weight planes and (bm, n) row
    blocks, double-buffered at native tile padding, plus the head
    temporaries of the widest layer (layers run one after another).
    """
    rows = _ceil_mult(bm, 8)
    lanes = lambda w: _ceil_mult(w, 128)
    blocks = _ceil_mult(bm, 32) * lanes(specs[0].k_dim)
    for spec in specs:
        blocks += 2 * _ceil_mult(spec.k_dim, 32) * lanes(spec.n)
        blocks += 4 * rows * (4 * lanes(spec.n) + 3 * 128)
    widest = max(lanes(spec.n) for spec in specs)
    return 2 * blocks + HEAD_TEMPORARIES * 4 * rows * widest


def _multi_seq_kwn_kernel(*refs, specs, ratio, bm, n_i, n_codes, beta,
                          v_th1, v_th2, v_reset, v_lim, use_snl, drive_gain,
                          ima_noise, snl_amp, has_noise, gated):
    """L stacked KWN macro layers per (row-tile, time-step) grid step.

    The inter-layer ternary spike tensor never exists outside this kernel
    body: layer l's spike output is a register value fed straight into
    layer l+1's MAC.  Per-layer membranes are carried in VMEM output
    blocks across the whole T axis; per-layer weight planes are
    const-indexed full-array refs (layer-stationary — staged once for the
    launch, resident across every time step).

    Gating: layer 0 consumes the scalar-prefetched host occupancy map
    (events are host-visible, so the host plans them, exactly like the
    single-layer kernel); for layer l > 0 the previous layer's winner set
    IS the activity plan — occupancy of each K tile is computed *in
    kernel* from the register-resident spikes (``jnp.any(tile != 0)``),
    and all-zero tiles skip the plane decode + MXU contraction.  Skipped
    blocks contribute exactly-zero partials, so gating is bitwise-neutral
    (same argument as ``_accumulate_mac_tile``).  The per-layer occupied-
    block counts are emitted as telemetry — the multi-layer occupancy map
    leaves the kernel as counters, not as spike tensors.
    """
    refs = list(refs)
    occ_ref = refs.pop(0) if gated else None
    x_ref = refs.pop(0)
    ctl_ref = refs.pop(0)
    n_layers = len(specs)
    w_refs = [tuple(refs.pop(0) for _ in range(5)) for _ in range(n_layers)]
    v0_refs = [refs.pop(0) for _ in range(n_layers)]
    noise_refs = [refs.pop(0) if has_noise else None for _ in range(n_layers)]
    v_refs = refs[:n_layers]
    spike_ref, mask_ref = refs[n_layers], refs[n_layers + 1]
    steps_refs = refs[n_layers + 2:2 * n_layers + 2]
    cnt_refs = refs[2 * n_layers + 2:3 * n_layers + 2]
    occn_refs = refs[3 * n_layers + 2:4 * n_layers + 2]

    i, t = pl.program_id(0), pl.program_id(1)
    row0 = i * bm

    @pl.when(t == 0)
    def _load_membranes():
        for li in range(n_layers):
            v_refs[li][...] = v0_refs[li][...]

    cur = x_ref[0].astype(jnp.float32)                    # (bm, k_dim_0)
    last_mask = None
    for li, spec in enumerate(specs):
        msb_ref, lsb_ref, bounds_ref, levels_ref, scale_ref = w_refs[li]
        seed = ctl_ref[0, li]
        n_occ = jnp.int32(0 if gated else spec.n_k)
        tiles = []
        for j0 in range(0, spec.n, spec.bn):
            jw = min(spec.bn, spec.n - j0)
            acc = jnp.zeros((bm, jw), jnp.float32)
            for kk, k0 in enumerate(range(0, spec.k_dim, spec.bk)):
                kw = min(spec.bk, spec.k_dim - k0)
                xt = cur[:, k0:k0 + kw]

                def _part(a, xt=xt, k0=k0, kw=kw, j0=j0, jw=jw,
                          msb_ref=msb_ref, lsb_ref=lsb_ref):
                    w = (ratio
                         * msb_ref[k0:k0 + kw, j0:j0 + jw].astype(jnp.float32)
                         + lsb_ref[k0:k0 + kw,
                                   j0:j0 + jw].astype(jnp.float32))
                    return a + jax.lax.dot_general(
                        xt, w, (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32)

                if not gated:
                    acc = _part(acc)
                else:
                    if li == 0:       # host-planned occupancy (events)
                        occ = occ_ref[(t * n_i + i) * spec.n_k + kk]
                    else:             # in-kernel: winners ARE the plan
                        occ = jnp.any(xt != 0).astype(jnp.int32)
                    acc = jax.lax.cond(occ > 0, _part, lambda a: a, acc)
                    if j0 == 0:       # occupancy is a K-tile property
                        n_occ = n_occ + (occ > 0).astype(jnp.int32)
            tiles.append(acc)
        mac = tiles[0] if len(tiles) == 1 else jnp.concatenate(tiles, -1)
        codes = _ramp_codes(mac, bounds_ref)
        if ima_noise is not None:
            codes = _ima_noisy_codes(codes, mac, seed, t, row0=row0,
                                     per_branch=spec.n, logical_n=spec.n,
                                     ima_noise=ima_noise, n_codes=n_codes)
        maskf, steps = _kwn_sweep(codes, spec.k, n_codes, bounded=gated)
        recon = _lut_reconstruct(codes, levels_ref)
        drive = recon * scale_ref[...] * maskf * drive_gain
        nz = _lif_noise(noise_refs[li], (bm, spec.n), seed, t, row0=row0,
                        logical_n=spec.n, snl_amp=snl_amp, use_snl=use_snl)
        v_new, spike, _ = _lif_update(
            v_refs[li][...], drive, maskf, nz, beta=beta, v_th1=v_th1,
            v_th2=v_th2, v_reset=v_reset, v_lim=v_lim, use_snl=use_snl)
        v_refs[li][...] = v_new
        steps_refs[li][0] = steps
        cnt_refs[li][0] = jnp.sum(spike, axis=-1, keepdims=True)
        occn_refs[li][...] = jnp.reshape(n_occ, (1, 1, 1))
        last_mask = maskf
        cur = spike                   # register hand-off to the next layer
    spike_ref[0] = cur
    mask_ref[0] = last_mask


@functools.partial(jax.jit, static_argnames=(
    "specs", "ratio", "drive_gain", "use_snl", "bm", "ima_noise",
    "snl_amp", "has_noise", "gated", "interpret") + _LIF_STATICS)
def fused_macro_multi_seq(x: jax.Array, planes, v0s, noises=None,
                          activity: jax.Array | None = None, ctl=None, *,
                          specs: tuple, ratio: float = 2.0,
                          drive_gain: float = 1.0, beta: float = 0.9,
                          v_th1: float = 1.0, v_th2: float = 0.6,
                          v_reset: float = 0.0, v_lim: float = 8.0,
                          use_snl: bool = True, bm: int = DEFAULT_BM,
                          ima_noise=None, snl_amp: float = 0.0,
                          has_noise: bool = False, gated: bool = False,
                          interpret: bool):
    """L stacked KWN macro layers over a whole event sequence, one launch.

    x:       (T, M, K0) int8 ternary events (K0 padded to layer 0's K
             tiling; M padded to ``bm``).
    planes:  per-layer (msb, lsb, boundaries, levels, scale) tuples; the
             int8 twin-cell planes are (k_dim_l, n_l) *unpadded* for
             l > 0 (inter-layer spikes never leave registers, so the
             stacked kernel needs no column padding).
    v0s:     per-layer (M, n_l) f32 initial membranes.
    noises:  per-layer (T, M, n_l) pre-drawn SNL noise (clean-path PRBS
             parity) when ``has_noise``; None for in-kernel counter noise.
    activity: (T, M/bm, K0/bk0) int32 layer-0 occupancy map when
             ``gated`` (scalar-prefetched).  Deeper layers gate on the
             in-kernel winner sets — no host map exists for them.
    ctl:     (1, L) int32 per-layer counter seeds (the counter's step word
             is the grid time index).
    specs:   tuple of ``LayerSpec`` (static per-layer geometry).

    Returns (v_outs (per-layer (M, n_l)), spikes (T, M, n_L) — the FINAL
    layer only, mask (T, M, n_L), steps (per-layer (T, M, 1) i32),
    counts (per-layer (T, M, 1) f32 row-wise spike counts — the telemetry
    stand-in for the deep spike tensors that never reach HBM),
    occupancy (per-layer (T, M/bm, 1) i32 occupied-K-tile counts)).
    """
    t_steps, m, kdim = x.shape
    n_layers = len(specs)
    assert kdim == specs[0].k_dim and m % bm == 0, (x.shape, specs[0], bm)
    n_codes = planes[0][3].shape[-1]
    n_i = m // bm
    if gated:
        assert activity.shape == (t_steps, n_i, specs[0].n_k), \
            (activity.shape, (t_steps, n_i, specs[0].n_k))

    row_spec = lambda shape: pl.BlockSpec(shape, lambda i, t, *_: (i, 0))
    step_spec = lambda shape: pl.BlockSpec(shape, lambda i, t, *_: (t, i, 0))
    const_spec = lambda shape: pl.BlockSpec(
        shape, lambda i, t, *_: (0,) * len(shape))
    if ctl is None:
        ctl = jnp.zeros((1, n_layers), jnp.int32)

    in_specs = [
        pl.BlockSpec((1, bm, kdim), lambda i, t, *_: (t, i, 0)),      # x
        const_spec((1, n_layers)),                                    # ctl
    ]
    inputs = [x.astype(jnp.int8), ctl.astype(jnp.int32)]
    for spec, (msb, lsb, bounds, levels, scale) in zip(specs, planes):
        assert msb.shape == (spec.k_dim, spec.n), (msb.shape, spec)
        in_specs += [const_spec((spec.k_dim, spec.n)),
                     const_spec((spec.k_dim, spec.n)),
                     _SMEM_SPEC, _SMEM_SPEC,
                     const_spec((1, spec.n))]
        inputs += [msb.astype(jnp.int8), lsb.astype(jnp.int8),
                   bounds.astype(jnp.float32).reshape(-1),
                   levels.astype(jnp.float32).reshape(-1),
                   scale.astype(jnp.float32).reshape(1, -1)]
    for spec, v0 in zip(specs, v0s):
        assert v0.shape == (m, spec.n), (v0.shape, spec)
        in_specs.append(row_spec((bm, spec.n)))
        inputs.append(v0.astype(jnp.float32))
    if has_noise:
        for spec, nz in zip(specs, noises):
            assert nz.shape == (t_steps, m, spec.n), (nz.shape, spec)
            in_specs.append(step_spec((1, bm, spec.n)))
            inputs.append(nz.astype(jnp.float32))

    n_last = specs[-1].n
    out_specs = [row_spec((bm, spec.n)) for spec in specs]            # v
    out_shape = [jax.ShapeDtypeStruct((m, spec.n), jnp.float32)
                 for spec in specs]
    out_specs += [step_spec((1, bm, n_last)), step_spec((1, bm, n_last))]
    out_shape += [jax.ShapeDtypeStruct((t_steps, m, n_last), jnp.float32),
                  jax.ShapeDtypeStruct((t_steps, m, n_last), jnp.float32)]
    out_specs += [step_spec((1, bm, 1)) for _ in specs]               # steps
    out_shape += [jax.ShapeDtypeStruct((t_steps, m, 1), jnp.int32)
                  for _ in specs]
    out_specs += [step_spec((1, bm, 1)) for _ in specs]               # counts
    out_shape += [jax.ShapeDtypeStruct((t_steps, m, 1), jnp.float32)
                  for _ in specs]
    out_specs += [pl.BlockSpec((1, 1, 1), lambda i, t, *_: (t, i, 0))
                  for _ in specs]                                     # occ
    out_shape += [jax.ShapeDtypeStruct((t_steps, n_i, 1), jnp.int32)
                  for _ in specs]

    kernel = functools.partial(
        _multi_seq_kwn_kernel, specs=specs, ratio=ratio, bm=bm, n_i=n_i,
        n_codes=n_codes, beta=beta, v_th1=v_th1, v_th2=v_th2,
        v_reset=v_reset, v_lim=v_lim, use_snl=use_snl,
        drive_gain=drive_gain, ima_noise=ima_noise, snl_amp=snl_amp,
        has_noise=has_noise, gated=gated)
    if gated:
        outs = pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=(n_i, t_steps),
                in_specs=in_specs, out_specs=out_specs),
            out_shape=out_shape,
            interpret=interpret,
            compiler_params=compiler_params(interpret),
        )(activity.reshape(-1).astype(jnp.int32), *inputs)
    else:
        outs = pl.pallas_call(
            kernel,
            grid=(n_i, t_steps),
            in_specs=in_specs,
            out_specs=out_specs,
            out_shape=out_shape,
            interpret=interpret,
            compiler_params=compiler_params(interpret),
        )(*inputs)
    outs = list(outs)
    v_outs = tuple(outs[:n_layers])
    spikes, mask = outs[n_layers], outs[n_layers + 1]
    steps = tuple(outs[n_layers + 2:2 * n_layers + 2])
    counts = tuple(outs[2 * n_layers + 2:3 * n_layers + 2])
    occupancy = tuple(outs[3 * n_layers + 2:4 * n_layers + 2])
    return v_outs, spikes, mask, steps, counts, occupancy

