"""Roofline terms from a compiled dry-run artifact.

compute   = HLO_FLOPs_per_device / peak_FLOPs_per_chip
memory    = HLO_bytes_per_device / HBM_bandwidth
collective= wire_bytes_per_device / ICI_link_bandwidth

``cost_analysis()`` on an SPMD-compiled executable reports *per-device*
flops/bytes (verified against a hand-computed matmul).  Collective bytes are
NOT in cost_analysis, so we parse the post-SPMD HLO: every
all-reduce / all-gather / reduce-scatter / all-to-all / collective-permute
result shape, with a wire-cost factor per op kind (ring model: all-reduce
moves ~2x its payload, the others ~1x).

Collectives inside while loops (lax.scan over layer groups / microbatches)
appear ONCE in the HLO but execute trip-count times; we attribute trip counts
by locating each while op's condition computation and extracting its loop
bound constant.  Nested loops multiply.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Any

# --- TPU v5e hardware constants (per chip) ---------------------------------
PEAK_FLOPS = 197e12          # bf16
HBM_BW = 819e9               # bytes/s
ICI_BW = 50e9                # bytes/s per link

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

_WIRE_FACTOR = {
    "all-reduce": 2.0,          # ring: reduce-scatter + all-gather
    "all-gather": 1.0,
    "reduce-scatter": 1.0,
    "all-to-all": 1.0,
    "collective-permute": 1.0,
}

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_COLL_RE = re.compile(
    r"=\s+((?:\([^)]*\))|(?:[a-z0-9]+\[[0-9,]*\]\S*))\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(-start)?\(")
_COMP_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w\.\-]+)\s+\([^)]*\)\s*->", re.M)
_WHILE_RE = re.compile(
    r"while\(.*?\)[^\n]*?condition=%?([\w\.\-]+)[^\n]*?body=%?([\w\.\-]+)"
    r"|while\(.*?\)[^\n]*?body=%?([\w\.\-]+)[^\n]*?condition=%?([\w\.\-]+)")
_CONST_RE = re.compile(r"constant\((\d+)\)")


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(shape_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _split_computations(hlo: str) -> dict[str, str]:
    """Split module text into named computations."""
    comps: dict[str, str] = {}
    cur_name, cur_lines = None, []
    for line in hlo.splitlines():
        m = _COMP_RE.match(line.strip()) if ("->" in line and "(" in line
                                             and not line.strip().startswith("%param")) else None
        if m and (line.startswith("ENTRY") or not line.startswith(" ")):
            if cur_name is not None:
                comps[cur_name] = "\n".join(cur_lines)
            cur_name = m.group(1)
            cur_lines = [line]
        else:
            cur_lines.append(line)
    if cur_name is not None:
        comps[cur_name] = "\n".join(cur_lines)
    return comps


def _loop_bound(cond_text: str) -> int:
    consts = [int(c) for c in _CONST_RE.findall(cond_text)]
    consts = [c for c in consts if 1 < c < 100000]
    return max(consts) if consts else 1


def _body_multipliers(hlo: str, comps: dict[str, str]) -> dict[str, int]:
    """Map computation name -> total trip multiplier (nested loops compose)."""
    # direct body -> bound
    parent: dict[str, tuple[str, int]] = {}
    for comp_name, text in comps.items():
        for m in _WHILE_RE.finditer(text):
            cond = m.group(1) or m.group(4)
            body = m.group(2) or m.group(3)
            if body and cond and cond in comps:
                parent[body] = (comp_name, _loop_bound(comps[cond]))

    mult: dict[str, int] = {}

    def resolve(name: str, seen=()) -> int:
        if name in mult:
            return mult[name]
        if name in seen:
            return 1
        if name in parent:
            pname, bound = parent[name]
            m = bound * resolve(pname, seen + (name,))
        else:
            m = 1
        mult[name] = m
        return m

    for name in comps:
        resolve(name)
    return mult


def collective_bytes(hlo: str) -> dict[str, float]:
    """Wire bytes per device by collective kind, loop-trip-count weighted."""
    comps = _split_computations(hlo)
    mults = _body_multipliers(hlo, comps)
    out: dict[str, float] = {k: 0.0 for k in _WIRE_FACTOR}
    counts: dict[str, int] = {k: 0 for k in _WIRE_FACTOR}
    for comp_name, text in comps.items():
        m = mults.get(comp_name, 1)
        for match in _COLL_RE.finditer(text):
            shape_str, kind = match.group(1), match.group(2)
            b = _shape_bytes(shape_str)
            out[kind] += b * _WIRE_FACTOR[kind] * m
            counts[kind] += m
    out_named = {f"{k}_bytes": v for k, v in out.items()}
    out_named.update({f"{k}_count": counts[k] for k in counts})
    out_named["total_wire_bytes"] = sum(out.values())
    return out_named


@dataclasses.dataclass
class Roofline:
    flops_per_device: float
    bytes_per_device: float
    wire_bytes_per_device: float
    model_flops_per_device: float = 0.0

    @property
    def compute_s(self) -> float:
        return self.flops_per_device / PEAK_FLOPS

    @property
    def memory_s(self) -> float:
        return self.bytes_per_device / HBM_BW

    @property
    def collective_s(self) -> float:
        return self.wire_bytes_per_device / ICI_BW

    @property
    def dominant(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def bound_s(self) -> float:
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def useful_flops_frac(self) -> float:
        if self.flops_per_device == 0:
            return 0.0
        return self.model_flops_per_device / self.flops_per_device

    @property
    def roofline_frac(self) -> float:
        """Fraction of the chip's peak the step would achieve if it runs at
        the dominant-term bound and only model_flops count as useful."""
        if self.bound_s == 0:
            return 0.0
        return (self.model_flops_per_device / PEAK_FLOPS) / self.bound_s

    def as_dict(self) -> dict[str, Any]:
        return {
            "flops_per_device": self.flops_per_device,
            "bytes_per_device": self.bytes_per_device,
            "wire_bytes_per_device": self.wire_bytes_per_device,
            "model_flops_per_device": self.model_flops_per_device,
            "compute_s": self.compute_s,
            "memory_s": self.memory_s,
            "collective_s": self.collective_s,
            "dominant": self.dominant,
            "useful_flops_frac": self.useful_flops_frac,
            "roofline_frac": self.roofline_frac,
        }


def model_flops(n_active_params: int, n_tokens: int, kind: str,
                n_devices: int) -> float:
    """6*N*D rule (fwd+bwd) for train; 2*N*D for inference steps."""
    per_tok = 6 * n_active_params if kind == "train" else 2 * n_active_params
    return per_tok * n_tokens / n_devices


def from_compiled(compiled, lowered_text: str | None = None,
                  model_flops_per_device: float = 0.0) -> Roofline:
    ca = compiled.cost_analysis()
    hlo = lowered_text or compiled.as_text()
    coll = collective_bytes(hlo)
    return Roofline(
        flops_per_device=float(ca.get("flops", 0.0)),
        bytes_per_device=float(ca.get("bytes accessed", 0.0)),
        wire_bytes_per_device=float(coll["total_wire_bytes"]),
        model_flops_per_device=model_flops_per_device,
    )
