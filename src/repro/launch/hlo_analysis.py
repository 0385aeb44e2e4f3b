"""Compiled-HLO analysis: collective bytes + roofline terms.

``collective_bytes`` parses the post-SPMD compiled module text and sums the
result-shape bytes of every cross-device collective (all-gather, all-reduce,
reduce-scatter, all-to-all, collective-permute).  ``cost_analysis`` has no
collective accounting, so this is the §Roofline collective term's source.

Hardware constants: TPU v5e — 197 TFLOP/s bf16, 819 GB/s HBM, ~50 GB/s/link
ICI (brief-specified).
"""
from __future__ import annotations

import re
from dataclasses import asdict, dataclass
from typing import Dict, Optional

PEAK_FLOPS = 197e12          # bf16 per chip
HBM_BW = 819e9               # bytes/s per chip
ICI_BW = 50e9                # bytes/s per link

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "s4": 1, "u4": 1, "f8e4m3fn": 1, "f8e5m2": 1,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([\d,]*)\]")


def cost_analysis_dict(compiled) -> Dict[str, float]:
    """``compiled.cost_analysis()`` as a dict (empty when the backend
    reports nothing)."""
    return compiled.cost_analysis() or {}
_OP_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(\([^)]*\)|[a-z0-9]+\[[^\]]*\][^ ]*)\s+"
    r"(" + "|".join(_COLLECTIVES) + r")[-\w]*\(")


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(shape_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Sum result-shape bytes per collective kind over the compiled module."""
    out: Dict[str, int] = {k: 0 for k in _COLLECTIVES}
    out["count"] = 0
    for line in hlo_text.splitlines():
        m = _OP_RE.match(line)
        if not m:
            continue
        shape_str, kind = m.group(1), m.group(2)
        # while-loop bodies appear once; scans therefore count once per HLO —
        # multiply by trip count is not recoverable from text, so we report
        # the static module bytes (documented in EXPERIMENTS.md §Roofline).
        out[kind] += _shape_bytes(shape_str)
        out["count"] += 1
    out["total"] = sum(out[k] for k in _COLLECTIVES)
    return out


@dataclass
class RooflineTerms:
    flops: float                 # per-device HLO flops
    hbm_bytes: float             # per-device bytes accessed
    coll_bytes: float            # per-device collective bytes (static module)
    compute_s: float
    memory_s: float
    collective_s: float
    bottleneck: str
    model_flops: Optional[float] = None
    useful_ratio: Optional[float] = None

    def to_dict(self):
        return asdict(self)


def roofline(cost: Dict, coll: Dict[str, int],
             model_flops_total: Optional[float] = None,
             num_chips: int = 256, ici_links: int = 4) -> RooflineTerms:
    """Build the three §Roofline terms from compiled artifacts.

    ``cost`` = compiled.cost_analysis() (PER-DEVICE program);
    ``model_flops_total`` = 6·N·D for the GLOBAL batch — divided by chips
    here so the useful-ratio compares per-device quantities."""
    flops = float(cost.get("flops", 0.0))
    hbm = float(cost.get("bytes accessed", 0.0))
    cb = float(coll.get("total", 0))
    compute_s = flops / PEAK_FLOPS
    memory_s = hbm / HBM_BW
    coll_s = cb / (ICI_BW * ici_links)
    terms = {"compute": compute_s, "memory": memory_s, "collective": coll_s}
    bottleneck = max(terms, key=terms.get)
    mf = uratio = None
    if model_flops_total:
        mf = model_flops_total / num_chips
        uratio = mf / flops if flops else None
    return RooflineTerms(flops, hbm, cb, compute_s, memory_s, coll_s,
                         bottleneck, mf, uratio)
