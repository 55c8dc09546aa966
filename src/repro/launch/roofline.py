"""Roofline-term extraction from compiled dry-run artifacts.

  compute term    = HLO_FLOPs / (chips * 197e12 bf16 FLOP/s)
  memory term     = HLO_bytes / (chips * 819e9 B/s HBM)
  collective term = collective_bytes / (chips * n_links * 50e9 B/s ICI)

HLO_FLOPs / HLO_bytes come from compiled.cost_analysis() (whole-program,
all devices).  collective_bytes is parsed out of the optimized HLO text:
for every all-gather / all-reduce / reduce-scatter / all-to-all /
collective-permute instruction we take the largest shape named in the
instruction (the full buffer that crosses links) — an upper-bound proxy;
loop-carried collectives count once per appearance (documented
limitation: the ring SpMM's permute sits in a fori_loop body, so its P
steps count as one).
"""
from __future__ import annotations

import dataclasses
import re

PEAK_FLOPS = 197e12          # bf16 / chip (TPU v5e)
HBM_BW = 819e9               # B/s / chip
ICI_BW = 50e9                # B/s / link
ICI_LINKS = 4                # torus links usable per chip (2D)

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "s32": 4, "s16": 2, "s8": 1, "u64": 8, "u32": 4, "u16": 2,
    "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"\b(" + "|".join(_DTYPE_BYTES) + r")\[([0-9,]*)\]")


def shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES[dtype]


@dataclasses.dataclass
class CollectiveStats:
    total_bytes: int
    by_kind: dict

    def __str__(self):
        parts = ", ".join(f"{k}: {v/1e9:.3f} GB" for k, v in
                          sorted(self.by_kind.items()))
        return f"collectives {self.total_bytes/1e9:.3f} GB ({parts})"


def collective_bytes(hlo_text: str) -> CollectiveStats:
    total = 0
    by_kind: dict[str, int] = {}
    for line in hlo_text.splitlines():
        stripped = line.strip()
        for kind in _COLLECTIVES:
            # match the op name as the instruction, not inside metadata
            if f"= {kind}(" in stripped or re.search(
                    rf"\)\s*{kind}\(", stripped) or re.search(
                    rf"\]\S*\s{kind}\(", stripped):
                sizes = [shape_bytes(m.group(1), m.group(2))
                         for m in _SHAPE_RE.finditer(stripped)]
                if sizes:
                    b = max(sizes)
                    total += b
                    by_kind[kind] = by_kind.get(kind, 0) + b
                break
    return CollectiveStats(total, by_kind)


@dataclasses.dataclass
class Roofline:
    flops: float
    hbm_bytes: float
    coll_bytes: float
    chips: int
    compute_s: float
    memory_s: float
    collective_s: float
    bottleneck: str
    model_flops: float
    useful_ratio: float

    def as_dict(self):
        return dataclasses.asdict(self)


def analyze(compiled, n_chips: int, model_flops: float = 0.0,
            analytic: dict | None = None) -> Roofline:
    """analytic: optional {'flops','hbm_bytes','coll_bytes'} override for
    loop-heavy (scan) programs where HloCostAnalysis counts while bodies
    once (see launch/analytic.py docstring)."""
    cost = compiled.cost_analysis()
    if isinstance(cost, list):  # older API returns [dict]
        cost = cost[0]
    if analytic is not None:
        flops = float(analytic["flops"])
        byts = float(analytic["hbm_bytes"])
        coll_total = float(analytic["coll_bytes"])
    else:
        flops = float(cost.get("flops", 0.0))
        byts = float(cost.get("bytes accessed", 0.0))
        coll_total = float(collective_bytes(compiled.as_text()).total_bytes)
    compute_s = flops / (n_chips * PEAK_FLOPS)
    memory_s = byts / (n_chips * HBM_BW)
    collective_s = coll_total / (n_chips * ICI_LINKS * ICI_BW)
    terms = {"compute": compute_s, "memory": memory_s,
             "collective": collective_s}
    bottleneck = max(terms, key=terms.get)
    return Roofline(flops, byts, coll_total, n_chips, compute_s,
                    memory_s, collective_s, bottleneck, model_flops,
                    (model_flops / flops) if flops else 0.0)
