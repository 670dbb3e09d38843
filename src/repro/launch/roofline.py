"""Roofline analysis from compiled dry-run artifacts (deliverable g).

This container is CPU-only; TPU v5e is the *target*. Wall-clock MFU cannot be
measured, so the three roofline terms are derived structurally:

  compute    = HLO_FLOPs          / (chips * 197e12  bf16 FLOP/s)
  memory     = HLO_bytes_accessed / (chips * 819e9   B/s HBM)
  collective = collective_bytes   / (chips * 50e9    B/s per ICI link)

HLO_FLOPs / bytes come from ``compiled.cost_analysis()``. collective_bytes is
parsed from the compiled HLO text: the result-shape bytes of every
all-gather / all-reduce / reduce-scatter / all-to-all / collective-permute op
(result bytes ~= bytes landed on the interconnect per chip for these ops;
scan-body collectives are multiplied by their trip count when XLA reports
them inside a while loop — we parse the flattened module, which already
repeats unrolled ops and keeps loop bodies once; we annotate accordingly).

MODEL_FLOPS = 6·N_active·D (train) or 2·N_active·D (forward-style steps);
the ratio MODEL_FLOPS / HLO_FLOPs flags remat/redundant compute.
"""
from __future__ import annotations

import dataclasses
import json
import re
from dataclasses import dataclass, field
from typing import Dict, Optional

TPU_V5E = {
    "flops_bf16": 197e12,  # per chip
    "hbm_bw": 819e9,  # B/s
    "ici_bw": 50e9,  # B/s per link (~ per-chip usable collective bandwidth)
}

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")

# e.g.:  %all-reduce.5 = f32[128,1024]{1,0} all-reduce(...)
_OP_RE = re.compile(
    r"=\s*(?:\()?([a-z0-9]+)\[([0-9,]*)\][^=]*?\s(" + "|".join(_COLLECTIVES) + r")\(",
)
# tuple-result collectives:  (f32[8,128], f32[8,128]) all-to-all(
_TUPLE_RE = re.compile(
    r"=\s*\(([^)]*)\)\s*(" + "|".join(_COLLECTIVES) + r")(?:-(?:start|done))?\(",
)
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")

_WHILE_TRIP_RE = re.compile(r"trip_count=(\d+)")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def collective_stats(hlo_text: str) -> Dict[str, int]:
    """Sum result bytes of collective ops in the (post-SPMD) HLO text."""
    out: Dict[str, int] = {c: 0 for c in _COLLECTIVES}
    counts: Dict[str, int] = {c: 0 for c in _COLLECTIVES}
    for m in _OP_RE.finditer(hlo_text):
        dtype, dims, op = m.group(1), m.group(2), m.group(3)
        out[op] += _shape_bytes(dtype, dims)
        counts[op] += 1
    for m in _TUPLE_RE.finditer(hlo_text):
        shapes, op = m.group(1), m.group(2)
        for sm in _SHAPE_RE.finditer(shapes):
            out[op] += _shape_bytes(sm.group(1), sm.group(2))
        counts[op] += 1
    stats = {f"{k}_bytes": v for k, v in out.items()}
    stats.update({f"{k}_count": v for k, v in counts.items()})
    stats["total_bytes"] = sum(out.values())
    return stats


def scan_trip_counts(hlo_text: str) -> list:
    return [int(m.group(1)) for m in _WHILE_TRIP_RE.finditer(hlo_text)]


@dataclass
class RooflineReport:
    arch: str
    shape: str
    mesh: str
    chips: int
    hlo_flops: float
    hlo_bytes: float
    collective_bytes: float
    model_flops: float
    n_layer_trips: int = 1  # scan trip multiplier applied to collectives
    collective_detail: Dict[str, int] = field(default_factory=dict)
    memory_per_device: Optional[Dict[str, float]] = None

    @property
    def t_compute(self) -> float:
        return self.hlo_flops / (self.chips * TPU_V5E["flops_bf16"])

    @property
    def t_memory(self) -> float:
        return self.hlo_bytes / (self.chips * TPU_V5E["hbm_bw"])

    @property
    def t_collective(self) -> float:
        return self.collective_bytes / (self.chips * TPU_V5E["ici_bw"])

    @property
    def bottleneck(self) -> str:
        terms = {
            "compute": self.t_compute,
            "memory": self.t_memory,
            "collective": self.t_collective,
        }
        return max(terms, key=terms.get)

    @property
    def useful_flops_ratio(self) -> float:
        return self.model_flops / max(self.hlo_flops, 1.0)

    @property
    def step_time_bound(self) -> float:
        return max(self.t_compute, self.t_memory, self.t_collective)

    def to_dict(self) -> Dict:
        d = dataclasses.asdict(self)
        d.update(
            t_compute=self.t_compute,
            t_memory=self.t_memory,
            t_collective=self.t_collective,
            bottleneck=self.bottleneck,
            useful_flops_ratio=self.useful_flops_ratio,
            step_time_bound=self.step_time_bound,
        )
        return d


def build_report(*, arch: str, shape: str, mesh_name: str, chips: int,
                 cost: Dict, hlo_text: str, model_flops: float,
                 memory_analysis=None) -> RooflineReport:
    """``cost`` is ignored except as a cross-check: the primary numbers come
    from the trip-count-aware ``repro.launch.hlo_analysis`` walker (XLA's CPU
    cost_analysis counts while bodies once and reports per-device only)."""
    from repro.launch import hlo_analysis

    a = hlo_analysis.analyze(hlo_text)
    mem = None
    if memory_analysis is not None:
        mem = {
            "argument_bytes": float(getattr(memory_analysis, "argument_size_in_bytes", 0)),
            "output_bytes": float(getattr(memory_analysis, "output_size_in_bytes", 0)),
            "temp_bytes": float(getattr(memory_analysis, "temp_size_in_bytes", 0)),
            "generated_code_bytes": float(getattr(memory_analysis, "generated_code_size_in_bytes", 0)),
        }
    detail = {k: v for k, v in a.items() if k.startswith("all") or k.startswith("reduce")
              or k.startswith("collective")}
    detail["xla_cost_analysis_flops_per_device"] = float(cost.get("flops", 0.0))
    detail["xla_cost_analysis_bytes_per_device"] = float(cost.get("bytes accessed", 0.0))
    return RooflineReport(
        arch=arch,
        shape=shape,
        mesh=mesh_name,
        chips=chips,
        hlo_flops=a["flops_per_device"] * chips,
        hlo_bytes=a["bytes_per_device"] * chips,
        collective_bytes=a["collective_bytes_per_device"] * chips,
        model_flops=model_flops,
        collective_detail=detail,
        memory_per_device=mem,
    )
