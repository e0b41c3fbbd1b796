"""Roofline-term extraction from compiled dry-run artifacts.

Three terms per (arch × shape × mesh), per the brief:

    compute    = HLO_FLOPs_per_chip / peak_flops_bf16
    memory     = HLO_bytes_per_chip / hbm_bw
    collective = Σ collective_bytes × factor / ici_bw_per_link

The bandwidth/peak constants come from the :class:`~repro.launch.mesh.
BackendSpec` of the target chip (``launch.mesh.BACKEND_SPECS``, keyed by
``device_kind``), which every caller names: these terms are a model of
that chip, never a measurement of it.

``cost_analysis()`` is the per-device SPMD program, so its flops/bytes are
already per-chip. Collective bytes are parsed from the compiled HLO: the sum
of output-buffer sizes of every all-gather / all-reduce / reduce-scatter /
all-to-all / collective-permute, with the ring-bandwidth convention
all-reduce ≈ 2× payload (reduce-scatter + all-gather phases) and 1×
otherwise. The convention is held fixed across all measurements so §Perf
deltas are comparable.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict

from repro.launch.mesh import BackendSpec

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")

# one `dtype[shape]` buffer, e.g. f32[16,1024]{1,0}
_BUF_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _buf_bytes(dtype: str, shape_str: str) -> int:
    if dtype not in _DTYPE_BYTES:
        return 0
    n = 1
    if shape_str:
        for s in shape_str.split(","):
            n *= int(s)
    return n * _DTYPE_BYTES[dtype]


@dataclass
class CollectiveStats:
    bytes_by_kind: Dict[str, int] = field(default_factory=dict)
    count_by_kind: Dict[str, int] = field(default_factory=dict)

    @property
    def weighted_bytes(self) -> float:
        total = 0.0
        for kind, b in self.bytes_by_kind.items():
            total += b * (2.0 if kind == "all-reduce" else 1.0)
        return total


def parse_collectives(hlo_text: str) -> CollectiveStats:
    stats = CollectiveStats()
    for line in hlo_text.splitlines():
        kind = None
        for k in _COLLECTIVES:
            if f" {k}(" in line or f" {k}-start(" in line:
                kind = k
                break
        if kind is None:
            continue
        eq = line.find("=")
        opn = line.find(f" {kind}")
        if eq < 0 or opn < 0:
            continue
        out_part = line[eq + 1:opn]
        total = sum(_buf_bytes(d, s) for d, s in _BUF_RE.findall(out_part))
        stats.bytes_by_kind[kind] = stats.bytes_by_kind.get(kind, 0) + total
        stats.count_by_kind[kind] = stats.count_by_kind.get(kind, 0) + 1
    return stats


@dataclass
class Roofline:
    flops_per_chip: float
    hbm_bytes_per_chip: float
    collective_bytes: float
    compute_s: float
    memory_s: float
    collective_s: float
    dominant: str
    model_flops: float
    useful_ratio: float          # MODEL_FLOPS / (HLO flops × chips)
    chips: int

    def to_dict(self):
        return dict(self.__dict__)


def model_flops_for(cfg, shape_kind: str, tokens: float, local_steps: int = 1):
    """Analytic MODEL_FLOPS: 6·N_active·D for training, 2·N_active·D fwd."""
    n_active = cfg.num_active_params()
    if shape_kind == "train":
        return 6.0 * n_active * tokens * local_steps
    return 2.0 * n_active * tokens


def roofline_from_hlo(hc, *, chips: int, model_flops: float,
                      spec: BackendSpec) -> Roofline:
    """Preferred path: trip-count-aware HloCost from launch.hlo_analysis.
    ``spec`` holds the target chip's bandwidth/peak constants."""
    return _mk_roofline(hc.flops, hc.bytes, hc.weighted_coll_bytes,
                        chips=chips, model_flops=model_flops, spec=spec)


def roofline_from(cost: Dict, stats: CollectiveStats, *, chips: int,
                  model_flops: float, spec: BackendSpec) -> Roofline:
    flops = float(cost.get("flops", 0.0))
    hbm = float(cost.get("bytes accessed", 0.0))
    coll = stats.weighted_bytes
    return _mk_roofline(flops, hbm, coll, chips=chips,
                        model_flops=model_flops, spec=spec)


def _mk_roofline(flops, hbm, coll, *, chips: int, model_flops: float,
                 spec: BackendSpec) -> Roofline:
    compute_s = flops / spec.peak_flops_bf16
    memory_s = hbm / spec.hbm_bw
    collective_s = coll / spec.ici_bw_per_link
    terms = {"compute": compute_s, "memory": memory_s,
             "collective": collective_s}
    dominant = max(terms, key=terms.get)
    useful = model_flops / max(flops * chips, 1.0)
    return Roofline(flops_per_chip=flops, hbm_bytes_per_chip=hbm,
                    collective_bytes=coll, compute_s=compute_s,
                    memory_s=memory_s, collective_s=collective_s,
                    dominant=dominant, model_flops=model_flops,
                    useful_ratio=useful, chips=chips)
