"""Roofline-term extraction from compiled dry-run artifacts.

Three terms per (arch x shape x mesh), in seconds:

    compute    = HLO_FLOPs_per_device / peak_FLOP/s
    memory     = HLO_bytes_per_device / HBM_bw
    collective = collective_bytes_per_device / (links x link_bw)

FLOPs/bytes come from ``compiled.cost_analysis()`` (the SPMD module is the
per-device program).  Collective bytes are NOT in cost_analysis: we parse
the compiled HLO text and sum result-shape bytes of every all-gather /
all-reduce / reduce-scatter / all-to-all / collective-permute instruction
(all-reduce wire bytes ~ 2x result size ring-wise; we report the raw sum
and apply the 2(n-1)/n ring factor in the term).
"""

from __future__ import annotations

import json
import re
from dataclasses import asdict, dataclass, field
from typing import Dict, List, Optional, Tuple

from .mesh import peaks

# The chip the dry-run cells are costed for (a key of mesh.PEAKS).
TARGET_KIND = "TPU v5 lite"
_PEAKS = peaks(TARGET_KIND)

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

# `%x = bf16[1,2,3]{...} all-reduce(...)` or tuple results
_INSTR_RE = re.compile(
    r"=\s*(\(?)([a-z0-9\[\],{}\s/#*_:.-]+?)\)?\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"[\s(.]", re.IGNORECASE)
_SHAPE_RE = re.compile(r"(pred|[subf]\d+|bf16|c64|c128)\[([0-9,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


@dataclass
class CollectiveStats:
    bytes_by_kind: Dict[str, int] = field(default_factory=dict)
    count_by_kind: Dict[str, int] = field(default_factory=dict)

    @property
    def total_bytes(self) -> int:
        return sum(self.bytes_by_kind.values())


def collective_stats(hlo_text: str) -> CollectiveStats:
    stats = CollectiveStats()
    for line in hlo_text.splitlines():
        m = _INSTR_RE.search(line)
        if not m:
            continue
        kind = m.group(3).lower()
        if "-start" in line.split(kind)[1][:8]:
            pass  # async start counted; matching -done has no shape cost
        nbytes = sum(_shape_bytes(dt, dims)
                     for dt, dims in _SHAPE_RE.findall(m.group(2)))
        stats.bytes_by_kind[kind] = stats.bytes_by_kind.get(kind, 0) + nbytes
        stats.count_by_kind[kind] = stats.count_by_kind.get(kind, 0) + 1
    return stats


@dataclass
class Roofline:
    arch: str
    shape: str
    mesh: str
    chips: int
    flops_per_device: float
    bytes_per_device: float
    collective_bytes: float
    collective_breakdown: Dict[str, int]
    peak_memory_per_device: float
    model_flops_total: float

    @property
    def compute_s(self) -> float:
        return self.flops_per_device / _PEAKS["peak_flops_bf16"]

    @property
    def memory_s(self) -> float:
        return self.bytes_per_device / _PEAKS["hbm_bw"]

    @property
    def collective_s(self) -> float:
        # v5e: 4 ICI links/chip usable concurrently for ring collectives;
        # ring AR moves ~2x payload.  Conservative: 2 links effective.
        eff_bw = 2 * _PEAKS["ici_bw"]
        return 2.0 * self.collective_bytes / eff_bw

    @property
    def dominant(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def useful_flops_ratio(self) -> float:
        denom = self.flops_per_device * self.chips
        return (self.model_flops_total / denom) if denom else 0.0

    @property
    def bound_s(self) -> float:
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def roofline_fraction(self) -> float:
        """useful-compute time / achievable step time (higher = closer to
        the compute roofline)."""
        useful_s = ((self.model_flops_total / self.chips)
                    / _PEAKS["peak_flops_bf16"])
        return useful_s / self.bound_s if self.bound_s else 0.0

    def to_json(self) -> dict:
        d = asdict(self)
        d["device_kind"] = TARGET_KIND     # the chip the terms are costed for
        for k in ("compute_s", "memory_s", "collective_s", "dominant",
                  "useful_flops_ratio", "roofline_fraction"):
            d[k] = getattr(self, k)
        return d


def extract_cost(compiled) -> Tuple[float, float, float]:
    """(flops, bytes_accessed, peak_memory) from a compiled executable."""
    ca = compiled.cost_analysis()
    flops = float(ca.get("flops", 0.0))
    nbytes = float(ca.get("bytes accessed", 0.0))
    try:
        ma = compiled.memory_analysis()
        peak = float(getattr(ma, "temp_size_in_bytes", 0)
                     + getattr(ma, "argument_size_in_bytes", 0)
                     + getattr(ma, "output_size_in_bytes", 0)
                     - getattr(ma, "alias_size_in_bytes", 0))
    except Exception:
        peak = 0.0
    return flops, nbytes, peak


def model_flops(cfg, shape_cfg, n_params: int) -> float:
    """6·N·D (train) / 2·N·D (forward-only prefill) / 2·N per decoded token."""
    if shape_cfg.kind == "train":
        tokens = shape_cfg.global_batch * shape_cfg.seq_len
        return 6.0 * n_params * tokens
    if shape_cfg.kind == "prefill":
        tokens = shape_cfg.global_batch * shape_cfg.seq_len
        return 2.0 * n_params * tokens
    return 2.0 * n_params * shape_cfg.global_batch   # one token / sequence


def active_param_count(cfg, model) -> int:
    """N for MODEL_FLOPS: MoE counts only activated experts (6·N_active·D)."""
    from repro.models.param import count_params
    total = count_params(model.param_specs())
    if cfg.moe is None:
        return total
    m = cfg.moe
    n_moe_layers = cfg.num_layers - m.first_dense_layers
    per_expert = 3 * cfg.d_model * m.d_expert
    inactive = n_moe_layers * (m.num_experts - m.top_k) * per_expert
    return total - inactive
