"""Roofline analysis from AOT-compiled artifacts (no hardware execution).

Three terms per (arch × shape × mesh), from the dry-run, against the peaks
of the chip the program targets (:data:`PEAKS`, keyed by ``device_kind``):

    compute   = HLO_FLOPs          / peak bf16 FLOP/s
    memory    = HLO_bytes_accessed / peak HBM B/s
    collective= collective_bytes   / one ICI link's B/s

HLO_FLOPs / bytes come from ``compiled.cost_analysis()``.  collective_bytes
is parsed from the compiled HLO text: we sum the *result* byte sizes of every
all-reduce / all-gather / reduce-scatter / all-to-all / collective-permute op
(result size ≈ per-device payload actually moved onto the wire once; an
explicit, consistent convention — noted in EXPERIMENTS.md §Roofline).

MODEL_FLOPS = 6·N·D (dense) or 6·N_active·D (MoE) per trained token gives the
useful-compute ratio that catches remat/redundancy waste.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Any

import numpy as np

from repro.configs.base import ModelConfig



@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    bf16_flops: float          # FLOP/s
    hbm_bytes_per_s: float
    ici_link_bytes_per_s: float


# Published per-chip peaks, keyed by jax ``Device.device_kind``.
# TPU v5e — Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16,
# 16 GiB HBM at 819 GB/s, 1,600 Gbit/s of ICI per chip over 4 links
# (50 GB/s per link).
V5E = "TPU v5 lite"
PEAKS = {
    V5E: ChipPeaks(bf16_flops=197e12, hbm_bytes_per_s=819e9,
                   ici_link_bytes_per_s=50e9),
}


def peaks(device_kind: str) -> ChipPeaks:
    """The published peaks of ``device_kind``; an unknown chip raises."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "s32": 4, "u32": 4, "s64": 8, "u64": 8,
    "f8e4m3fn": 1, "f8e5m2": 1, "bf16": 2, "f16": 2, "f32": 4, "f64": 8,
    "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")

# `%x = bf16[4,8]{1,0} all-gather(...)` or tuple results
_OP_RE = re.compile(
    r"=\s*(?P<rtype>\([^)]*\)|[a-z0-9]+\[[0-9,]*\][^\s]*)\s+"
    r"(?P<op>" + "|".join(c + r"(?:-start|-done)?" for c in _COLLECTIVES) + r")\(",
)
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def collective_bytes(hlo_text: str) -> dict[str, float]:
    """Per-op-kind byte totals from HLO text (``lowered/compiled.as_text()``)."""
    out: dict[str, float] = {c: 0.0 for c in _COLLECTIVES}
    out["total"] = 0.0
    for m in _OP_RE.finditer(hlo_text):
        op = m.group("op")
        if op.endswith("-done"):
            continue  # counted at -start
        kind = next(c for c in _COLLECTIVES if op.startswith(c))
        nbytes = sum(_shape_bytes(d, s) for d, s in _SHAPE_RE.findall(m.group("rtype")))
        out[kind] += nbytes
        out["total"] += nbytes
    return out


def collective_counts(hlo_text: str) -> dict[str, int]:
    counts: dict[str, int] = {c: 0 for c in _COLLECTIVES}
    for m in _OP_RE.finditer(hlo_text):
        op = m.group("op")
        if op.endswith("-done"):
            continue
        counts[next(c for c in _COLLECTIVES if op.startswith(c))] += 1
    return counts


def model_flops(cfg: ModelConfig, n_tokens: int, kind: str = "train") -> float:
    """6·N_active·D for training, 2·N_active·D for inference forward."""
    n_active = active_params(cfg)
    mult = 6.0 if kind == "train" else 2.0
    return mult * n_active * n_tokens


def active_params(cfg: ModelConfig) -> float:
    """Parameters touched per token (MoE: top-k + shared experts only)."""
    total = cfg.n_params()
    if not cfg.n_experts:
        return float(total)
    gate = {"swiglu": 3, "geglu": 3, "relu2": 2, "gelu": 2}[cfg.mlp_type]
    per_expert = gate * cfg.d_model * cfg.d_ff_expert
    n_moe_layers = sum(cfg.moe_layer_flags)
    inactive = (cfg.n_experts - cfg.top_k) * per_expert * n_moe_layers
    return float(total - inactive)


@dataclasses.dataclass
class RooflineTerms:
    flops: float               # per-device HLO flops
    bytes_accessed: float      # per-device HLO bytes
    coll_bytes: float          # per-device collective payload bytes
    chips: int
    n_tokens: int
    model_flops_total: float   # 6·N·D (whole step, all chips)
    device_kind: str           # key into PEAKS

    @property
    def t_compute(self) -> float:
        return self.flops / peaks(self.device_kind).bf16_flops

    @property
    def t_memory(self) -> float:
        return self.bytes_accessed / peaks(self.device_kind).hbm_bytes_per_s

    @property
    def t_collective(self) -> float:
        return self.coll_bytes / peaks(self.device_kind).ici_link_bytes_per_s

    @property
    def bottleneck(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def useful_flops_ratio(self) -> float:
        """MODEL_FLOPS / total compiled FLOPs (all chips)."""
        total_hlo = self.flops * self.chips
        return self.model_flops_total / total_hlo if total_hlo else float("nan")

    def as_dict(self) -> dict[str, Any]:
        return {
            "flops_per_chip": self.flops,
            "bytes_per_chip": self.bytes_accessed,
            "coll_bytes_per_chip": self.coll_bytes,
            "t_compute_s": self.t_compute,
            "t_memory_s": self.t_memory,
            "t_collective_s": self.t_collective,
            "bottleneck": self.bottleneck,
            "model_flops_total": self.model_flops_total,
            "useful_flops_ratio": self.useful_flops_ratio,
            "chips": self.chips,
            "n_tokens": self.n_tokens,
            "device_kind": self.device_kind,
        }


def analyze(compiled, cfg: ModelConfig, *, chips: int, n_tokens: int,
            device_kind: str, kind: str = "train") -> RooflineTerms:
    """Roofline terms from the compiled artifact.

    Uses the trip-count-aware HLO cost model (``repro.launch.hlo_cost``):
    XLA's cost_analysis() counts scan/while bodies once, under-reporting any
    scanned program (layers, microbatches, CE chunks) by the trip count —
    verified exactly on synthetic programs (grad=3×fwd, remat=4×fwd ✓).
    """
    from repro.launch import hlo_cost

    hlo = compiled.as_text()
    hc = hlo_cost.analyze_hlo(hlo)
    return RooflineTerms(
        flops=hc.flops, bytes_accessed=hc.bytes, coll_bytes=hc.coll_bytes["total"],
        chips=chips, n_tokens=n_tokens,
        model_flops_total=model_flops(cfg, n_tokens, kind),
        device_kind=device_kind)
