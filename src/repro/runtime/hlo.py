"""Roofline analysis from compiled XLA artifacts (no hardware needed).

Three terms per (arch × shape × mesh), per the methodology in the brief:

    compute    = HLO_FLOPs / (chips × peak_FLOP/s)
    memory     = HLO_bytes / (chips × HBM_bw)
    collective = collective_bytes / (chips × link_bw)

``cost_analysis()`` supplies FLOPs/bytes (whole-program, i.e. summed over
devices for SPMD — divided by chip count here). Collective bytes are
parsed from the optimized HLO text: the summed result sizes of all-gather /
all-reduce / reduce-scatter / all-to-all / collective-permute ops, weighted
by ring-algorithm factors (all-reduce ≈ 2x its payload on a ring).

Hardware model (TPU v5e-class, from the brief):
    197 TFLOP/s bf16 per chip, 819 GB/s HBM, ~50 GB/s/link ICI.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field

import numpy as np

PEAK_FLOPS = 197e12        # bf16 / chip
HBM_BW = 819e9             # bytes/s / chip
ICI_BW = 50e9              # bytes/s / link

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

# result-bytes multipliers approximating ring-algorithm wire traffic
_COLLECTIVE_FACTORS = {
    "all-gather": 1.0,
    "all-reduce": 2.0,
    "reduce-scatter": 1.0,
    "all-to-all": 1.0,
    "collective-permute": 1.0,
}

_SHAPE_RE = re.compile(r"\b(pred|[sufbc]\d+|bf16)\[([0-9,]*)\]")
_OP_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(.+?)\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(",
)


def _shape_bytes(shape_str: str) -> int:
    """Sum byte sizes of every tensor literal in an HLO type string."""
    total = 0
    for dt, dims in _SHAPE_RE.findall(shape_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


@dataclass
class CollectiveStats:
    bytes_by_kind: dict[str, int] = field(default_factory=dict)
    count_by_kind: dict[str, int] = field(default_factory=dict)
    weighted_bytes: float = 0.0

    @property
    def total_bytes(self) -> int:
        return sum(self.bytes_by_kind.values())


def parse_collectives(hlo_text: str) -> CollectiveStats:
    """Sum result sizes of collective ops in (optimized) HLO text.

    ``-done`` ops are skipped so async (start/done) pairs count once.
    """
    stats = CollectiveStats()
    for line in hlo_text.splitlines():
        if "-done(" in line:
            continue
        m = _OP_RE.match(line)
        if not m:
            continue
        result_type, kind = m.group(1), m.group(2)
        b = _shape_bytes(result_type)
        if kind == "all-gather" and "-start" in line:
            pass  # result of start op includes the full gathered buffer
        stats.bytes_by_kind[kind] = stats.bytes_by_kind.get(kind, 0) + b
        stats.count_by_kind[kind] = stats.count_by_kind.get(kind, 0) + 1
        stats.weighted_bytes += b * _COLLECTIVE_FACTORS[kind]
    return stats


@dataclass
class Roofline:
    flops: float               # whole-program HLO FLOPs
    hbm_bytes: float           # whole-program bytes accessed
    collective_bytes: float    # weighted wire bytes
    chips: int
    compute_s: float = 0.0
    memory_s: float = 0.0
    collective_s: float = 0.0
    bottleneck: str = ""
    model_flops: float = 0.0   # analytic 6ND / 2ND
    useful_ratio: float = 0.0  # model_flops / HLO flops

    def finalize(self) -> "Roofline":
        self.compute_s = self.flops / (self.chips * PEAK_FLOPS)
        self.memory_s = self.hbm_bytes / (self.chips * HBM_BW)
        self.collective_s = self.collective_bytes / (self.chips * ICI_BW)
        terms = {
            "compute": self.compute_s,
            "memory": self.memory_s,
            "collective": self.collective_s,
        }
        self.bottleneck = max(terms, key=terms.get)
        if self.flops:
            self.useful_ratio = self.model_flops / self.flops
        return self

    def step_time_bound_s(self) -> float:
        return max(self.compute_s, self.memory_s, self.collective_s)

    def as_dict(self) -> dict:
        return {
            "flops": self.flops,
            "hbm_bytes": self.hbm_bytes,
            "collective_bytes": self.collective_bytes,
            "chips": self.chips,
            "compute_s": self.compute_s,
            "memory_s": self.memory_s,
            "collective_s": self.collective_s,
            "bottleneck": self.bottleneck,
            "model_flops": self.model_flops,
            "useful_ratio": self.useful_ratio,
        }


def cost_of(compiled) -> dict:
    """compiled.cost_analysis() as a plain dict."""
    return dict(compiled.cost_analysis())


def analyze(
    compiled,
    *,
    chips: int,
    model_flops: float = 0.0,
    hlo_text: str | None = None,
) -> Roofline:
    ca = cost_of(compiled)
    flops = float(ca.get("flops", 0.0))
    hbm = float(ca.get("bytes accessed", 0.0))
    text = hlo_text if hlo_text is not None else compiled.as_text()
    coll = parse_collectives(text)
    return Roofline(
        flops=flops,
        hbm_bytes=hbm,
        collective_bytes=coll.weighted_bytes,
        chips=chips,
        model_flops=model_flops,
    ).finalize()


def memory_summary(compiled) -> dict:
    """Per-device memory from compiled.memory_analysis() (best effort)."""
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return {}
    out = {}
    for k in (
        "argument_size_in_bytes",
        "output_size_in_bytes",
        "temp_size_in_bytes",
        "alias_size_in_bytes",
        "generated_code_size_in_bytes",
    ):
        v = getattr(ma, k, None)
        if v is not None:
            out[k] = int(v)
    if out:
        # live bytes: args + outputs + temps - aliased (donated) buffers
        out["total_bytes"] = (
            out.get("argument_size_in_bytes", 0)
            + out.get("output_size_in_bytes", 0)
            + out.get("temp_size_in_bytes", 0)
            - out.get("alias_size_in_bytes", 0)
        )
    return out


def model_flops_train(n_params: int, tokens: int) -> float:
    return 6.0 * n_params * tokens


def model_flops_forward(n_params: int, tokens: int) -> float:
    return 2.0 * n_params * tokens
