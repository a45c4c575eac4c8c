"""Roofline analysis from compiled dry-run artifacts.

Hardware model (task sheet): TPU v5e — 197 TFLOP/s bf16 per chip, 819 GB/s
HBM, ~50 GB/s/link ICI.

Terms (per chip; XLA SPMD programs are per-device, so cost_analysis numbers
are already per-chip):

  compute_t    = flops / 197e12
  memory_t     = hbm_bytes / 819e9
  collective_t = ici_link_bytes / 50e9

XLA's HloCostAnalysis counts while-loop bodies ONCE regardless of trip count,
so the dry-run compiles two fully-unrolled *probe* programs (1 period and 2
periods of layers) and linearly extrapolates:

  total(T) = probe1 + (T - 1) * (probe2 - probe1)

which is exact for costs linear in depth (all per-layer costs are; embedding /
head / optimizer bookkeeping live in the base term).  Collective link-bytes
come from parsing the compiled probe HLO text: per op, output bytes scaled by
the ring-schedule factor for its replica-group size.
"""
from __future__ import annotations

import dataclasses
import json
import re
from typing import Any

PEAK_FLOPS = 197e12     # bf16 per chip
HBM_BW = 819e9          # bytes/s per chip
ICI_BW = 50e9           # bytes/s per link

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

# e.g.:  %ag = bf16[16,288,512]{2,1,0} all-gather(%p), replica_groups=...
_OP_RE = re.compile(
    r"=\s*(?:\()?([a-z0-9]+)\[([0-9,]*)\][^\s]*\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(")
_GROUPS_RE = re.compile(r"replica_groups=\{\{([0-9,]+)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def _group_size(line: str) -> int:
    m = _GROUPS_IOTA_RE.search(line)
    if m:  # replica_groups=[G,S]<=[N] — G groups of size S
        return int(m.group(2))
    m = _GROUPS_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    return 2


def _link_bytes(kind: str, out_bytes: int, n: int) -> float:
    """Per-device bytes crossing ICI under ring schedules."""
    if n <= 1:
        return 0.0
    if kind == "all-gather":       # output = gathered size
        return out_bytes * (n - 1) / n
    if kind == "all-reduce":       # reduce-scatter + all-gather
        return 2.0 * out_bytes * (n - 1) / n
    if kind == "reduce-scatter":   # output = shard; input moved = out*n
        return out_bytes * (n - 1)
    if kind == "all-to-all":
        return out_bytes * (n - 1) / n
    if kind == "collective-permute":
        return float(out_bytes)
    return float(out_bytes)


def parse_collectives(hlo_text: str) -> dict:
    """Sum per-device collective link bytes by op kind from compiled HLO.

    Skips '-done' lines (the '-start' already carries the shape) and the
    while-loop caveat is handled upstream (probes are fully unrolled).
    """
    per_kind: dict[str, float] = {k: 0.0 for k in _COLLECTIVES}
    counts: dict[str, int] = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        if "-done(" in line:
            continue
        m = _OP_RE.search(line)
        if not m:
            continue
        dtype, dims, kind = m.group(1), m.group(2), m.group(3)
        out_b = _shape_bytes(dtype, dims)
        n = _group_size(line)
        per_kind[kind] += _link_bytes(kind, out_b, n)
        counts[kind] += 1
    total = sum(per_kind.values())
    return {"per_kind_bytes": per_kind, "counts": counts,
            "total_link_bytes": total}


def cost_analysis_dict(compiled) -> dict:
    """``Compiled.cost_analysis()``, or ``{}`` where the backend gives
    none."""
    return compiled.cost_analysis() or {}


@dataclasses.dataclass
class ProbeCost:
    flops: float
    bytes_accessed: float
    collective_bytes: float
    collective_detail: dict

    @staticmethod
    def from_compiled(compiled) -> "ProbeCost":
        ca = cost_analysis_dict(compiled)
        coll = parse_collectives(compiled.as_text())
        return ProbeCost(
            flops=float(ca.get("flops", 0.0)),
            bytes_accessed=float(ca.get("bytes accessed", 0.0)),
            collective_bytes=coll["total_link_bytes"],
            collective_detail=coll,
        )


def extrapolate(p1: ProbeCost, p2: ProbeCost, n_periods: int) -> dict:
    """total(T) = p1 + (T-1) * max(0, p2 - p1).

    The marginal is clamped at zero: XLA occasionally optimizes the 2-period
    probe harder than the 1-period one (fusion/layout choices differ), which
    would otherwise extrapolate to negative cost on shallow-dominated
    programs (decode)."""
    t = n_periods

    def lin(a, b):
        return a + (t - 1) * max(0.0, b - a)

    per_kind = {
        k: lin(p1.collective_detail["per_kind_bytes"][k],
               p2.collective_detail["per_kind_bytes"][k])
        for k in _COLLECTIVES}
    return {
        "flops": lin(p1.flops, p2.flops),
        "bytes_accessed": lin(p1.bytes_accessed, p2.bytes_accessed),
        "collective_bytes": lin(p1.collective_bytes, p2.collective_bytes),
        "collective_per_kind": per_kind,
    }


def roofline_terms(costs: dict) -> dict:
    ct = costs["flops"] / PEAK_FLOPS
    mt = costs["bytes_accessed"] / HBM_BW
    xt = costs["collective_bytes"] / ICI_BW
    dom = max(("compute", ct), ("memory", mt), ("collective", xt),
              key=lambda kv: kv[1])[0]
    return {
        "compute_s": ct,
        "memory_s": mt,
        "collective_s": xt,
        "bottleneck": dom,
        "step_s_lower_bound": max(ct, mt, xt),
    }


def model_flops(cfg, shape, *, n_chips: int) -> dict:
    """MODEL_FLOPS = 6*N*D (dense) / 6*N_active*D (MoE), D = tokens/step."""
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        mult = 6
    elif shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        mult = 2
    else:
        tokens = shape.global_batch  # one token per sequence
        mult = 2
    n_active = cfg.n_active_params()
    return {
        "model_flops_total": mult * n_active * tokens,
        "model_flops_per_chip": mult * n_active * tokens / n_chips,
        "n_params": cfg.n_params(),
        "n_active_params": n_active,
    }


def summarize(cfg, shape, *, n_chips: int, probe1: ProbeCost,
              probe2: ProbeCost, n_periods: int, memory_analysis: str,
              extra: dict | None = None) -> dict:
    costs = extrapolate(probe1, probe2, n_periods)
    terms = roofline_terms(costs)
    mf = model_flops(cfg, shape, n_chips=n_chips)
    useful = mf["model_flops_per_chip"] / max(costs["flops"], 1.0)
    return {
        "arch": cfg.name,
        "shape": shape.name,
        "n_chips": n_chips,
        "costs_per_chip": costs,
        "roofline": terms,
        "model_flops": mf,
        "useful_flops_ratio": useful,
        "memory_analysis": memory_analysis,
        **(extra or {}),
    }
