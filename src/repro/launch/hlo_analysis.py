"""Post-compile HLO analysis: collective traffic + roofline terms.

collective_bytes is not in cost_analysis(), so we parse the optimized
(SPMD-partitioned) HLO text and sum per-op traffic with a ring model:

  all-gather         (n-1)/n * result_bytes
  reduce-scatter     (n-1)   * result_bytes      (~operand bytes)
  all-reduce         2(n-1)/n * result_bytes
  all-to-all         (n-1)/n * result_bytes
  collective-permute 1.0     * result_bytes

n = size of the first replica group of the op.

Hardware constants (TPU v5e-class target, per assignment):
  197 TFLOP/s bf16 per chip, 819 GB/s HBM, ~50 GB/s/link ICI.
"""
from __future__ import annotations

import re
from typing import Dict

PEAK_FLOPS = 197e12      # bf16 / chip
HBM_BW = 819e9           # bytes/s / chip
ICI_BW = 50e9            # bytes/s / link


_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16, "s4": 1, "u4": 1,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_COLL_RE = re.compile(
    r"=\s*(\([^)]*\)|\S+)\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(")
_GROUPS_RE = re.compile(r"replica_groups=\{\{([0-9,]+)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")

_RING_FACTOR = {
    "all-gather": lambda n: (n - 1) / n,
    "reduce-scatter": lambda n: float(n - 1),
    "all-reduce": lambda n: 2 * (n - 1) / n,
    "all-to-all": lambda n: (n - 1) / n,
    "collective-permute": lambda n: 1.0,
}


def _shape_bytes(text: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(text):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_stats(hlo_text: str) -> Dict[str, float]:
    """Per-kind op counts and ring-model bytes from optimized HLO."""
    stats: Dict[str, float] = {}
    counts: Dict[str, int] = {}
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        if "-done(" in line:
            continue  # paired with -start; count once
        shape_txt, kind = m.group(1), m.group(2)
        size = _shape_bytes(shape_txt)
        n = 1
        g = _GROUPS_RE.search(line)
        if g:
            n = len(g.group(1).split(","))
        else:
            gi = _GROUPS_IOTA_RE.search(line)
            if gi:
                n = int(gi.group(2))
        n = max(n, 2)
        traffic = size * _RING_FACTOR[kind](n)
        stats[kind] = stats.get(kind, 0.0) + traffic
        counts[kind + "_count"] = counts.get(kind + "_count", 0) + 1
    stats["total_bytes"] = sum(v for k, v in stats.items()
                               if not k.endswith("_count"))
    stats.update(counts)
    return stats


def roofline_terms(flops_per_device: float, bytes_per_device: float,
                   collective_bytes_per_device: float,
                   links_per_chip: float = 4.0) -> Dict[str, float]:
    """The three roofline terms in seconds/chip + dominant bottleneck."""
    compute_s = flops_per_device / PEAK_FLOPS
    memory_s = bytes_per_device / HBM_BW
    collective_s = collective_bytes_per_device / (ICI_BW * links_per_chip)
    terms = {"compute_s": compute_s, "memory_s": memory_s,
             "collective_s": collective_s}
    dom = max(terms, key=terms.get)
    bound = max(terms.values())
    terms["bottleneck"] = dom.replace("_s", "")
    terms["step_time_lower_bound_s"] = bound
    # roofline fraction: how much of the bound is the compute term
    terms["roofline_fraction"] = (compute_s / bound) if bound > 0 else 0.0
    return terms
