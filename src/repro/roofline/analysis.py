"""Three-term roofline analysis from compiled dry-run artifacts.

Per the grading spec (CPU container, TPU v5e target):

    compute    = HLO_FLOPs        / (chips × 197e12 FLOP/s bf16)
    memory     = HLO_bytes        / (chips × 819e9  B/s HBM)
    collective = collective_bytes / (chips × 50e9   B/s per ICI link)

``cost_analysis()`` supplies HLO_FLOPs and HLO bytes-accessed.  Collective
bytes are parsed out of the optimized HLO text: every all-gather /
all-reduce / reduce-scatter / all-to-all / collective-permute op contributes
its *wire* bytes per participating chip, using the standard ring-algorithm
cost per op kind (group size g parsed from replica_groups):

    all-gather        (g-1)/g × result_bytes
    reduce-scatter    (g-1)/g × operand_bytes
    all-reduce        2 (g-1)/g × operand_bytes   (RS + AG)
    all-to-all        (g-1)/g × operand_bytes
    collective-permute  operand_bytes

Cross-pod (DCN) collectives are reported separately: a replica group whose
members span pods (device id stride ≥ pod size) pays the DCN, not ICI —
this is what the hierarchical/compressed cross-pod modes move.

MODEL_FLOPS (6·N·D dense / 6·N_active·D MoE) anchors the useful-compute
ratio; HLO_FLOPs below cost_analysis's own numbers signals remat recompute
or dispatch overhead.
"""

from __future__ import annotations

import dataclasses
import json
import re
from typing import Any, Dict, List, Optional, Tuple

__all__ = [
    "HW",
    "parse_collectives",
    "roofline",
    "model_flops",
]

#: TPU v5e hardware constants (grading spec).
HW = {
    "peak_flops": 197e12,   # bf16 FLOP/s per chip
    "hbm_bw": 819e9,        # B/s per chip
    "ici_bw": 50e9,         # B/s per link per chip
    "hbm_bytes": 16e9,      # HBM capacity per chip
}

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_COLL_RE = re.compile(
    r"(?P<result>\S+)\s*=\s*(?P<rtype>[\w\[\],{}() ]+?)\s*"
    r"(?P<op>all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\("
)

_SHAPE_RE = re.compile(r"(?P<dt>f64|f32|f16|bf16|f8e4m3fn|f8e5m2|s64|u64|s32|u32|s16|u16|s8|u8|pred|c64|c128)\[(?P<dims>[\d,]*)\]")

_GROUPS_RE = re.compile(r"replica_groups=\{(?P<body>[^}]*(?:\}[^}]*)*?)\}\}|replica_groups=\[(?P<dims>[\d,]+)\]")


def _shape_bytes(type_str: str) -> int:
    """Sum byte sizes of every tensor shape in an HLO type string (handles tuples)."""
    total = 0
    for m in _SHAPE_RE.finditer(type_str):
        dims = m.group("dims")
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[m.group("dt")]
    return total


@dataclasses.dataclass
class CollectiveOp:
    kind: str
    wire_bytes_per_chip: float   # ring-cost bytes this op moves per chip
    group_size: int
    cross_pod: bool
    line: str = ""


def _group_info(line: str, n_devices: int, pod_size: int) -> Tuple[int, bool]:
    """(group size, crosses pod boundary) from replica_groups annotation."""
    m = re.search(r"replica_groups=\{\{([^}]*)\}", line)
    if m:
        members = [int(x) for x in m.group(1).split(",") if x.strip()]
        size = max(len(members), 1)
        cross = len({mm // pod_size for mm in members}) > 1 if pod_size else False
        return size, cross
    # iota form: replica_groups=[N,M]<=[dims](T(perm))? — N groups of M,
    # members = rows of reshape(transpose(iota(dims), perm), (N, M)).
    # Materialize the mapping exactly (cheap at fleet sizes) — stride
    # heuristics miss transposed multi-axis groups.
    m = re.search(r"replica_groups=\[(\d+),(\d+)\]<=\[([\d,]+)\](T\(([\d,]+)\))?", line)
    if m:
        import numpy as _np

        n, size = int(m.group(1)), int(m.group(2))
        dims = [int(x) for x in m.group(3).split(",")]
        ids = _np.arange(int(_np.prod(dims))).reshape(dims)
        if m.group(5):
            perm = [int(x) for x in m.group(5).split(",")]
            ids = _np.transpose(ids, perm)
        groups = ids.reshape(n, size)
        cross = False
        if pod_size:
            cross = bool((_np.ptp(groups // pod_size, axis=1) > 0).any())
        return size, cross
    return n_devices, False


def parse_collectives(
    hlo_text: str, *, n_devices: int, pod_size: int = 0
) -> List[CollectiveOp]:
    """Extract every collective op with its per-chip wire bytes.

    Each op is weighted by its region's while-loop trip-count product
    (:func:`repro.roofline.hlo_loops.region_multipliers`) — a collective
    inside a 13-unit scan really crosses the wire 13×.
    """
    from .hlo_loops import region_multipliers, split_regions

    regions = split_regions(hlo_text)
    mults = region_multipliers(hlo_text)
    out: List[CollectiveOp] = []
    for rname, lines in regions.items():
        weight = mults.get(rname, 1)
        seen_starts = set()
        for line in lines:
            m = _COLL_RE.search(line)
            if not m:
                continue
            op = m.group("op")
            if f"{op}-done" in line:
                continue  # the -start line carries the shapes
            name = line.split("=", 1)[0].strip()
            if name in seen_starts:
                continue
            seen_starts.add(name)
            # result type precedes the op name on the line
            type_str = line.split("=", 1)[1].split(op, 1)[0]
            result_bytes = _shape_bytes(type_str)
            # operand types: result matches operand for AR/CP; for AG
            # result = g × operand; for RS operand = g × result.
            g, cross = _group_info(line, n_devices, pod_size)
            g = max(g, 1)
            if op == "all-gather":
                wire = (g - 1) / g * result_bytes
            elif op == "reduce-scatter":
                wire = (g - 1) * result_bytes          # operand = g × result
            elif op == "all-reduce":
                wire = 2 * (g - 1) / g * result_bytes  # RS + AG of operand(=result)
            elif op == "all-to-all":
                wire = (g - 1) / g * result_bytes
            else:  # collective-permute
                wire = result_bytes
            out.append(
                CollectiveOp(op, float(wire) * weight, g, cross, line[:160])
            )
    return out


def model_flops(n_params: int, n_active: int, tokens: int, *, kind: str = "train") -> float:
    """6·N·D (train) / 2·N·D (inference) with N = active params."""
    mult = 6.0 if kind == "train" else 2.0
    return mult * n_active * tokens


def roofline(
    *,
    cost: Dict[str, float],
    hlo_text: str,
    n_devices: int,
    pod_size: int = 0,
    model_flops_total: float = 0.0,
    analytic_flops_total: Optional[float] = None,
    analytic_bytes_per_chip: Optional[float] = None,
    dcn_bw: float = 25e9,
) -> Dict[str, Any]:
    """Assemble the three-term roofline report for one (arch × shape × mesh).

    cost_analysis counts while bodies once (tests/test_roofline.py proves
    it), so when the analytic totals are supplied they drive the compute and
    memory terms; the raw compiled numbers are reported alongside.  The
    collective term is always HLO-derived with per-region trip correction.
    """
    flops_raw = float(cost.get("flops", 0.0))
    bytes_raw = float(cost.get("bytes accessed", 0.0))
    colls = parse_collectives(hlo_text, n_devices=n_devices, pod_size=pod_size)
    ici_bytes = sum(c.wire_bytes_per_chip for c in colls if not c.cross_pod)
    dcn_bytes = sum(c.wire_bytes_per_chip for c in colls if c.cross_pod)

    # compiled SPMD modules are per-device programs: cost_analysis flops /
    # bytes and all HLO shapes are already per-chip (verified against a
    # hand-counted sharded matmul).
    flops_chip = (
        analytic_flops_total / n_devices if analytic_flops_total else flops_raw
    )
    bytes_chip = analytic_bytes_per_chip if analytic_bytes_per_chip else bytes_raw
    t_compute = flops_chip / HW["peak_flops"]
    t_memory = bytes_chip / HW["hbm_bw"]
    t_coll = ici_bytes / HW["ici_bw"] + dcn_bytes / dcn_bw

    terms = {"compute": t_compute, "memory": t_memory, "collective": t_coll}
    bottleneck = max(terms, key=terms.get)
    useful = (
        model_flops_total / (flops_chip * n_devices) if flops_chip else 0.0
    )
    return {
        "flops_per_chip": flops_chip,
        "bytes_per_chip": bytes_chip,
        "flops_raw_costanalysis": flops_raw,
        "bytes_raw_costanalysis": bytes_raw,
        "ici_bytes_per_chip": ici_bytes,
        "dcn_bytes_per_chip": dcn_bytes,
        "n_collectives": len(colls),
        "collective_kinds": {
            k: sum(1 for c in colls if c.kind == k)
            for k in ("all-gather", "all-reduce", "reduce-scatter", "all-to-all", "collective-permute")
        },
        "t_compute_s": t_compute,
        "t_memory_s": t_memory,
        "t_collective_s": t_coll,
        "bottleneck": bottleneck,
        "model_flops": model_flops_total,
        "useful_flops_ratio": useful,
        "roofline_fraction": (
            max(t_compute, 1e-30) / max(t_compute, t_memory, t_coll)
        ),
    }
