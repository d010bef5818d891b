"""Least work the scheduling semantics must do, from the cell's shapes.

Counted once per simulation, whatever a kernel does: every node's state
read (allocatable and requested cpu, memory, pod count, plus one topology
domain id and one matching-pod count per counted term), the updated
requested state written back, and each pod's row read (its group id) and
choice written. So the count does not change with how pods are split into
segments or how a kernel implements the work, and no kernel can read over
100% of its roofline. Operations: one evaluation of the two resource
scores per node (LeastAllocated: 2 subtractions, 2 multiplications, 2
divisions, 2 floors, an add, a halving and a floor; BalancedAllocation: 2
divisions, a subtraction, an absolute value, a subtraction, a
multiplication, a floor: 18 in all) and one comparison per pod.
"""

from __future__ import annotations

import json
import os
from typing import Sequence, Tuple

RESOURCES = 3          # cpu, memory, pod count
WORD = 4               # bytes of an f32 or i32
SCORE_OPS_PER_NODE = 18


def work(n_nodes: int, segments: Sequence[int], terms: int = 0,
         sims: int = 1) -> Tuple[float, float]:
    """(operations, bytes) for `sims` simulations of `n_nodes` nodes whose
    pods (all simulations together), split in any way into `segments`,
    ride one kernel. `terms`:
    counted pod terms (spread or anti-affinity) the kernel evaluates."""
    pods = sum(int(s) for s in segments)
    node_read = n_nodes * (2 * RESOURCES + 2 * terms) * WORD
    node_write = n_nodes * RESOURCES * WORD
    pod_rw = pods * 2 * WORD
    ops = sims * n_nodes * SCORE_OPS_PER_NODE + pods
    return float(ops), float(sims * (node_read + node_write) + pod_rw)


def peaks(kind: str) -> Tuple[float, float]:
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "peaks.json"), encoding="utf-8") as f:
        table = json.load(f)
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]["flops_per_s"], table[kind]["bytes_per_s"]


def roofline_pct(ops: float, nbytes: float, seconds: float,
                 kind: str) -> Tuple[float, str]:
    """(share of the roofline in %, the bound that governs)."""
    flops, bw = peaks(kind)
    t_ops, t_bytes = ops / flops, nbytes / bw
    bound = "bytes" if t_bytes >= t_ops else "operations"
    return 100.0 * max(t_ops, t_bytes) / seconds, bound
