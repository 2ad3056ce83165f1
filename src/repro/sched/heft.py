"""HEFT (Heterogeneous Earliest-Finish-Time) [Topcuoglu et al. 2002] with
insertion-based slot search — the scheduling consumer of Lotaru's
predictions (Section 8.1): the schedule types and the plain reference.

  * `Schedule` / `ScheduledTask` — what every scheduler returns;
  * `comm_seconds` / `comm_structure` — the data-transfer charge between
    two nodes, scalar and as the pairwise arrays the fused engine uses;
  * `heft_schedule_reference` — plain scalar HEFT over a callable
    `(uid, node) -> seconds`: the oracle the production engine
    (`sched.fused.fused_heft_schedule`) is held to bitwise.

The production engine is arithmetic-compatible with the reference on
purpose: sums are sequential (`cumsum`), communication terms use the exact
`comm_seconds` expression elementwise, and ties resolve to the first node
in list order — so the parity tests can assert bitwise-equal schedules.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from repro.core.microbench import NodeSpec
from repro.workflow.dag import WorkflowDAG


@dataclass
class ScheduledTask:
    uid: str
    node: str
    est: float     # estimated (predicted) start
    eft: float     # estimated finish


@dataclass
class Schedule:
    assignment: Dict[str, str] = field(default_factory=dict)   # uid -> node
    order: Dict[str, List[str]] = field(default_factory=dict)  # node -> uids
    est: Dict[str, Tuple[float, float]] = field(default_factory=dict)

    @property
    def predicted_makespan(self) -> float:
        return max((f for _, f in self.est.values()), default=0.0)


def comm_seconds(gb: float, a: NodeSpec, b: NodeSpec) -> float:
    if a.name == b.name:
        return 0.0
    gbps = min(getattr(a, "net_gbps", 1.0), getattr(b, "net_gbps", 1.0))
    return gb * 8.0 / gbps


def comm_structure(nodes: List[NodeSpec]) -> Tuple[np.ndarray, np.ndarray]:
    """(same, gbps_min) pairwise arrays: comm_seconds(gb, a, b) == 0 where
    `same`, (gb * 8.0) / gbps_min elsewhere — the elementwise form the
    fused engine charges in its ranks and placements."""
    net = np.asarray([float(getattr(n, "net_gbps", 1.0)) for n in nodes])
    gbps_min = np.minimum.outer(net, net)
    same = np.asarray([[a.name == b.name for b in nodes] for a in nodes])
    return same, gbps_min


def heft_schedule_reference(dag: WorkflowDAG, nodes: List[NodeSpec],
                            predict: Callable[[str, NodeSpec], float],
                            ready_at=None,
                            node_available: Optional[Dict[str, float]] = None
                            ) -> Schedule:
    """Plain scalar HEFT: one predict() call per (task, node) in the rank
    pass and another per placement candidate.  The bit-parity oracle for
    the fused engine and the baseline of `benchmarks/replan_latency.py` —
    not a serving path.  `ready_at` and `node_available` take the dict /
    callable forms `sched.fused.fused_heft_schedule` takes."""
    succ = dag.successors()
    order = dag.topo_order()
    w_avg = {u: sum(predict(u, n) for n in nodes) / len(nodes) for u in order}

    # upward rank
    rank: Dict[str, float] = {}
    for u in reversed(order):
        best = 0.0
        t = dag.tasks[u]
        for v in succ[u]:
            avg_comm = sum(comm_seconds(t.output_gb, a, b)
                           for a in nodes for b in nodes) / (len(nodes) ** 2)
            best = max(best, avg_comm + rank[v])
        rank[u] = w_avg[u] + best

    sched = Schedule(order={n.name: [] for n in nodes})
    node_by_name = {n.name: n for n in nodes}
    slots: Dict[str, List[Tuple[float, float]]] = {
        n.name: ([(0.0, node_available[n.name])]
                 if node_available and node_available.get(n.name, 0.0) > 0.0
                 else []) for n in nodes}
    finish: Dict[str, float] = {}

    for u in sorted(order, key=lambda u: -rank[u]):
        t = dag.tasks[u]
        best = None
        for n in nodes:
            if ready_at is None:
                ready = 0.0
            elif callable(ready_at):
                ready = ready_at(u, n)
            else:
                ready = ready_at.get(u, 0.0)
            for d in t.deps:
                dn = node_by_name[sched.assignment[d]]
                ready = max(ready, finish[d] +
                            comm_seconds(dag.tasks[d].output_gb, dn, n))
            dur = predict(u, n)
            est = _earliest_slot(slots[n.name], ready, dur)
            if best is None or est + dur < best[1]:
                best = (est, est + dur, n.name)
        est, eft, name = best
        slots[name].append((est, eft))
        slots[name].sort()
        sched.assignment[u] = name
        sched.order[name].append(u)
        sched.est[u] = (est, eft)
        finish[u] = eft
    for name in sched.order:
        sched.order[name].sort(key=lambda u: sched.est[u][0])
    return sched


def _earliest_slot(busy: List[Tuple[float, float]], ready: float,
                   dur: float) -> float:
    """insertion policy: earliest gap >= dur after `ready`."""
    start = ready
    for (b0, b1) in busy:
        if start + dur <= b0:
            return start
        start = max(start, b1)
    return start
