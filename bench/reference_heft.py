"""Plain HEFT, the reference every schedule of the replan cell is held to.

It imports nothing of `src/` and takes plain data: task ids, their
dependencies and output sizes, a (T, N) float64 cost matrix, and the
cluster's node names and network rates.  It is HEFT (Topcuoglu et al.,
2002) with insertion-based slot search, written out scalar by scalar in
Python floats, with the semantics the scheduler documents:

  * topological order: Kahn's algorithm, always taking the
    lexicographically smallest ready task;
  * upward rank: the mean cost over the nodes (a left-to-right sum over
    the node order, divided by N) plus, over the successors, the largest
    of the mean pairwise transfer time of the task's output plus the
    successor's rank;
  * transfer time of `gb` from node a to node b: 0 on the same node, else
    gb * 8 / min(rate_a, rate_b) seconds;
  * placement in decreasing rank (ties in topological order): on every
    node, the task is ready at the later of its external ready time and
    each dependency's finish plus the transfer; it starts in the first gap
    of the node's busy intervals that holds it; the node with the least
    finish wins, the first such node in node order on a tie;
  * a node with availability t > 0 starts with the busy interval [0, t);
  * each node's order is its tasks by start time, ties in placement order.

`frontier_constraints` derives a replan's external ready times and node
availability from the execution state the way an online planner does:
finished tasks end when they ended; a running task is estimated to end at
the later of now and its start plus its predicted mean.
"""
from __future__ import annotations

from typing import Dict, List, Mapping, Optional, Sequence, Tuple

import numpy as np


def transfer_s(gb: float, a: int, b: int, rates: Sequence[float]) -> float:
    if a == b:
        return 0.0
    return gb * 8.0 / min(rates[a], rates[b])


def topo_order(uids: Sequence[str], deps: Mapping[str, Sequence[str]]
               ) -> List[str]:
    indeg = {u: len(deps[u]) for u in uids}
    succ: Dict[str, List[str]] = {u: [] for u in uids}
    for u in uids:
        for d in deps[u]:
            succ[d].append(u)
    ready = sorted(u for u in uids if indeg[u] == 0)
    out: List[str] = []
    while ready:
        u = ready.pop(0)
        out.append(u)
        for v in succ[u]:
            indeg[v] -= 1
            if indeg[v] == 0:
                ready.append(v)
        ready.sort()
    if len(out) != len(uids):
        raise ValueError("dependency cycle")
    return out


def _earliest(busy: List[Tuple[float, float]], ready: float,
              dur: float) -> float:
    start = ready
    for b0, b1 in busy:
        if start + dur <= b0:
            return start
        start = max(start, b1)
    return start


def heft(uids: Sequence[str], deps: Mapping[str, Sequence[str]],
         out_gb: Mapping[str, float], W, nodes: Sequence[str],
         rates: Sequence[float], ready=None, avail=None) -> dict:
    """Schedule `uids` (rows of `W`, in that order) on `nodes`.

    `ready`: optional (T, N) external ready times, rows as `W`; `avail`:
    optional (N,) node availability.  Returns {"assignment": uid -> node,
    "order": node -> [uid], "est": uid -> (start, finish)}."""
    row = {u: i for i, u in enumerate(uids)}
    w = [[float(v) for v in r] for r in np.asarray(W, np.float64)]
    n = len(nodes)
    order = topo_order(uids, deps)
    succ: Dict[str, List[str]] = {u: [] for u in uids}
    for u in uids:
        for d in deps[u]:
            succ[d].append(u)
    rank: Dict[str, float] = {}
    for u in reversed(order):
        w_avg = sum(w[row[u]]) / n
        best = 0.0
        for v in succ[u]:
            avg = sum(transfer_s(out_gb[u], a, b, rates)
                      for a in range(n) for b in range(n)) / (n * n)
            best = max(best, avg + rank[v])
        rank[u] = w_avg + best

    busy: List[List[Tuple[float, float]]] = [
        [(0.0, float(avail[j]))] if avail is not None and avail[j] > 0.0
        else [] for j in range(n)]
    node_of: Dict[str, int] = {}
    finish: Dict[str, float] = {}
    out = {"assignment": {}, "order": {name: [] for name in nodes},
           "est": {}}
    for u in sorted(order, key=lambda u: -rank[u]):
        best = None
        for j in range(n):
            r = 0.0 if ready is None else float(ready[row[u]][j])
            for d in deps[u]:
                r = max(r, finish[d] + transfer_s(out_gb[d], node_of[d], j,
                                                  rates))
            dur = w[row[u]][j]
            est = _earliest(busy[j], r, dur)
            if best is None or est + dur < best[1]:
                best = (est, est + dur, j)
        est, eft, j = best
        busy[j].append((est, eft))
        busy[j].sort()
        node_of[u] = j
        finish[u] = eft
        out["assignment"][u] = nodes[j]
        out["order"][nodes[j]].append(u)
        out["est"][u] = (est, eft)
    for name in nodes:
        out["order"][name].sort(key=lambda u: out["est"][u][0])
    return out


def frontier_constraints(frontier: Sequence[str],
                         deps: Mapping[str, Sequence[str]],
                         out_gb: Mapping[str, float], nodes: Sequence[str],
                         rates: Sequence[float], now: float,
                         finished: Mapping[str, Tuple[str, float]],
                         running: Sequence[Tuple[str, str, float]],
                         running_mean: Sequence[float]
                         ) -> Tuple[np.ndarray, np.ndarray]:
    """(ready (T, N), avail (N,)) of a replan at time `now`.

    `deps` holds each frontier task's full dependency list; `finished`
    maps uid -> (node, finish); `running` lists (uid, node, start) with
    `running_mean` the predicted runtime of each on its node."""
    col = {name: j for j, name in enumerate(nodes)}
    done_at = {u: (col[name], float(end))
               for u, (name, end) in finished.items()}
    avail = [float(now)] * len(nodes)
    for (u, name, start), mean in zip(running, running_mean):
        end = max(now, start + float(mean))
        done_at[u] = (col[name], end)
        avail[col[name]] = max(avail[col[name]], end)
    inside = set(frontier)
    ready = np.empty((len(frontier), len(nodes)))
    for i, u in enumerate(frontier):
        for j in range(len(nodes)):
            r = now
            for d in deps[u]:
                if d in inside:
                    continue
                a, end = done_at[d]
                r = max(r, end + transfer_s(out_gb[d], a, j, rates))
            ready[i, j] = r
    return ready, np.asarray(avail)


def mismatches(got_assignment: Mapping[str, str],
               got_order: Mapping[str, Sequence[str]],
               got_est: Mapping[str, Tuple[float, float]], want: dict
               ) -> int:
    """Tasks whose node, start or finish differ, plus nodes whose order
    differs, between a schedule and the reference's."""
    bad = 0
    for u, name in want["assignment"].items():
        if (got_assignment.get(u) != name
                or tuple(got_est.get(u, ())) != want["est"][u]):
            bad += 1
    bad += sum(1 for u in got_assignment if u not in want["assignment"])
    for name, uids in want["order"].items():
        if list(got_order.get(name, [])) != uids:
            bad += 1
    return bad
