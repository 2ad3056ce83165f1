"""Replan-latency benchmark: what one in-flight replanning round costs on
the host CPU, swept over problem sizes.

Scenario: a frontier replan — the round `online.rescheduler` runs on
every drift event.  Two implementations of the same decision:

  * scalar-callback — the pre-plane path: `heft_schedule_reference` pulls
    every (task, node) runtime through its own `PredictionService` call,
    so one replan costs O(T x N) store syncs + gathers + predictive
    dispatches (plus extra calls per placement candidate).  Only timed at
    the smallest size — it is minutes at fleet scale;
  * fused — the production engine behind the resident plane
    (`sched.fused.FusedPlane.schedule`): posterior rows and the cost view
    stay resident, only dirty rows re-predict, and the candidate-EFT
    sweep is NumPy below `_JIT_MIN_CELLS` cells and one jitted dispatch
    above.

The schedules must be bit-identical, asserted at every size before
anything is timed: against the scalar reference where it is affordable,
and the jit sweep against the NumPy sweep beyond.  All times are host
wall-clock on the machine that runs this script, not device times.

  PYTHONPATH=src python -m benchmarks.replan_latency
"""
from __future__ import annotations

import time

import numpy as np

from repro.core.microbench import simulate_microbench
from repro.core.predictor import LotaruPredictor
from repro.core.traces import TraceRow
from repro.online import PredictionService
from repro.online.events import PredictionQuery
from repro.sched import fused
from repro.sched.cluster import LOCAL, TARGET_MACHINES
from repro.sched.fused import FusedPlane
from repro.sched.heft import heft_schedule_reference
from repro.workflow.dag import TaskInstance, WorkflowDAG
from repro.workflow.simulator import random_cluster

TASK_TYPES = ("bwa", "idx", "dedup", "qc", "merge", "report")


def _build(n_tasks: int, n_nodes: int, seed: int):
    rng = np.random.default_rng(seed)
    traces = []
    for j, t in enumerate(TASK_TYPES):
        traces += [TraceRow("wf", t, "local", s,
                            2.0 + j + (15.0 + 6 * j) * s)
                   for s in np.linspace(0.05, 0.4, 6)]
    lot = LotaruPredictor("G",
                          local_bench=simulate_microbench(LOCAL, 1))
    lot.fit(traces)
    nodes = random_cluster(rng, list(TARGET_MACHINES), n_nodes=n_nodes)
    benches = {n.name: simulate_microbench(n, 1) for n in nodes}
    svc = PredictionService(lot, benches)
    dag = WorkflowDAG("replan")
    for i in range(n_tasks):
        deps = [f"t{j}" for j in range(i)
                if rng.random() < min(3.0 / max(i, 1), 0.5)]
        dag.add(TaskInstance(f"t{i}", TASK_TYPES[i % len(TASK_TYPES)],
                             "replan", float(rng.uniform(0.05, 4.0)),
                             output_gb=float(rng.uniform(0.0, 2.0)),
                             deps=deps))
    return dag, nodes, svc


def reference_schedule(dag, nodes, mat, quantile=None):
    """`heft_schedule_reference` through a callable over the cost matrix
    `mat` gives at `quantile` — the costs the fused engine schedules on."""
    uids = list(dag.tasks)
    W = mat.costs(uids, [n.name for n in nodes], quantile=quantile)
    row = {u: i for i, u in enumerate(uids)}
    col = {n.name: j for j, n in enumerate(nodes)}
    return heft_schedule_reference(
        dag, nodes, lambda u, n: float(W[row[u], col[n.name]]))


def numpy_sweep_schedule(dag, nodes, mat, quantile=None):
    """`fused_heft_schedule` held to its NumPy sweep at any size: the
    oracle of the jit sweep where the scalar reference takes minutes."""
    jit_min = fused._JIT_MIN_CELLS
    fused._JIT_MIN_CELLS = float("inf")
    try:
        return fused.fused_heft_schedule(dag, nodes, mat, quantile=quantile)
    finally:
        fused._JIT_MIN_CELLS = jit_min


def same_schedule(a, b) -> bool:
    return (a.assignment == b.assignment and a.order == b.order
            and a.est == b.est)


SIZES = ((100, 20), (500, 50), (1000, 100))
SCALAR_MAX_CELLS = 100 * 20       # the O(T x N)-dispatch path is minutes
                                  # beyond this


def _one_size(n_tasks: int, n_nodes: int, seed: int, repeats: int) -> dict:
    dag, nodes, svc = _build(n_tasks, n_nodes, seed)
    plane = FusedPlane(svc, nodes, dag=dag)

    def fused_round():
        return plane.schedule(dag)

    fus = fused_round()                       # warms + compiles the sweep
    row = {"n_tasks": n_tasks, "n_nodes": n_nodes,
           "predicted_makespan_s": fus.predicted_makespan}
    if n_tasks * n_nodes <= SCALAR_MAX_CELLS:
        def scalar_predict(uid, node):
            t = dag.tasks[uid]
            return float(svc.predict_batch(
                [PredictionQuery(t.task_name, node.name, t.input_gb)])[0][0])
        ref = heft_schedule_reference(dag, nodes, scalar_predict)
        row["oracle"] = "reference"
        row["bit_parity"] = same_schedule(fus, ref)
        assert row["bit_parity"], "fused replan diverged from the reference"
        row["scalar_callback_s"] = min(
            _timed(lambda: heft_schedule_reference(dag, nodes,
                                                   scalar_predict))
            for _ in range(repeats))
    else:
        row["oracle"] = "numpy"
        row["bit_parity"] = same_schedule(
            fus, numpy_sweep_schedule(dag, nodes, plane.matrix()))
        assert row["bit_parity"], "jit sweep diverged from the NumPy sweep"
    # best-of-repeats on EVERY side, so a transient stall in one path
    # cannot skew the reported ratio
    row["fused_s"] = min(_timed(fused_round) for _ in range(repeats))
    if "scalar_callback_s" in row:
        row["speedup"] = row["scalar_callback_s"] / row["fused_s"]
    return row


def run(seed: int = 0, repeats: int = 5, quiet: bool = False) -> dict:
    rows = [_one_size(t, n, seed, repeats) for t, n in SIZES]
    first = rows[0]
    out = {"sizes": rows, "bit_parity": all(r["bit_parity"] for r in rows),
           # top-level fields: the 100x20 round
           **{k: first[k] for k in ("n_tasks", "n_nodes",
                                    "scalar_callback_s", "fused_s",
                                    "speedup", "predicted_makespan_s")}}
    if not quiet:
        print("Replan round latency (host wall-clock, best of repeats):")
        print("  size        scalar-callback       fused   parity vs")
        for r in rows:
            scalar = (f"{r['scalar_callback_s'] * 1e3:12.1f} ms"
                      if "scalar_callback_s" in r else f"{'(skipped)':>15s}")
            print(f"  {r['n_tasks']:4d}x{r['n_nodes']:<4d}{scalar}"
                  f"  {r['fused_s'] * 1e3:8.1f} ms   {r['oracle']}")
        print(f"[claim] fused replan >= 5x over scalar (host) -> "
              f"{'PASS' if out['speedup'] >= 5.0 else 'FAIL'}")
        print(f"[claim] bit-identical schedules at every size -> "
              f"{'PASS' if out['bit_parity'] else 'FAIL'}")
    return out


def _timed(fn) -> float:
    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0


if __name__ == "__main__":
    run()
