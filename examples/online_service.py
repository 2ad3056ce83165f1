"""Online prediction service demo: a workflow executes on a drifted
heterogeneous cluster while the service ingests completions, tightens its
posteriors, recalibrates node factors, and re-plans the unstarted frontier
when predictions leave their uncertainty bands.

  PYTHONPATH=src python examples/online_service.py [--workflow eager]
"""
import argparse
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
from benchmarks.common import build_experiment
from repro.online import (OnlinePredictor, OnlineReschedulingPlanner,
                          PredictionService)
from repro.online.events import PredictionQuery
from repro.sched.cluster import TARGET_MACHINES
from repro.sched.fused import fused_heft_schedule
from repro.workflow.simulator import execute_adaptive, execute_schedule

DRIFT = {"A1": 1.5, "N2": 0.6, "C2": 2.0}   # true-runtime multiplier
                                            # (>1 = slower than benchmarked)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workflow", default="eager")
    args = ap.parse_args()

    exp = build_experiment(args.workflow, training_set=0)
    lot = exp.predictors["lotaru-g"]
    nodes = list(TARGET_MACHINES)
    true_rt = lambda u, n: exp.gt.runtime(
        exp.dag.tasks[u].task_name, exp.dag.tasks[u].input_gb, n, u) \
        * DRIFT.get(n.name, 1.0)

    print(f"== {args.workflow}: {len(exp.dag.tasks)} tasks, cluster drift "
          f"{DRIFT} ==\n")

    # --- batched service: one call answers the whole scheduling matrix ------
    svc = PredictionService(lot, exp.benches)
    queries = [PredictionQuery(t.task_name, n.name, t.input_gb)
               for t in exp.dag.tasks.values() for n in nodes]
    out = svc.predict_batch(queries)
    print(f"service answered {len(queries)} (task, node) queries in one "
          f"batched call; sample:")
    for q, (m, lo, hi) in list(zip(queries, out))[:3]:
        print(f"   {q.task:16s} on {q.node}: {m:8.1f}s  [{lo:.1f}, {hi:.1f}]")

    # --- static vs adaptive execution ---------------------------------------
    pred_rt = lambda u, n: lot.predict(exp.dag.tasks[u].task_name,
                                       exp.dag.tasks[u].input_gb,
                                       exp.benches[n.name])[0]
    static = execute_schedule(
        exp.dag, fused_heft_schedule(exp.dag, nodes, pred_rt), nodes,
        true_rt)
    online = OnlinePredictor(lot, benches=exp.benches)
    planner = OnlineReschedulingPlanner(exp.dag, nodes, online,
                                        benches=exp.benches)
    adaptive = execute_adaptive(exp.dag, nodes, planner, true_rt)
    oracle = execute_schedule(
        exp.dag, fused_heft_schedule(exp.dag, nodes, true_rt), nodes,
        true_rt)

    print(f"\nstatic schedule makespan:   {static.makespan / 60:7.1f}m")
    print(f"adaptive (online) makespan: {adaptive.makespan / 60:7.1f}m "
          f"({adaptive.n_reschedules} reschedules, "
          f"{planner.stats.completions} completions observed)")
    print(f"oracle (true runtimes):     {oracle.makespan / 60:7.1f}m")

    print("\nlearned node corrections (true drift in parentheses):")
    for name in sorted(online.node_stats):
        corr = online.node_stats[name].correction
        print(f"   {name}: x{corr:4.2f}  (x{DRIFT.get(name, 1.0):4.2f})")


if __name__ == "__main__":
    main()
