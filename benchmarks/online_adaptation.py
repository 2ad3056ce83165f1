"""Online-adaptation benchmark (beyond-paper, Hilman-et-al.-style):
prediction-error decay and makespan recovery of the streaming predictor
versus static Lotaru, plus batched-predict parity/throughput.

Scenario: the cold-start handoff the paper targets — the predictor was
fitted on downsampled *local* profiling only, and the cluster's true
per-node speeds have drifted from what the microbenchmarks measured
(multi-tenant interference, thermal limits, mis-sized volumes: the reason
online adaptation exists).  As production tasks finish, the online
predictor folds completions into its posteriors; the static predictor
never changes.

Claims checked:
  * after 25% of workflow tasks complete, the online predictor's median
    APE on the remaining tasks is strictly below static Lotaru's;
  * in-flight rescheduling recovers makespan under degraded nodes;
  * the batched predict path matches the scalar loop (atol 1e-4) while
    serving >= 1024 queries per call.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

from benchmarks.common import build_experiment, fmt_table
from repro.core import bayes
from repro.online import (OnlinePredictor, OnlineReschedulingPlanner,
                          PredictionService, TaskCompletion)
from repro.online.events import PredictionQuery
from repro.sched.cluster import TARGET_MACHINES
from repro.sched.fused import fused_heft_schedule
from repro.workflow.generator import WORKFLOWS
from repro.workflow.simulator import execute_adaptive, execute_schedule

# true-runtime multiplier per node (>1 = the node runs SLOWER than its
# benchmark predicted; 1.0 = the benchmark was right) — the drift the
# online predictor must discover
DRIFT = {"A1": 1.5, "A2": 0.7, "N1": 1.4, "N2": 0.6, "C2": 2.0}
CHECKPOINTS = (0.25, 0.5, 0.75)


def _mape(pred, dag, benches, actual, uids, nodes) -> float:
    errs = [abs(pred.predict(dag.tasks[u].task_name, dag.tasks[u].input_gb,
                             benches[n.name])[0] - actual[(u, n.name)])
            / actual[(u, n.name)]
            for u in uids for n in nodes]
    return 100.0 * float(np.median(errs))


def run_error_decay(seed: int = 0, quiet: bool = False) -> dict:
    nodes = list(TARGET_MACHINES)
    decay: Dict[str, Dict[float, List[float]]] = {
        "static": {c: [] for c in CHECKPOINTS},
        "online": {c: [] for c in CHECKPOINTS}}
    for wf in WORKFLOWS:
        exp = build_experiment(wf, training_set=0, seed=seed)
        lot = exp.predictors["lotaru-g"]
        true_rt = lambda u, n: exp.gt.runtime(
            exp.dag.tasks[u].task_name, exp.dag.tasks[u].input_gb, n, u) \
            * DRIFT.get(n.name, 1.0)
        actual = {(u, n.name): true_rt(u, n)
                  for u in exp.dag.tasks for n in nodes}
        # completions arrive in true execution order
        pred_rt = lambda u, n: lot.predict(
            exp.dag.tasks[u].task_name, exp.dag.tasks[u].input_gb,
            exp.benches[n.name])[0]
        sched = fused_heft_schedule(exp.dag, nodes, pred_rt)
        recs = sorted(execute_schedule(exp.dag, sched, nodes, true_rt).records,
                      key=lambda r: r.finish)
        online = OnlinePredictor(lot, benches=exp.benches)
        done = 0
        for c in CHECKPOINTS:
            upto = int(round(c * len(recs)))
            for r in recs[done:upto]:
                t = exp.dag.tasks[r.uid]
                online.observe(TaskCompletion(
                    wf, r.uid, t.task_name, r.node, t.input_gb,
                    r.finish - r.start, r.finish))
            done = upto
            rem = [r.uid for r in recs[upto:]]
            if not rem:
                continue
            decay["static"][c].append(
                _mape(lot, exp.dag, exp.benches, actual, rem, nodes))
            decay["online"][c].append(
                _mape(online, exp.dag, exp.benches, actual, rem, nodes))

    summary = {m: {c: float(np.mean(v)) for c, v in per.items() if v}
               for m, per in decay.items()}
    if not quiet:
        rows = [[f"{int(100 * c)}% complete",
                 f"{summary['static'][c]:.2f}%",
                 f"{summary['online'][c]:.2f}%"]
                for c in CHECKPOINTS if c in summary["static"]]
        print(fmt_table(["checkpoint", "static lotaru-g", "online"], rows,
                        "Prediction-error decay (median APE on remaining "
                        "tasks, drifted cluster)"))
        ok = summary["online"][0.25] < summary["static"][0.25]
        print(f"\n[claim] online MPE < static after 25% completions -> "
              f"{'PASS' if ok else 'FAIL'} "
              f"({summary['online'][0.25]:.2f}% vs "
              f"{summary['static'][0.25]:.2f}%)")
    return summary


def run_makespan_recovery(seed: int = 0, quiet: bool = False) -> dict:
    nodes = list(TARGET_MACHINES)
    out = {}
    for wf in WORKFLOWS:
        exp = build_experiment(wf, training_set=0, seed=seed)
        lot = exp.predictors["lotaru-g"]
        true_rt = lambda u, n: exp.gt.runtime(
            exp.dag.tasks[u].task_name, exp.dag.tasks[u].input_gb, n, u) \
            * DRIFT.get(n.name, 1.0)
        pred_rt = lambda u, n: lot.predict(
            exp.dag.tasks[u].task_name, exp.dag.tasks[u].input_gb,
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
        out[wf] = {"static": static.makespan, "adaptive": adaptive.makespan,
                   "oracle": oracle.makespan,
                   "reschedules": adaptive.n_reschedules}
    if not quiet:
        rows = [[wf, f"{v['static'] / 60:.1f}m", f"{v['adaptive'] / 60:.1f}m",
                 f"{v['oracle'] / 60:.1f}m", str(v["reschedules"])]
                for wf, v in out.items()]
        print(fmt_table(["workflow", "static", "adaptive", "oracle",
                         "reschedules"], rows,
                        "Makespan recovery under benchmark drift"))
        wins = sum(v["adaptive"] <= v["static"] * 1.001 for v in out.values())
        print(f"\n[claim] adaptive <= static makespan: {wins}/{len(out)}")
    return out


def run_batched_parity(seed: int = 0, quiet: bool = False) -> dict:
    """>= 1024 queries in one service call, means/stds match the scalar
    loop within atol 1e-4."""
    exp = build_experiment("eager", training_set=0, seed=seed)
    lot = exp.predictors["lotaru-g"]
    svc = PredictionService(lot, exp.benches)
    rng = np.random.default_rng(seed)
    tasks = lot.task_names()
    queries = [PredictionQuery(tasks[int(rng.integers(0, len(tasks)))],
                               TARGET_MACHINES[int(rng.integers(0, 5))].name,
                               float(rng.uniform(0.05, 12.0)))
               for _ in range(1536)]
    out = svc.predict_batch(queries)
    max_dm = max_ds = 0.0
    for q, (m, lo, hi) in zip(queries, out):
        m2, lo2, hi2 = lot.predict(q.task, q.input_gb, exp.benches[q.node])
        z = svc.z
        s, s2 = (hi - m) / z, (hi2 - m2) / z
        max_dm = max(max_dm, abs(m - m2))
        max_ds = max(max_ds, abs(s - s2))
    if not quiet:
        print(f"Batched parity over {len(queries)} queries: "
              f"max |mean diff| {max_dm:.2e}s, max |std diff| {max_ds:.2e}s")
        print(f"[claim] batched == scalar (atol 1e-4) for >=1024 queries -> "
              f"{'PASS' if max_dm < 1e-4 and max_ds < 1e-4 else 'FAIL'}")
    return {"n_queries": len(queries), "max_mean_diff": max_dm,
            "max_std_diff": max_ds}


def run(seed: int = 0, quiet: bool = False) -> dict:
    decay = run_error_decay(seed, quiet)
    if not quiet:
        print()
    recovery = run_makespan_recovery(seed, quiet)
    if not quiet:
        print()
    parity = run_batched_parity(seed, quiet)
    return {"error_decay": decay, "makespan_recovery": recovery,
            "batched_parity": parity}


if __name__ == "__main__":
    run()
