"""Evaluation B.2 (Figs. 7-8): carbon savings from temporal shifting with
predicted vs accurate runtimes, 4 regions x 2 policies.  Paper claims:
accurate best (mostly), Lotaru-A ~second, Online-P worst; next-Monday
saves more than semi-weekly."""
from __future__ import annotations

from typing import Dict

import numpy as np

from benchmarks.common import build_experiment, fmt_table
from repro.sched.carbon import REGIONS, shift_workload
from repro.sched.cluster import TARGET_MACHINES
from repro.sched.fused import fused_heft_schedule
from repro.workflow.generator import WORKFLOWS
from repro.workflow.simulator import execute_schedule

CARBON_METHODS = ("online-p", "lotaru-g", "lotaru-a", "accurate")


def run(seed: int = 0, quiet: bool = False) -> dict:
    nodes = list(TARGET_MACHINES)
    power_kw = sum(n.power_watts for n in nodes) / 1000.0

    durations = {}          # (wf, method) -> (predicted_h, actual_h)
    for wf in WORKFLOWS:
        exp = build_experiment(wf, training_set=0, seed=seed)

        def true_rt(uid, node):
            t = exp.dag.tasks[uid]
            return exp.gt.runtime(t.task_name, t.input_gb, node, uid)

        for meth in CARBON_METHODS:
            def pred_rt(uid, node):
                t = exp.dag.tasks[uid]
                if meth == "accurate":
                    return true_rt(uid, node)
                return exp.predictors[meth].predict(
                    t.task_name, t.input_gb, exp.benches[node.name])[0]
            sched = fused_heft_schedule(exp.dag, nodes, pred_rt)
            res = execute_schedule(exp.dag, sched, nodes, true_rt)
            durations[(wf, meth)] = (sched.predicted_makespan / 3600.0,
                                     res.makespan / 3600.0)

    out: Dict[str, Dict[str, Dict[str, float]]] = {}
    for policy in ("semi_weekly", "next_monday"):
        out[policy] = {}
        for region in REGIONS:
            out[policy][region] = {}
            for meth in CARBON_METHODS:
                savings = []
                for wf in WORKFLOWS:
                    pred_h, act_h = durations[(wf, meth)]
                    o = shift_workload(region, policy, pred_h, act_h,
                                       power_kw, seed=seed)
                    savings.append(o.savings_pct)
                out[policy][region][meth] = float(np.mean(savings))

    for policy in out:
        rows = [[r] + [f"{out[policy][r][m]:.1f}%" for m in CARBON_METHODS]
                for r in REGIONS]
        print(fmt_table(["region"] + list(CARBON_METHODS), rows,
                        f"Fig. {'7' if policy == 'semi_weekly' else '8'} - "
                        f"carbon savings, {policy}"))
        print()
    if not quiet:
        sw = np.mean([out["semi_weekly"][r]["lotaru-a"] for r in REGIONS])
        nm = np.mean([out["next_monday"][r]["lotaru-a"] for r in REGIONS])
        la = np.mean([out["next_monday"][r]["lotaru-a"] for r in REGIONS])
        op = np.mean([out["next_monday"][r]["online-p"] for r in REGIONS])
        print(f"[claim] next-monday > semi-weekly -> "
              f"{'PASS' if nm > sw else 'FAIL'};  lotaru-a > online-p -> "
              f"{'PASS' if la >= op else 'FAIL'}")
    return out


if __name__ == "__main__":
    run()
