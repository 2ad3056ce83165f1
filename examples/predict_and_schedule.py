"""Fleet resource management with Lotaru: prediction error per method,
HEFT makespans, carbon-aware shifting, cloud cost — the Evaluation B loop
on one page, plus the Lotaru-R accelerator-fleet extrapolation.

  PYTHONPATH=src python examples/predict_and_schedule.py [--workflow eager]
"""
import argparse
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
from benchmarks.common import ALL_METHODS, build_experiment
from repro.core.extrapolation import extrapolate_roofline
from repro.sched.carbon import REGIONS, shift_workload
from repro.sched.cluster import TARGET_MACHINES, TPU_FLEET
from repro.sched.fused import fused_heft_schedule
from repro.workflow.simulator import execute_schedule


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workflow", default="eager")
    args = ap.parse_args()

    exp = build_experiment(args.workflow, training_set=0)
    nodes = list(TARGET_MACHINES)
    true_rt = lambda u, n: exp.gt.runtime(exp.dag.tasks[u].task_name,
                                          exp.dag.tasks[u].input_gb, n, u)

    print(f"== {args.workflow}: {len(exp.dag.tasks)} tasks on "
          f"{len(nodes)} heterogeneous nodes ==")
    rows = {}
    for meth, pred in exp.predictors.items():
        def pred_rt(u, n):
            t = exp.dag.tasks[u]
            return pred.predict(t.task_name, t.input_gb,
                                exp.benches[n.name])[0]
        sched = fused_heft_schedule(exp.dag, nodes, pred_rt)
        res = execute_schedule(exp.dag, sched, nodes, true_rt)
        rows[meth] = (sched.predicted_makespan, res.makespan)
    ms_true = execute_schedule(
        exp.dag, fused_heft_schedule(exp.dag, nodes, true_rt), nodes,
        true_rt).makespan
    print(f"{'method':10s} {'predicted':>10s} {'actual':>10s} {'vs perfect':>11s}")
    for meth, (pm, am) in rows.items():
        print(f"{meth:10s} {pm/60:9.1f}m {am/60:9.1f}m "
              f"{100*(am/ms_true-1):+10.1f}%")
    print(f"{'perfect':10s} {'-':>10s} {ms_true/60:9.1f}m")

    print("\n== carbon-aware shifting (next-monday policy) ==")
    pm, am = rows["lotaru-a"]
    power_kw = sum(n.power_watts for n in nodes) / 1000
    for region in REGIONS:
        o = shift_workload(region, "next_monday", pm / 3600, am / 3600,
                           power_kw)
        print(f"   {region:14s}: shift to t+{o.start_h:5.0f}h saves "
              f"{o.savings_pct:5.1f}% CO2")

    print("\n== Lotaru-R: extrapolating an ML step across the TPU fleet ==")
    # measured-on-v5e roofline terms of a glm4-9b train step (from the dry-run)
    terms = {"compute": 1.37, "memory": 0.055, "collective": 0.85}
    t_v5e = max(terms.values())
    for name, node in TPU_FLEET.items():
        t = extrapolate_roofline(terms, TPU_FLEET["v5e"], node)
        print(f"   {name:9s}: predicted step {t:7.3f}s  "
              f"(x{t_v5e/t:4.2f} vs v5e)")


if __name__ == "__main__":
    main()
