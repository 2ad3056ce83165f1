"""Quickstart: the whole Lotaru loop in one minute.

  1. profile the local machine with microbenchmarks,
  2. run a workflow locally on downsampled inputs,
  3. fit per-task Bayesian models,
  4. predict runtimes for every (task, node) pair of a heterogeneous cluster,
  5. feed HEFT and compare against ground truth.

  PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np

from repro.core.microbench import run_local_microbench, simulate_microbench
from repro.core.predictor import LotaruPredictor
from repro.sched.cluster import LOCAL, TARGET_MACHINES
from repro.sched.fused import fused_heft_schedule
from repro.workflow.generator import GroundTruth, build_workflow
from repro.workflow.profiling import local_profiling
from repro.workflow.simulator import execute_schedule


def main():
    print("== 1. infrastructure profiling (REAL probes on this machine) ==")
    real = run_local_microbench()
    print(f"   this machine: cpu={real.cpu:.1f} GFLOP/s  mem={real.mem:.1f} GB/s"
          f"  io r/w={real.io_read:.0f}/{real.io_write:.0f} MB/s")
    local_bench = simulate_microbench(LOCAL, 1)
    benches = {n.name: simulate_microbench(n, 1) for n in TARGET_MACHINES}
    print(f"   cluster nodes: {', '.join(benches)} (Table 2 specs)")

    print("\n== 2. local workflow execution on downsampled inputs ==")
    wf = "eager"
    gt = GroundTruth(wf, seed=0)
    traces, prof_s = local_profiling(wf, gt, training_set=0)
    print(f"   {len(traces)} task executions in {prof_s/60:.1f} simulated min")

    print("\n== 3./4. Bayesian models + heterogeneous prediction ==")
    lot = LotaruPredictor("G", local_bench=local_bench).fit(traces)
    for task in ("bwa_aln", "fastqc", "multiqc"):
        m = lot.models[task]
        mean, lo, hi = lot.predict(task, 8.0, benches["A1"])
        kind = "BLR" if m.correlated else "median"
        print(f"   {task:15s} [{kind:6s}] on A1 @8GB: "
              f"{mean:7.1f}s  [{lo:7.1f}, {hi:7.1f}]")

    print("\n== 5. HEFT scheduling with the predictions ==")
    dag = build_workflow(wf, seed=0)
    nodes = list(TARGET_MACHINES)
    true_rt = lambda u, n: gt.runtime(dag.tasks[u].task_name,
                                      dag.tasks[u].input_gb, n, u)
    pred_rt = lambda u, n: lot.predict(dag.tasks[u].task_name,
                                       dag.tasks[u].input_gb,
                                       benches[n.name])[0]
    ms_pred = execute_schedule(dag, fused_heft_schedule(dag, nodes, pred_rt),
                               nodes, true_rt).makespan
    ms_true = execute_schedule(dag, fused_heft_schedule(dag, nodes, true_rt),
                               nodes, true_rt).makespan
    print(f"   makespan with lotaru predictions: {ms_pred/60:.1f} min")
    print(f"   makespan with perfect knowledge:  {ms_true/60:.1f} min "
          f"(+{100*(ms_pred/ms_true-1):.1f}%)")


if __name__ == "__main__":
    main()
