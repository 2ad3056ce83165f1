"""End-to-end behaviour tests for the paper's system: local profiling ->
prediction -> scheduling, the full Lotaru pipeline, and the CSV interface."""
import os
import tempfile

import numpy as np
import pytest

from repro.core.microbench import simulate_microbench
from repro.core.predictor import BaselinePredictor, LotaruPredictor
from repro.core.traces import (PredictionRow, TraceRow, read_traces,
                               write_csv)
from repro.core.downsample import partition_sizes, validate_partitions
from repro.sched.cluster import LOCAL, TARGET_MACHINES
from repro.sched.fused import fused_heft_schedule
from repro.workflow.generator import GroundTruth, build_workflow
from repro.workflow.profiling import local_profiling
from repro.workflow.simulator import execute_schedule


def test_downsampling_respects_paper_rule():
    sizes = partition_sizes(10.0)
    assert len(sizes) >= 3
    assert sum(sizes) >= 1.0 - 1e-9          # >= 10% of the input
    assert validate_partitions(sizes, 10.0)


def test_full_pipeline_beats_baselines_end_to_end():
    """Lotaru predictions must beat the Online baselines on the heterogeneous
    cluster AND produce near-optimal HEFT makespans (the paper's headline)."""
    wf = "eager"
    gt = GroundTruth(wf, seed=0)
    traces, _ = local_profiling(wf, gt, training_set=0)
    local_bench = simulate_microbench(LOCAL, 1)
    benches = {n.name: simulate_microbench(n, 1) for n in TARGET_MACHINES}
    lot = LotaruPredictor("G", local_bench=local_bench).fit(traces)
    onl = BaselinePredictor("online-m").fit(traces)
    dag = build_workflow(wf, seed=0)

    errs = {"lotaru": [], "online": []}
    for node in TARGET_MACHINES:
        for uid, t in dag.tasks.items():
            actual = gt.runtime(t.task_name, t.input_gb, node, uid)
            errs["lotaru"].append(abs(lot.predict(
                t.task_name, t.input_gb, benches[node.name])[0] - actual) / actual)
            errs["online"].append(abs(onl.predict(
                t.task_name, t.input_gb, benches[node.name])[0] - actual) / actual)
    assert np.median(errs["lotaru"]) < np.median(errs["online"])

    nodes = list(TARGET_MACHINES)
    true_rt = lambda u, n: gt.runtime(dag.tasks[u].task_name,
                                      dag.tasks[u].input_gb, n, u)
    def pred_rt(u, n):
        t = dag.tasks[u]
        return lot.predict(t.task_name, t.input_gb, benches[n.name])[0]
    ms_pred = execute_schedule(dag, fused_heft_schedule(dag, nodes, pred_rt),
                               nodes, true_rt).makespan
    ms_true = execute_schedule(dag, fused_heft_schedule(dag, nodes, true_rt),
                               nodes, true_rt).makespan
    assert ms_pred <= 1.25 * ms_true       # near-optimal (paper: ~1.03-1.05)


def test_uncertainty_bounds_calibrated():
    """~95% of true runtimes should fall inside the 1.96-sigma bounds on the
    local machine (Bayesian calibration, Section 4.5 / Fig. 4)."""
    wf = "chipseq"
    gt = GroundTruth(wf, seed=0)
    traces, _ = local_profiling(wf, gt, training_set=0)
    lot = LotaruPredictor("G",
                          local_bench=simulate_microbench(LOCAL, 1)).fit(traces)
    dag = build_workflow(wf, seed=0)
    inside = total = 0
    for uid, t in dag.tasks.items():
        if not lot.models[t.task_name].correlated:
            continue
        actual = gt.runtime(t.task_name, t.input_gb, LOCAL, uid)
        _, lo, hi = lot.predict(t.task_name, t.input_gb, None, z=2.5)
        inside += int(lo <= actual <= hi)
        total += 1
    assert total > 10
    assert inside / total > 0.65


def test_csv_interface_roundtrip():
    rows = [TraceRow("wf", "bwa", "local", 0.5, 42.0, 0.5, 0.2, 0.8, "i0")]
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "traces.csv")
        write_csv(path, rows)
        back = read_traces(path)
        assert back[0].task == "bwa"
        assert back[0].runtime_s == pytest.approx(42.0)
        assert back[0].cpu_fraction == pytest.approx(0.8)

        # predictor consumes the CSV and emits a predictions CSV
        lot = LotaruPredictor("G", local_bench=simulate_microbench(LOCAL, 1))
        lot.fit(back * 4)
        dag = build_workflow("bacass", seed=0)
        benches = [simulate_microbench(n, 1) for n in TARGET_MACHINES]
        # only 'bwa' has a model; predict for a fake task list
        preds = [PredictionRow("wf", "bwa", b.name, 1.0,
                               *lot.predict("bwa", 1.0, b), "lotaru-g")
                 for b in benches]
        out = os.path.join(d, "preds.csv")
        write_csv(out, preds)
        assert os.path.getsize(out) > 0
