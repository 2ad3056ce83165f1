import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.sched.carbon import (REGIONS, candidate_starts, emissions_g,
                                intensity_series, shift_workload)
from repro.sched.cluster import PAPER_MACHINES, TARGET_MACHINES
from repro.sched.cost import _billed_hours, cost_deviation_pct
from repro.sched.elastic import (checkpoint_every_n_steps, choose_workers,
                                 expected_waste_fraction, young_daly_interval_s)
from repro.sched.fused import fused_heft_schedule
from repro.sched.heft import comm_seconds
from repro.sched.plane import RuntimeDist, TaskDistribution
from repro.sched.straggler import (decide_speculation, normal_quantile,
                                   straggler_threshold)
from repro.workflow.dag import TaskInstance, WorkflowDAG
from repro.workflow.generator import (GroundTruth, WORKFLOW_INPUTS,
                                      WORKFLOW_TASKS, WORKFLOWS,
                                      build_workflow, true_runtimes)
from repro.workflow.simulator import execute_schedule, random_cluster


# --- generator --------------------------------------------------------------
def test_workflow_task_counts_match_table3():
    expected = {"bacass": 5, "atacseq": 14, "chipseq": 14, "eager": 13,
                "methylseq": 8}
    for wf, n in expected.items():
        assert len(WORKFLOW_TASKS[wf]) == n


def test_dag_structure():
    dag = build_workflow("eager", seed=0)
    n_samples = WORKFLOW_INPUTS["eager"][0]
    chain = sum(1 for m in WORKFLOW_TASKS["eager"] if not m.merge)
    merges = sum(1 for m in WORKFLOW_TASKS["eager"] if m.merge)
    assert len(dag.tasks) == n_samples * chain + merges
    order = dag.topo_order()
    seen = set()
    for uid in order:
        assert all(d in seen for d in dag.tasks[uid].deps)
        seen.add(uid)


def test_ground_truth_scales_with_machine():
    gt = GroundTruth("eager", seed=0)
    t_local = gt.runtime("bwa_aln", 2.0, PAPER_MACHINES["local"], "x")
    t_a1 = gt.runtime("bwa_aln", 2.0, PAPER_MACHINES["A1"], "x")
    t_c2 = gt.runtime("bwa_aln", 2.0, PAPER_MACHINES["C2"], "x")
    assert t_a1 > t_local > t_c2   # cpu-bound task follows cpu speeds


# --- HEFT + simulator ---------------------------------------------------------
def _small_dag():
    dag = WorkflowDAG("toy")
    dag.add(TaskInstance("a", "a", "toy", 1.0, output_gb=0.1))
    dag.add(TaskInstance("b", "b", "toy", 1.0, output_gb=0.1, deps=["a"]))
    dag.add(TaskInstance("c", "c", "toy", 1.0, output_gb=0.1, deps=["a"]))
    dag.add(TaskInstance("d", "d", "toy", 1.0, deps=["b", "c"]))
    return dag


def test_heft_respects_dependencies_and_uses_fast_node():
    dag = _small_dag()
    nodes = [PAPER_MACHINES["A1"], PAPER_MACHINES["C2"]]
    rt = {"A1": 100.0, "C2": 10.0}

    sched = fused_heft_schedule(dag, nodes, lambda u, n: rt[n.name])
    for uid, t in dag.tasks.items():
        s, f = sched.est[uid]
        for d in t.deps:
            assert sched.est[d][1] <= s + 1e-9
    # heavily skewed costs -> everything should land on C2
    assert all(v == "C2" for v in sched.assignment.values())


def test_simulated_makespan_at_least_critical_path():
    dag = build_workflow("bacass", seed=0)
    gt = GroundTruth("bacass", seed=0)
    nodes = list(TARGET_MACHINES)
    true_rt = lambda u, n: gt.runtime(dag.tasks[u].task_name,
                                      dag.tasks[u].input_gb, n, u)
    sched = fused_heft_schedule(dag, nodes, true_rt)
    res = execute_schedule(dag, sched, nodes, true_rt)
    best_each = {u: min(true_rt(u, n) for n in nodes) for u in dag.tasks}
    assert res.makespan >= dag.critical_path_length(best_each) - 1e-6
    # every task executed exactly once
    assert len(res.records) == len(dag.tasks)


def test_simulator_failure_increases_makespan():
    dag = build_workflow("bacass", seed=0)
    gt = GroundTruth("bacass", seed=0)
    nodes = list(TARGET_MACHINES)
    true_rt = lambda u, n: gt.runtime(dag.tasks[u].task_name,
                                      dag.tasks[u].input_gb, n, u)
    sched = fused_heft_schedule(dag, nodes, true_rt)
    base = execute_schedule(dag, sched, nodes, true_rt).makespan
    mid = base / 2
    failed = execute_schedule(dag, sched, nodes, true_rt,
                              failures={nodes[0].name: mid}).makespan
    assert failed >= base


@settings(max_examples=8, deadline=None)
@given(seed=st.integers(0, 10_000))
def test_property_random_clusters_schedule_all_tasks(seed):
    rng = np.random.default_rng(seed)
    dag = build_workflow("bacass", seed=0)
    gt = GroundTruth("bacass", seed=0)
    nodes = random_cluster(rng, list(TARGET_MACHINES), n_nodes=5)
    true_rt = lambda u, n: gt.runtime(dag.tasks[u].task_name,
                                      dag.tasks[u].input_gb, n, u)
    sched = fused_heft_schedule(dag, nodes, true_rt)
    res = execute_schedule(dag, sched, nodes, true_rt)
    assert len(res.records) == len(dag.tasks)
    assert res.makespan > 0


# --- carbon -------------------------------------------------------------------
def test_carbon_series_deterministic_and_ordered():
    for r in REGIONS:
        s1, s2 = intensity_series(r, 0), intensity_series(r, 0)
        np.testing.assert_array_equal(s1, s2)
    assert intensity_series("france").mean() < intensity_series("germany").mean()


def test_candidate_starts_policies():
    sw = candidate_starts("semi_weekly")
    nm = candidate_starts("next_monday")
    assert 0.0 in sw and 0.0 in nm
    assert len(sw) > len(nm) > 1


def test_shift_saves_vs_now_with_accurate_duration():
    o = shift_workload("germany", "next_monday", predicted_h=5.0,
                       actual_h=5.0, power_kw=2.0)
    assert o.emissions_shifted_g <= o.emissions_now_g + 1e-6


def test_shift_workload_accepts_distribution():
    """decision-plane consumer: predicted_h may be a RuntimeDist booked at
    quantile q — q=0.5 reproduces the float-mean path exactly, a higher q
    books strictly more (never fewer) low-carbon hours."""
    base = shift_workload("germany", "next_monday", predicted_h=5.0,
                          actual_h=5.0, power_kw=2.0)
    dist = RuntimeDist(mean=5.0, std=1.0)
    at_mean = shift_workload("germany", "next_monday", dist,
                             actual_h=5.0, power_kw=2.0, q=0.5)
    assert at_mean.emissions_shifted_g == pytest.approx(
        base.emissions_shifted_g, rel=1e-12)
    q95 = shift_workload("germany", "next_monday", dist,
                         actual_h=5.0, power_kw=2.0, q=0.95)
    # booking covers the 95%-quantile duration: a superset of the cheapest
    # hours, so reserved emissions can only grow
    assert q95.emissions_shifted_g >= at_mean.emissions_shifted_g


# --- cost ----------------------------------------------------------------------
def test_billing_math():
    assert _billed_hours(3600, "hourly") == 1
    assert _billed_hours(3601, "hourly") == 2
    assert _billed_hours(90, "minute") == pytest.approx(2 / 60)


def test_cost_deviation_sign():
    assert cost_deviation_pct(110, 100) == pytest.approx(10.0)
    assert cost_deviation_pct(90, 100) == pytest.approx(-10.0)


# --- straggler / elastic -----------------------------------------------------------
def test_normal_quantile_sanity():
    assert normal_quantile(0, 1, 0.5) == pytest.approx(0.0, abs=1e-6)
    assert normal_quantile(0, 1, 0.975) == pytest.approx(1.96, abs=0.01)
    assert normal_quantile(10, 2, 0.95) == pytest.approx(10 + 1.645 * 2, abs=0.05)


def test_speculation_decision():
    nodes = list(TARGET_MACHINES)
    # running on A1 with predictive N(30, 5); elsewhere the predicted mean
    # follows cpu speed, so the backup should land on C2 (fastest)
    dist = TaskDistribution(
        "u", tuple(n.name for n in nodes),
        np.asarray([30.0 if n.name == "A1" else 100.0 / n.cpu
                    for n in nodes]),
        np.full(len(nodes), 5.0))
    d = decide_speculation(elapsed_s=50, dist=dist, node="A1",
                           idle_nodes=[n for n in nodes if n.name != "A1"])
    assert d.speculate and d.backup_node == "C2"
    d2 = decide_speculation(elapsed_s=31, dist=dist, node="A1",
                            idle_nodes=nodes)
    assert not d2.speculate


def test_young_daly():
    assert young_daly_interval_s(60, 24 * 3600) == pytest.approx(
        (2 * 60 * 24 * 3600) ** 0.5)
    steps = checkpoint_every_n_steps(0.5, 60, 24 * 3600, 256)
    assert steps >= 1
    w = expected_waste_fraction(0.5, steps, 60, 24 * 3600, 256)
    assert 0 < w < 1


def test_choose_workers_meets_deadline():
    d = choose_workers(total_steps=1000, step_time_mean_s=1.0,
                       step_time_std_s=0.1, deadline_h=0.2, max_workers=16)
    assert d.meets_deadline
    assert d.n_workers > 1
