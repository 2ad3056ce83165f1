"""Vectorized decision plane: bit-parity of the fused HEFT engine over a
scalar callable vs the scalar reference, quantile-aware scheduling,
one-dispatch prediction matrices, the shared AS 241 inverse-normal, and
speculative re-execution."""
import math

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.microbench import simulate_microbench
from repro.core.predictor import LotaruPredictor
from repro.core.traces import TraceRow
from repro.online import (OnlinePredictor, OnlineReschedulingPlanner,
                          PredictionService)
from repro.online.events import PredictionQuery
from repro.sched.cluster import LOCAL, TARGET_MACHINES
from repro.sched.cost import predicted_cost, predicted_cost_quantile
from repro.sched.fused import fused_heft_schedule
from repro.sched.heft import heft_schedule_reference
from repro.sched.plane import PredictionMatrix, RuntimeDist, quantile_z
from repro.sched.straggler import ndtri
from repro.workflow.dag import TaskInstance, WorkflowDAG
from repro.workflow.generator import WORKFLOWS, GroundTruth, build_workflow
from repro.workflow.profiling import local_profiling
from repro.workflow.simulator import (SpeculationPolicy, execute_adaptive,
                                      execute_schedule, random_cluster)


# --- shared inverse-normal (AS 241) ---------------------------------------------
def _acklam(q: float) -> float:
    """The retired scalar Acklam approximation (|err| ~ 1.15e-9), kept
    verbatim as the property-test oracle for the vectorized AS 241."""
    a = [-3.969683028665376e+01, 2.209460984245205e+02,
         -2.759285104469687e+02, 1.383577518672690e+02,
         -3.066479806614716e+01, 2.506628277459239e+00]
    b = [-5.447609879822406e+01, 1.615858368580409e+02,
         -1.556989798598866e+02, 6.680131188771972e+01,
         -1.328068155288572e+01]
    c = [-7.784894002430293e-03, -3.223964580411365e-01,
         -2.400758277161838e+00, -2.549732539343734e+00,
         4.374664141464968e+00, 2.938163982698783e+00]
    d = [7.784695709041462e-03, 3.224671290700398e-01,
         2.445134137142996e+00, 3.754408661907416e+00]
    p = min(max(q, 1e-12), 1 - 1e-12)
    if p < 0.02425:
        t = math.sqrt(-2 * math.log(p))
        return (((((c[0] * t + c[1]) * t + c[2]) * t + c[3]) * t + c[4]) * t
                + c[5]) / ((((d[0] * t + d[1]) * t + d[2]) * t + d[3]) * t + 1)
    if p <= 0.97575:
        t = p - 0.5
        r = t * t
        return (((((a[0] * r + a[1]) * r + a[2]) * r + a[3]) * r + a[4]) * r
                + a[5]) * t / (((((b[0] * r + b[1]) * r + b[2]) * r + b[3])
                                * r + b[4]) * r + 1)
    t = math.sqrt(-2 * math.log(1 - p))
    return -(((((c[0] * t + c[1]) * t + c[2]) * t + c[3]) * t + c[4]) * t
             + c[5]) / ((((d[0] * t + d[1]) * t + d[2]) * t + d[3]) * t + 1)


@settings(max_examples=200, deadline=None)
@given(p=st.floats(1e-9, 1 - 1e-9))
def test_ndtri_matches_retired_acklam(p):
    assert float(ndtri(p)) == pytest.approx(_acklam(p), abs=1e-6)


def test_ndtri_exact_landmarks_and_vectorization():
    # double-precision landmarks AS 241 must hit (Acklam could not)
    assert float(ndtri(0.5)) == 0.0
    assert float(ndtri(0.975)) == pytest.approx(1.959963984540054, abs=1e-12)
    assert float(ndtri(0.95)) == pytest.approx(1.6448536269514722, abs=1e-12)
    p = np.linspace(1e-6, 1 - 1e-6, 257)
    z = ndtri(p)
    assert z.shape == p.shape
    np.testing.assert_array_equal(z, [float(ndtri(pi)) for pi in p])
    np.testing.assert_allclose(z + ndtri(1.0 - p), 0.0, atol=1e-9)
    assert quantile_z(0.5) == 0.0


# --- matrix HEFT bit-parity ------------------------------------------------------
def _random_dag(rng, n_tasks: int) -> WorkflowDAG:
    dag = WorkflowDAG("rand")
    for i in range(n_tasks):
        deps = [f"t{j}" for j in range(i)
                if rng.random() < min(3.0 / max(i, 1), 0.5)]
        dag.add(TaskInstance(f"t{i}", f"task{i % 5}", "rand",
                             float(rng.uniform(0.05, 4.0)),
                             output_gb=float(rng.uniform(0.0, 2.0)),
                             deps=deps))
    return dag


def _assert_bit_identical(a, b):
    assert a.assignment == b.assignment
    assert a.order == b.order
    assert a.est == b.est        # exact float equality: bit parity


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 10_000))
def test_matrix_heft_bit_parity_random_dags(seed):
    rng = np.random.default_rng(seed)
    dag = _random_dag(rng, int(rng.integers(5, 40)))
    nodes = random_cluster(rng, list(TARGET_MACHINES),
                           n_nodes=int(rng.integers(2, 8)))
    costs = {(u, n.name): float(rng.uniform(1.0, 500.0))
             for u in dag.tasks for n in nodes}
    predict = lambda u, n: costs[(u, n.name)]
    _assert_bit_identical(fused_heft_schedule(dag, nodes, predict),
                          heft_schedule_reference(dag, nodes, predict))


@settings(max_examples=6, deadline=None)
@given(seed=st.integers(0, 10_000))
def test_matrix_heft_bit_parity_with_replan_constraints(seed):
    """parity must also hold on the rescheduler's path: external ready
    times plus node-availability constraints."""
    rng = np.random.default_rng(seed)
    dag = _random_dag(rng, 20)
    nodes = random_cluster(rng, list(TARGET_MACHINES), n_nodes=4)
    costs = {(u, n.name): float(rng.uniform(1.0, 300.0))
             for u in dag.tasks for n in nodes}
    predict = lambda u, n: costs[(u, n.name)]
    ready = {u: float(rng.uniform(0.0, 50.0)) for u in dag.tasks}
    avail = {n.name: float(rng.uniform(0.0, 80.0)) for n in nodes}
    _assert_bit_identical(
        fused_heft_schedule(dag, nodes, predict, ready_at=ready,
                      node_available=avail),
        heft_schedule_reference(dag, nodes, predict, ready_at=ready,
                                node_available=avail))


@pytest.mark.parametrize("workflow", WORKFLOWS)
def test_matrix_heft_bit_parity_real_workflow(workflow):
    dag = build_workflow(workflow, seed=0)
    gt = GroundTruth(workflow, seed=0)
    nodes = list(TARGET_MACHINES)
    predict = lambda u, n: gt.runtime(dag.tasks[u].task_name,
                                      dag.tasks[u].input_gb, n, u)
    _assert_bit_identical(fused_heft_schedule(dag, nodes, predict),
                          heft_schedule_reference(dag, nodes, predict))


# --- quantile-aware scheduling ---------------------------------------------------
def test_quantile_requires_uncertainty():
    dag = build_workflow("bacass", seed=0)
    nodes = list(TARGET_MACHINES)
    with pytest.raises(ValueError, match="quantile"):
        fused_heft_schedule(dag, nodes, lambda u, n: 1.0, quantile=0.95)


def test_quantile_scheduling_prefers_certain_node():
    """mean-equal but uncertainty-skewed costs: the median schedule is
    indifferent (ties to the first node), the q95 schedule must flee the
    high-variance node."""
    dag = WorkflowDAG("toy")
    dag.add(TaskInstance("a", "a", "toy", 1.0))
    nodes = [TARGET_MACHINES[0], TARGET_MACHINES[1]]   # A1, A2
    means = np.asarray([[100.0, 101.0]])
    stds = np.asarray([[50.0, 0.1]])
    mat = PredictionMatrix(["a"], [n.name for n in nodes], means, stds)
    mean_sched = fused_heft_schedule(dag, nodes, mat)
    assert mean_sched.assignment["a"] == "A1"           # 100 < 101
    q95 = fused_heft_schedule(dag, nodes, mat, quantile=0.95)
    assert q95.assignment["a"] == "A2"   # 100+1.645*50 >> 101+1.645*0.1
    # q=0.5 is exactly the mean schedule (z(0.5) == 0)
    _assert_bit_identical(mean_sched,
                          fused_heft_schedule(dag, nodes, mat, quantile=0.5))


# --- one-dispatch prediction matrix ---------------------------------------------
def _service():
    lot = LotaruPredictor("G", local_bench=simulate_microbench(LOCAL, 1))
    traces = []
    for j, t in enumerate(("bwa", "idx", "merge")):
        traces += [TraceRow("wf", t, "local", s, 2.0 + j + (20.0 + 7 * j) * s)
                   for s in np.linspace(0.05, 0.4, 6)]
    lot.fit(traces)
    benches = {n.name: simulate_microbench(n, 1) for n in TARGET_MACHINES}
    return PredictionService(lot, benches)


def test_predict_matrix_matches_flattened_batch():
    """the decision plane's single dispatch is elementwise-identical to
    predict_batch over the flattened (task, node) queries."""
    svc = _service()
    tasks = [("bwa", 0.3), ("idx", 1.7), ("merge", 4.0), ("bwa", 8.5)]
    names = [n.name for n in TARGET_MACHINES]
    mean, std = svc.predict_matrix(tasks, names)
    assert mean.shape == std.shape == (len(tasks), len(names))
    flat = svc.predict_batch([PredictionQuery(t, n, gb)
                              for t, gb in tasks for n in names])
    np.testing.assert_array_equal(mean.ravel(), flat[:, 0])
    # finalize returns [mean, lower, upper]; recover std via the band width
    np.testing.assert_allclose(std.ravel(), (flat[:, 2] - flat[:, 0]) / svc.z,
                               rtol=0, atol=1e-12)


def test_prediction_matrix_from_service_and_rows():
    svc = _service()
    entries = [("u0", "bwa", 0.3), ("u1", "idx", 1.7), ("u2", "bwa", 2.0)]
    mat = PredictionMatrix.from_service(svc, entries, list(TARGET_MACHINES))
    assert mat.uids == ("u0", "u1", "u2")
    row = mat.row("u1")
    m, s = row.on("C2")
    assert (m, s) == mat.on("u1", "C2")
    assert row.dist("C2").quantile(0.5) == pytest.approx(m)
    assert row.quantile("C2", 0.95) > m
    # costs() reindexing follows the requested orders
    sub = mat.costs(["u2", "u0"], ["C2", "A1"])
    assert sub[0, 0] == mat.mean("u2", "C2")
    assert sub[1, 1] == mat.mean("u0", "A1")
    with pytest.raises(ValueError):
        PredictionMatrix(["a"], ["n"], np.zeros((2, 1)))


def test_cost_quantile_bounds_mean_cost():
    """billing at the posterior q-quantile can only cost more than billing
    the mean (q=0.5 reproduces it)."""
    dag = build_workflow("bacass", seed=0)
    gt = GroundTruth("bacass", seed=0)
    nodes = list(TARGET_MACHINES)
    predict = lambda u, n: gt.runtime(dag.tasks[u].task_name,
                                      dag.tasks[u].input_gb, n, u)
    mat = PredictionMatrix.from_callable(list(dag.tasks), nodes, predict)
    mat = PredictionMatrix(mat.uids, mat.node_names, mat.means,
                           0.2 * mat.means)        # 20% predictive std
    sched = fused_heft_schedule(dag, nodes, mat)
    base = predicted_cost(sched, nodes, "minute")
    assert predicted_cost_quantile(sched, mat, nodes, "minute", q=0.5) \
        == pytest.approx(base, rel=1e-9)
    assert predicted_cost_quantile(sched, mat, nodes, "minute", q=0.95) \
        >= base


def test_runtime_dist_quantile():
    d = RuntimeDist(mean=100.0, std=10.0)
    assert d.quantile(0.5) == pytest.approx(100.0)
    assert d.quantile(0.95) == pytest.approx(100.0 + 16.448536269514722,
                                             rel=1e-9)


# --- speculative re-execution ----------------------------------------------------
def _experiment(wf="bacass"):
    gt = GroundTruth(wf, seed=0)
    traces, _ = local_profiling(wf, gt, training_set=0)
    local_bench = simulate_microbench(LOCAL, 1)
    benches = {n.name: simulate_microbench(n, 1) for n in TARGET_MACHINES}
    lot = LotaruPredictor("G", local_bench=local_bench).fit(traces)
    return gt, build_workflow(wf, seed=0), lot, benches


def test_speculation_beats_no_speculation_and_records_once():
    """an injected straggler is duplicated on an idle node, the backup
    wins, makespan improves, and the cancelled loser never produces a
    second ExecRecord."""
    gt, dag, lot, benches = _experiment("bacass")
    nodes = list(TARGET_MACHINES)
    true_rt = lambda u, n: gt.runtime(dag.tasks[u].task_name,
                                      dag.tasks[u].input_gb, n, u)
    # the straggler: the last task to start in the baseline run, inflated
    # 10x — an incident local to its original placement
    base_planner = OnlineReschedulingPlanner(
        dag, nodes, OnlinePredictor(lot, benches=benches), benches=benches)
    base = execute_adaptive(dag, nodes, base_planner, true_rt)
    victim = max(base.records, key=lambda r: r.start).uid
    sf = lambda u: 10.0 if u == victim else 1.0

    no_spec = execute_adaptive(
        dag, nodes,
        OnlineReschedulingPlanner(dag, nodes,
                                  OnlinePredictor(lot, benches=benches),
                                  benches=benches),
        true_rt, straggler_factor=sf)
    spec = execute_adaptive(
        dag, nodes,
        OnlineReschedulingPlanner(dag, nodes,
                                  OnlinePredictor(lot, benches=benches),
                                  benches=benches),
        true_rt, straggler_factor=sf,
        speculation=SpeculationPolicy(q=0.95, check_interval_s=15.0))

    assert spec.n_backups >= 1
    assert spec.backup_waste_s > 0.0
    assert spec.makespan < no_spec.makespan
    # exactly one ExecRecord per task: the loser was cancelled, not recorded
    uids = [r.uid for r in spec.records]
    assert sorted(uids) == sorted(dag.tasks)
    # the backup's slot shows as busy on the loser's node only until the
    # winner finished
    for node, iv in spec.node_busy.items():
        iv = sorted(iv)
        for (a0, a1), (b0, b1) in zip(iv, iv[1:]):
            assert a1 <= b0 + 1e-9, (node, a1, b0)


def test_speculation_requires_capable_planner():
    class NoSpec:
        def initial_schedule(self):           # pragma: no cover
            raise AssertionError
        def on_completion(self, rec, state):  # pragma: no cover
            raise AssertionError
    dag = build_workflow("bacass", seed=0)
    with pytest.raises(TypeError, match="decide_speculation"):
        execute_adaptive(dag, list(TARGET_MACHINES), NoSpec(),
                         lambda u, n: 1.0,
                         speculation=SpeculationPolicy())


def test_static_execution_unaffected_by_speculation_plumbing():
    """execute_schedule (no speculation) still runs every task once with
    the event-loop backup machinery present."""
    gt, dag, lot, benches = _experiment("bacass")
    nodes = list(TARGET_MACHINES)
    true_rt = lambda u, n: gt.runtime(dag.tasks[u].task_name,
                                      dag.tasks[u].input_gb, n, u)
    sched = fused_heft_schedule(dag, nodes, true_rt)
    res = execute_schedule(dag, sched, nodes, true_rt)
    assert res.n_backups == 0 and res.backup_waste_s == 0.0
    assert len(res.records) == len(dag.tasks)


def test_speculation_budget_caps_bound_duplicate_work():
    """max_total_backups / max_concurrent_backups bound duplicate work:
    a zero budget launches nothing, a small budget launches at most that
    many backups while still beating the uncapped-straggler makespan."""
    gt, dag, lot, benches = _experiment("bacass")
    nodes = list(TARGET_MACHINES)
    true_rt = lambda u, n: gt.runtime(dag.tasks[u].task_name,
                                      dag.tasks[u].input_gb, n, u)
    base = execute_adaptive(
        dag, nodes,
        OnlineReschedulingPlanner(dag, nodes,
                                  OnlinePredictor(lot, benches=benches),
                                  benches=benches), true_rt)
    victims = {r.uid for r in
               sorted(base.records, key=lambda r: r.start)[-3:]}
    sf = lambda u: 10.0 if u in victims else 1.0

    def run_with(policy):
        return execute_adaptive(
            dag, nodes,
            OnlineReschedulingPlanner(dag, nodes,
                                      OnlinePredictor(lot, benches=benches),
                                      benches=benches),
            true_rt, straggler_factor=sf, speculation=policy)

    none = run_with(None)
    uncapped = run_with(SpeculationPolicy(q=0.95, check_interval_s=15.0))
    capped = run_with(SpeculationPolicy(q=0.95, check_interval_s=15.0,
                                        max_concurrent_backups=1,
                                        max_total_backups=2))
    zero = run_with(SpeculationPolicy(q=0.95, check_interval_s=15.0,
                                      max_total_backups=0))

    assert zero.n_backups == 0
    assert zero.makespan == pytest.approx(none.makespan)
    assert 1 <= capped.n_backups <= 2                # bounded duplicates
    assert capped.n_backups <= uncapped.n_backups
    assert capped.backup_waste_s <= uncapped.backup_waste_s + 1e-9
    assert capped.makespan < none.makespan           # gains retained
    assert sorted(r.uid for r in capped.records) == sorted(dag.tasks)
