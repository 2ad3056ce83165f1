"""Online prediction subsystem: exact streaming updates, batched predict
kernel parity, service consistency, and in-flight rescheduling safety."""
import numpy as np
import pytest

from repro.core import bayes
from repro.core.microbench import simulate_microbench
from repro.core.predictor import LotaruPredictor
from repro.core.traces import TraceRow
from repro.online import (OnlinePredictor, OnlineReschedulingPlanner,
                          PredictionService, TaskCompletion)
from repro.online.events import PredictionQuery
from repro.sched.cluster import LOCAL, PAPER_MACHINES, TARGET_MACHINES
from repro.sched.fused import fused_heft_schedule
from repro.workflow.generator import GroundTruth, build_workflow
from repro.workflow.profiling import local_profiling
from repro.workflow.simulator import execute_adaptive, execute_schedule


def _fitted_post(rng, n=8, slope=30.0, base=4.0, noise=0.2):
    x = rng.uniform(0.1, 0.5, n).astype(np.float32)
    y = (base + slope * x + rng.normal(0, noise, n)).astype(np.float32)
    return {k: np.asarray(v) for k, v in bayes.fit_blr(x, y).items()}


# --- conjugate streaming updates ------------------------------------------------
def test_incremental_update_equals_batch_refit(rng):
    """folding observations in one at a time == the closed-form posterior
    from the same prior and all observations at once (conjugate exactness)."""
    nig0 = bayes.nig_from_blr(_fitted_post(rng))
    x_new = rng.uniform(0.5, 6.0, 9)
    y_new = 4 + 30 * x_new + rng.normal(0, 0.2, 9)
    inc = nig0
    for a, b in zip(x_new, y_new):
        inc = bayes.nig_update(inc, a, b)
    bat = bayes.nig_refit(nig0, x_new, y_new)
    np.testing.assert_allclose(inc["mu"], bat["mu"], rtol=1e-9, atol=1e-12)
    np.testing.assert_allclose(inc["v"], bat["v"], rtol=1e-9, atol=1e-15)
    np.testing.assert_allclose(inc["prec"], bat["prec"], rtol=1e-9)
    assert inc["a"] == pytest.approx(bat["a"])
    assert inc["b"] == pytest.approx(bat["b"], rel=1e-6)


def test_nig_lift_preserves_predictive(rng):
    """lifting to NIG and exporting back is predictive-exact."""
    post = _fitted_post(rng)
    back = bayes.nig_to_blr(bayes.nig_from_blr(post))
    for xq in (0.3, 2.0, 10.0):
        m0, s0 = bayes.predict_blr(post, np.float32(xq))
        m1, s1 = bayes.predict_blr(back, np.float32(xq))
        assert float(m0) == pytest.approx(float(m1), rel=1e-5)
        assert float(s0) == pytest.approx(float(s1), rel=1e-4)


def test_posterior_std_shrinks_monotonically(rng):
    """weight uncertainty phi V phi never increases under rank-1 precision
    updates, and the predictive std contracts on consistent data."""
    nig = bayes.nig_from_blr(_fitted_post(rng, noise=0.0))
    phi_eval = np.array([1.0, (3.0 - nig["x_mu"]) / nig["x_sd"]])
    weight_terms = [phi_eval @ nig["v"] @ phi_eval]
    stds = [float(bayes.predict_blr(bayes.nig_to_blr(nig),
                                    np.float32(3.0))[1])]
    for x in np.linspace(0.5, 5.0, 12):
        nig = bayes.nig_update(nig, x, 4 + 30 * x)
        weight_terms.append(phi_eval @ nig["v"] @ phi_eval)
        stds.append(float(bayes.predict_blr(bayes.nig_to_blr(nig),
                                            np.float32(3.0))[1]))
    assert all(b <= a + 1e-12 for a, b in zip(weight_terms, weight_terms[1:]))
    assert stds[-1] < stds[0]


def test_constant_posterior_predictive():
    post = bayes.constant_posterior(30.0, 2.5)
    for xq in (0.1, 50.0):
        m, s = bayes.predict_blr(post, np.float32(xq))
        assert float(m) == pytest.approx(30.0, rel=1e-6)
        assert float(s) == pytest.approx(2.5, rel=1e-5)


# --- batched predict kernel ------------------------------------------------------
@pytest.mark.parametrize("impl", ["ref", "interpret"])
def test_batched_predict_matches_scalar(rng, impl):
    """vmap reference and Pallas kernel both reproduce the scalar
    predict_blr loop (atol 1e-4) at >=1024 queries."""
    import jax.numpy as jnp
    from repro.kernels import ops
    posts = [_fitted_post(rng, slope=10 + 7 * i, base=1 + 2 * i)
             for i in range(5)]
    q = 1200
    idx = rng.integers(0, len(posts), q)
    stack = {k: np.stack([posts[i][k] for i in idx]).astype(np.float32)
             for k in posts[0]}
    xq = rng.uniform(0.2, 9.0, q).astype(np.float32)
    mean, std = ops.bayes_predict(
        jnp.asarray(xq), {k: jnp.asarray(v) for k, v in stack.items()},
        impl=impl)
    for j in rng.choice(q, 64, replace=False):
        m, s = bayes.predict_blr(posts[idx[j]], np.float32(xq[j]))
        assert abs(float(mean[j]) - float(m)) < 1e-4
        assert abs(float(std[j]) - float(s)) < 1e-4


def _local_traces(task="bwa", n=6, slope=30.0, base=4.0):
    return [TraceRow("wf", task, "local", s, base + slope * s)
            for s in np.linspace(0.05, 0.4, n)]


def test_service_predict_rows_matches_scalar_predict():
    lot = LotaruPredictor("G", local_bench=simulate_microbench(LOCAL, 1))
    lot.fit(_local_traces("bwa") + _local_traces("idx", slope=12, base=2))
    dag = build_workflow("bacass", seed=0)
    benches = {n.name: simulate_microbench(n, 1) for n in TARGET_MACHINES}
    svc = PredictionService(lot, benches)
    queries = [PredictionQuery(t, n.name, x)
               for t in ("bwa", "idx") for n in TARGET_MACHINES
               for x in (0.1, 1.0, 4.0)]
    out = svc.predict_batch(queries)
    for q, (m, lo, hi) in zip(queries, out):
        m2, lo2, hi2 = lot.predict(q.task, q.input_gb, benches[q.node])
        assert m == pytest.approx(m2, rel=1e-4, abs=1e-3)
        assert lo == pytest.approx(lo2, rel=1e-4, abs=1e-3)
        assert hi == pytest.approx(hi2, rel=1e-4, abs=1e-3)


def test_service_local_query_and_unknown_node():
    """node=None means local (factor 1) for any predictor; an unknown node
    name raises instead of silently mispredicting."""
    lot = LotaruPredictor("G", local_bench=simulate_microbench(LOCAL, 1))
    lot.fit(_local_traces())
    svc = PredictionService(lot)
    m, lo, hi = svc.predict_batch([PredictionQuery("bwa", None, 2.0)])[0]
    m2, lo2, hi2 = lot.predict("bwa", 2.0, None)
    assert m == pytest.approx(m2, rel=1e-6)
    with pytest.raises(KeyError):
        svc.predict_batch([PredictionQuery("bwa", "no-such-node", 2.0)])


def test_observe_unknown_node_is_dropped_not_local():
    """a completion from an unresolvable node must not be folded into the
    local posterior as if factor were 1."""
    lot = LotaruPredictor("G", local_bench=simulate_microbench(LOCAL, 1))
    lot.fit(_local_traces())
    online = OnlinePredictor(lot)         # no benches registered
    before = online.predict("bwa", 2.0)[0]
    for i in range(6):
        online.observe(TaskCompletion("wf", f"u{i}", "bwa", "mystery-node",
                                      2.0, 9999.0))
    assert online.predict("bwa", 2.0)[0] == pytest.approx(before, rel=1e-9)


def test_service_restacks_after_observation():
    """service predictions must track the online predictor's version."""
    lot = LotaruPredictor("G", local_bench=simulate_microbench(LOCAL, 1))
    lot.fit(_local_traces())
    online = OnlinePredictor(lot)
    svc = PredictionService(online)
    before = svc.predict_batch([PredictionQuery("bwa", None, 2.0)])[0][0]
    for _ in range(6):
        online.observe(TaskCompletion("wf", "u", "bwa", "local", 2.0, 200.0))
    after = svc.predict_batch([PredictionQuery("bwa", None, 2.0)])[0][0]
    assert after != pytest.approx(before, rel=1e-6)
    assert after > before          # pulled toward the 200s observations


# --- online predictor learning ----------------------------------------------------
def test_online_local_updates_converge_to_truth():
    """streamed local completions at full scale correct an extrapolation
    the downsampled profile got wrong."""
    lot = LotaruPredictor("G", local_bench=simulate_microbench(LOCAL, 1))
    lot.fit(_local_traces(slope=30, base=4))
    online = OnlinePredictor(lot)
    # the real (local) relation at production scale has a steeper slope
    for i, x in enumerate(np.linspace(1.0, 6.0, 10)):
        online.observe(TaskCompletion("wf", f"u{i}", "bwa", "local",
                                      float(x), 4 + 40 * float(x)))
    pred = online.predict("bwa", 8.0)[0]
    static = lot.predict("bwa", 8.0)[0]
    truth = 4 + 40 * 8.0
    assert abs(pred - truth) < abs(static - truth)
    assert pred == pytest.approx(truth, rel=0.1)


def test_node_factor_recalibration_converges():
    """a node 2x slower than its benchmark claims is corrected from
    observed/predicted ratios (across distinct tasks)."""
    local_bench = simulate_microbench(LOCAL, 1)
    lot = LotaruPredictor("G", local_bench=local_bench)
    tasks = ["t1", "t2", "t3", "t4"]
    traces = []
    for j, t in enumerate(tasks):
        traces += _local_traces(t, slope=20 + 5 * j, base=3 + j)
    lot.fit(traces)
    bench = simulate_microbench(PAPER_MACHINES["N2"], 1)
    online = OnlinePredictor(lot, benches={"N2": bench})
    miss = 2.0                     # node actually 2x slower than benchmarked
    for i in range(12):
        t = tasks[i % len(tasks)]
        x = 1.0 + (i % 3)
        true_local = lot.predict(t, x)[0]
        runtime = true_local * lot.factor(t, bench) * miss
        online.observe(TaskCompletion("wf", f"u{i}", t, "N2", x, runtime))
    corr = online.node_stats["N2"].correction
    assert 1.5 < corr <= 2.2
    # predictions on the degraded node improve accordingly
    t, x = "t1", 2.0
    truth = lot.predict(t, x)[0] * lot.factor(t, bench) * miss
    e_static = abs(lot.predict(t, x, bench)[0] - truth) / truth
    e_online = abs(online.predict(t, x, bench)[0] - truth) / truth
    assert e_online < e_static


def test_online_median_task_scale_fix():
    """one full-scale observation of a weakly-correlated merge task fixes
    the downsampled-median underestimate (the paper's known weakness)."""
    rows = [TraceRow("wf", "multiqc", "local", s, r)
            for s, r in zip([0.1, 0.2, 0.3, 0.4], [30, 29, 31, 30])]
    lot = LotaruPredictor("G", local_bench=simulate_microbench(LOCAL, 1))
    lot.fit(rows)
    online = OnlinePredictor(lot)
    assert online.predict("multiqc", 50.0)[0] == pytest.approx(30, abs=1.5)
    for i in range(3):
        online.observe(TaskCompletion("wf", f"m{i}", "multiqc", "local",
                                      50.0, 300.0))
    assert online.predict("multiqc", 50.0)[0] == pytest.approx(300, rel=0.2)


# --- in-flight rescheduling --------------------------------------------------------
def _experiment(wf="bacass"):
    gt = GroundTruth(wf, seed=0)
    traces, _ = local_profiling(wf, gt, training_set=0)
    local_bench = simulate_microbench(LOCAL, 1)
    benches = {n.name: simulate_microbench(n, 1) for n in TARGET_MACHINES}
    lot = LotaruPredictor("G", local_bench=local_bench).fit(traces)
    return gt, build_workflow(wf, seed=0), lot, benches


def test_adaptive_execution_respects_dag_dependencies():
    gt, dag, lot, benches = _experiment("bacass")
    nodes = list(TARGET_MACHINES)
    slow = {"C2": 4.0, "N2": 2.5}     # nodes far slower than benchmarked
    true_rt = lambda u, n: gt.runtime(dag.tasks[u].task_name,
                                      dag.tasks[u].input_gb, n, u) \
        * slow.get(n.name, 1.0)
    online = OnlinePredictor(lot, benches=benches)
    planner = OnlineReschedulingPlanner(dag, nodes, online, benches=benches)
    res = execute_adaptive(dag, nodes, planner, true_rt)
    assert len(res.records) == len(dag.tasks)
    start = {r.uid: r.start for r in res.records}
    finish = {r.uid: r.finish for r in res.records}
    for u, t in dag.tasks.items():
        for d in t.deps:
            assert finish[d] <= start[u] + 1e-9, (d, u)
    # no node runs two tasks at once
    for node, iv in res.node_busy.items():
        iv = sorted(iv)
        for (a0, a1), (b0, b1) in zip(iv, iv[1:]):
            assert a1 <= b0 + 1e-9, (node, a1, b0)


def test_adaptive_recovers_makespan_under_degraded_nodes():
    gt, dag, lot, benches = _experiment("eager")
    nodes = list(TARGET_MACHINES)
    slow = {"C2": 4.0}
    true_rt = lambda u, n: gt.runtime(dag.tasks[u].task_name,
                                      dag.tasks[u].input_gb, n, u) \
        * slow.get(n.name, 1.0)
    pred_rt = lambda u, n: lot.predict(dag.tasks[u].task_name,
                                       dag.tasks[u].input_gb,
                                       benches[n.name])[0]
    static = execute_schedule(dag, fused_heft_schedule(dag, nodes, pred_rt),
                              nodes, true_rt)
    online = OnlinePredictor(lot, benches=benches)
    planner = OnlineReschedulingPlanner(dag, nodes, online, benches=benches)
    adaptive = execute_adaptive(dag, nodes, planner, true_rt)
    assert adaptive.n_reschedules >= 1
    assert adaptive.makespan < static.makespan


def test_on_complete_hook_sees_every_completion():
    gt, dag, lot, benches = _experiment("bacass")
    nodes = list(TARGET_MACHINES)
    true_rt = lambda u, n: gt.runtime(dag.tasks[u].task_name,
                                      dag.tasks[u].input_gb, n, u)
    sched = fused_heft_schedule(dag, nodes, true_rt)
    seen = []
    execute_schedule(dag, sched, nodes, true_rt,
                     on_complete=lambda rec, state: seen.append(rec.uid))
    assert sorted(seen) == sorted(dag.tasks)
