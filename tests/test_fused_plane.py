"""Fused decision plane: bit-parity of both sweeps (NumPy and the jitted
`kernels.decision_plane` dispatch) vs the scalar `heft_schedule_reference`,
dirty-row residency vs full re-gathers, and megabatched replans (one
predictive dispatch + one vmapped sweep per cluster group)."""
import contextlib
import math

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.microbench import simulate_microbench
from repro.core.predictor import LotaruPredictor
from repro.core.traces import TraceRow
from repro.online import OnlinePredictor, PredictionService
from repro.online.events import TaskCompletion
from repro.sched import fused as fused_mod
from repro.sched.cluster import LOCAL, TARGET_MACHINES
from repro.sched.fused import (FusedPlane, ReplanRequest,
                               fused_heft_schedule, replan_many)
from repro.sched.heft import heft_schedule_reference
from repro.sched.plane import PredictionMatrix
from repro.store import compute
from repro.store.posterior import PosteriorStore
from repro.workflow.dag import TaskInstance, WorkflowDAG
from repro.workflow.simulator import random_cluster

TASK_TYPES = ("bwa", "idx", "dedup", "qc", "merge", "report")


def _predictor():
    traces = []
    for j, t in enumerate(TASK_TYPES):
        traces += [TraceRow("wf", t, "local", s, 2.0 + j + (15.0 + 6 * j) * s)
                   for s in np.linspace(0.05, 0.4, 6)]
    lot = LotaruPredictor("G", local_bench=simulate_microbench(LOCAL, 1))
    lot.fit(traces)
    return lot


def _build(n_tasks, n_nodes, seed, online=False, store=None):
    rng = np.random.default_rng(seed)
    lot = _predictor()
    pred = OnlinePredictor(lot) if online else lot
    nodes = random_cluster(rng, list(TARGET_MACHINES), n_nodes=n_nodes)
    benches = {n.name: simulate_microbench(n, 1) for n in nodes}
    svc = PredictionService(pred, benches, store=store)
    dag = WorkflowDAG("fused")
    for i in range(n_tasks):
        deps = [f"t{j}" for j in range(i)
                if rng.random() < min(3.0 / max(i, 1), 0.5)]
        dag.add(TaskInstance(f"t{i}", TASK_TYPES[i % len(TASK_TYPES)],
                             "fused", float(rng.uniform(0.05, 4.0)),
                             output_gb=float(rng.uniform(0.0, 2.0)),
                             deps=deps))
    return dag, nodes, svc


def _matrix(dag, nodes, svc):
    entries = [(u, dag.tasks[u].task_name, dag.tasks[u].input_gb)
               for u in dag.tasks]
    return PredictionMatrix.from_service(svc, entries, nodes)


def _same_schedule(a, b):
    assert a.assignment == b.assignment
    assert a.order == b.order
    assert a.est == b.est


def _reference(dag, nodes, mat, quantile=None, **kw):
    """`heft_schedule_reference` through a callable over the cost matrix
    `mat` gives at `quantile` — the costs the fused engine schedules on."""
    uids = list(dag.tasks)
    W = mat.costs(uids, [n.name for n in nodes], quantile=quantile)
    row = {u: i for i, u in enumerate(uids)}
    col = {n.name: j for j, n in enumerate(nodes)}
    return heft_schedule_reference(
        dag, nodes, lambda u, n: float(W[row[u], col[n.name]]), **kw)


@contextlib.contextmanager
def _engine(name):
    """Hold `fused_heft_schedule` to one sweep at every size: 'jit' or
    'numpy'.  A context, not a fixture: hypothesis refuses
    function-scoped fixtures."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(fused_mod, "_JIT_MIN_CELLS",
                   0 if name == "jit" else math.inf)
        yield


# --- engine parity ---------------------------------------------------------------

@pytest.mark.parametrize("engine", ["numpy", "jit"])
@settings(max_examples=6, deadline=None)
@given(seed=st.integers(0, 10 ** 6), n_tasks=st.integers(5, 40),
       n_nodes=st.integers(4, 6))
def test_fused_engines_bitwise_match_reference(engine, seed, n_tasks,
                                               n_nodes):
    dag, nodes, svc = _build(n_tasks, n_nodes, seed)
    mat = _matrix(dag, nodes, svc)
    cache = {}
    for q in (None, 0.5, 0.95):
        want = _reference(dag, nodes, mat, quantile=q)
        with _engine(engine):
            got = fused_heft_schedule(dag, nodes, mat, quantile=q,
                                      rank_cache=cache)
        _same_schedule(got, want)


@pytest.mark.parametrize("engine", ["numpy", "jit"])
@settings(max_examples=4, deadline=None)
@given(seed=st.integers(0, 10 ** 6))
def test_fused_engines_match_on_constrained_replans(engine, seed):
    """node_available busy prefixes + external ready times (dict,
    callable, and precomputed-array forms) — the shapes `_replan` uses."""
    rng = np.random.default_rng(seed)
    dag, nodes, svc = _build(24, 4, seed)
    mat = _matrix(dag, nodes, svc)
    avail = {n.name: float(rng.uniform(0.0, 30.0)) for n in nodes}
    ready_d = {u: float(rng.uniform(0.0, 20.0)) for u in dag.tasks}

    def ready_fn(uid, node):
        return ready_d[uid] + 0.25 * (hash(node.name) % 7)

    order = dag.topo_order()
    ready_arr = np.asarray([[ready_fn(u, n) for n in nodes] for u in order])
    for ready in (ready_d, ready_fn, ready_arr):
        # the reference takes dict/callable only; the (T, N) array form is
        # the fused engine's extension, built here from the same callable
        ref_ready = ready_fn if isinstance(ready, np.ndarray) else ready
        want = _reference(dag, nodes, mat, quantile=0.95,
                          ready_at=ref_ready, node_available=avail)
        with _engine(engine):
            got = fused_heft_schedule(dag, nodes, mat, quantile=0.95,
                                      ready_at=ready, node_available=avail)
        _same_schedule(got, want)


def test_auto_engine_policy_is_size_based(monkeypatch):
    dag, nodes, svc = _build(20, 4, 3)
    mat = _matrix(dag, nodes, svc)
    calls = []
    real = fused_mod._schedule_jit
    monkeypatch.setattr(fused_mod, "_schedule_jit",
                        lambda *a, **k: calls.append(1) or real(*a, **k))
    fused_heft_schedule(dag, nodes, mat)           # 80 cells < threshold
    assert not calls
    monkeypatch.setattr(fused_mod, "_JIT_MIN_CELLS", 1)
    fused_heft_schedule(dag, nodes, mat)
    assert calls


def _frontier(dag, started, name):
    """The unstarted sub-DAG: what `OnlineReschedulingPlanner._replan`
    plans after the tasks in `started` were booked."""
    sub = WorkflowDAG(name)
    for u in dag.topo_order():
        if u in started:
            continue
        t = dag.tasks[u]
        sub.add(TaskInstance(u, t.task_name, t.workflow, t.input_gb,
                             t.output_gb, deps=[d for d in t.deps
                                                if d not in started]))
    return sub


@pytest.mark.parametrize("seed", range(4))
def test_frontiers_of_a_run_share_one_dep_width(seed):
    """A replan's frontier is the complement of a set closed under
    dependencies, so its fan-in is at most the whole DAG's.  Passed that
    fan-in as `dep_width`, every frontier packs its dependencies to one
    width (its jitted sweeps differ in the task bucket alone) and the
    schedule is still bitwise HEFT; a DAG planned on its own keeps the
    narrowest bucket."""
    rng = np.random.default_rng(seed)
    dag, nodes, svc = _build(40, 4, seed)
    mat = _matrix(dag, nodes, svc)
    fan_in = max(len(t.deps) for t in dag.tasks.values())
    bucket = -(-fan_in // fused_mod._DEP_BUCKET) * fused_mod._DEP_BUCKET
    assert fused_mod._PlanContext(dag, nodes).dep_rows.shape[1] == bucket
    widths, own = set(), set()
    for k in range(12):
        started, p = set(), rng.uniform(0.1, 0.9)
        for u in dag.topo_order():
            if all(d in started for d in dag.tasks[u].deps) \
                    and rng.random() < p:
                started.add(u)
        sub = _frontier(dag, started, dag.name)
        if not sub.tasks:
            continue
        widths.add(fused_mod._PlanContext(sub, nodes, fan_in)
                   .dep_rows.shape[1])
        own.add(fused_mod._PlanContext(sub, nodes).dep_rows.shape[1])
        if k < 3:
            sub_mat = PredictionMatrix(
                tuple(sub.tasks), mat.node_names,
                mat.means[[mat.uid_index[u] for u in sub.tasks]],
                mat.stds[[mat.uid_index[u] for u in sub.tasks]])
            want = _reference(sub, nodes, sub_mat, quantile=0.95)
            with _engine("jit"):
                got = fused_heft_schedule(sub, nodes, sub_mat,
                                          quantile=0.95,
                                          dep_width=fan_in + 5)
            _same_schedule(got, want)
    assert widths == {bucket}
    assert max(own) <= bucket


# --- residency: dirty rows vs full re-gather -------------------------------------

def test_dirty_row_update_matches_full_regather():
    """Interleave observes (stream drift) with plane syncs: the resident
    rows must stay bitwise what a cold full gather computes, while only
    the dirty subset is re-predicted (block-granular)."""
    store = PosteriorStore(block_size=1)
    dag, nodes, svc = _build(36, 4, 7, online=True, store=store)
    plane = FusedPlane(svc, nodes, dag=dag)
    online = svc.predictor
    rng = np.random.default_rng(0)
    n_rows = len(plane.uids)
    for step, drift_type in enumerate(("bwa", "merge", "qc")):
        for k in range(4):
            online.observe(TaskCompletion(
                "fused", f"obs{step}-{k}", drift_type, "local",
                float(rng.uniform(0.1, 0.5)),
                float(rng.uniform(10.0, 60.0)),
                finish_time=float(step * 10 + k)))
        mat = plane.matrix()
        fresh = _matrix(dag, nodes, svc)
        assert np.array_equal(mat.means, fresh.means)
        assert np.array_equal(mat.stds, fresh.stds)
        got = plane.schedule(dag, quantile=0.95)
        want = _reference(dag, nodes, fresh, quantile=0.95)
        _same_schedule(got, want)
    # residency did real work: one full gather, then dirty subsets only
    assert plane.stats.full_gathers == 1
    refreshed_after_first = plane.stats.rows_refreshed - n_rows
    assert 0 < refreshed_after_first < 2 * n_rows


def test_plane_matrix_cached_until_store_moves():
    dag, nodes, svc = _build(12, 4, 5, online=True)
    plane = FusedPlane(svc, nodes, dag=dag)
    m1 = plane.matrix()
    m2 = plane.matrix()
    assert m1 is m2
    assert plane.stats.matrix_rebuilds == 1
    assert plane.stats.cost_rebuilds == 0
    plane.schedule(dag, quantile=0.95)
    plane.schedule(dag, quantile=0.95)
    assert plane.stats.cost_rebuilds == 1      # resident (T, N) cost view


# --- megabatched replans ---------------------------------------------------------

def test_replan_many_single_predict_dispatch(monkeypatch):
    store = PosteriorStore()
    dag, nodes, svc = _build(20, 4, 9, store=store)
    dag2, _, _ = _build(15, 4, 10)
    planes = [FusedPlane(svc, nodes, dag=dag), FusedPlane(svc, nodes, dag=dag2)]
    calls = []
    real = compute.predict_stacked
    monkeypatch.setattr(compute, "predict_stacked",
                        lambda *a, **k: calls.append(1) or real(*a, **k))
    scheds = replan_many([ReplanRequest(plane=planes[0], dag=dag,
                                        quantile=0.95),
                          ReplanRequest(plane=planes[1], dag=dag2,
                                        quantile=0.95)])
    assert len(calls) == 1            # both planes' rows in ONE dispatch
    mats = [_matrix(dag, nodes, svc), _matrix(dag2, nodes, svc)]
    _same_schedule(scheds[0], _reference(dag, nodes, mats[0],
                                         quantile=0.95))
    _same_schedule(scheds[1], _reference(dag2, nodes, mats[1],
                                         quantile=0.95))


def test_replan_many_fuses_same_cluster_sweeps(monkeypatch):
    from repro.kernels import decision_plane as dp
    dag, nodes, svc = _build(40, 4, 13)
    planes = [FusedPlane(svc, nodes, dag=dag) for _ in range(3)]
    monkeypatch.setattr(fused_mod, "_JIT_MIN_CELLS", 1)
    calls = []
    real = dp.eft_sweep_many
    monkeypatch.setattr(dp, "eft_sweep_many",
                        lambda *a, **k: calls.append(1) or real(*a, **k))
    reqs = [ReplanRequest(plane=p, dag=dag, quantile=q)
            for p, q in zip(planes, (None, 0.5, 0.95))]
    scheds = replan_many(reqs)
    assert len(calls) == 1            # three tenants, one vmapped sweep
    mat = _matrix(dag, nodes, svc)
    for s, q in zip(scheds, (None, 0.5, 0.95)):
        _same_schedule(s, _reference(dag, nodes, mat, quantile=q))


def test_eft_sweep_many_lanes_match_single():
    import jax

    from repro.kernels import decision_plane as dp
    dag, nodes, svc = _build(30, 4, 17)
    mat = _matrix(dag, nodes, svc)
    ctx = fused_mod._PlanContext(dag, nodes)
    packs = []
    for q in (0.5, 0.95):
        W = mat.costs(ctx.order, ctx.names, quantile=q)
        rank = ctx.ranks(dag, W)
        packs.append(fused_mod._sweep_inputs(ctx, dag, nodes, W, rank,
                                             None, None))
    stacked = [np.stack([p[k] for p in packs]) for k in range(6)]
    with jax.enable_x64(True):
        many = dp.eft_sweep_many(*stacked, ctx.same, ctx.gbps_min, S=16)
        many = [np.asarray(a) for a in many]
        for b, p in enumerate(packs):
            single = dp.eft_sweep(*p, ctx.same, ctx.gbps_min, S=16)
            for lane, one in zip(many, single):
                assert np.array_equal(lane[b], np.asarray(one))


# --- rescheduler residency -------------------------------------------------------

def test_rescheduler_serves_from_resident_plane():
    from repro.online import OnlineReschedulingPlanner
    from repro.workflow.simulator import execute_adaptive
    rng = np.random.default_rng(41)
    dag, nodes, svc = _build(18, 4, 41)
    lot = _predictor()
    online = OnlinePredictor(lot)
    planner = OnlineReschedulingPlanner(
        dag, nodes, online,
        benches={n.name: simulate_microbench(n, 1) for n in nodes},
        z=0.5, quantile=0.95)
    def true_runtime(uid, node):
        t = dag.tasks[uid]
        base = 2.0 + 20.0 * t.input_gb
        return base * float(rng.uniform(0.8, 1.6))

    result = execute_adaptive(dag, nodes, planner, true_runtime)
    assert {r.uid for r in result.records} == set(dag.tasks)
    st_ = planner._plane.stats
    assert st_.full_gathers == 1          # resident rows, never rebuilt
    assert st_.rounds >= 1
