"""Fused decision plane: bit-parity of both sweep engines (NumPy and the
jitted `kernels.decision_plane` dispatch) vs `heft_schedule_matrix`,
dirty-row residency vs full re-gathers, megabatched replans (one
predictive dispatch + one vmapped sweep per cluster group), the Pallas
kernel forms in interpret mode, and the decision-plane roofline model."""
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
from repro.sched.heft import heft_schedule_matrix, upward_ranks
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


# --- engine parity ---------------------------------------------------------------

@settings(max_examples=6, deadline=None)
@given(seed=st.integers(0, 10 ** 6), n_tasks=st.integers(5, 40),
       n_nodes=st.integers(4, 6))
def test_fused_engines_bitwise_match_reference(seed, n_tasks, n_nodes):
    dag, nodes, svc = _build(n_tasks, n_nodes, seed)
    mat = _matrix(dag, nodes, svc)
    cache = {}
    for q in (None, 0.5, 0.95):
        want = heft_schedule_matrix(dag, nodes, mat, quantile=q)
        for engine in ("numpy", "jit"):
            got = fused_heft_schedule(dag, nodes, mat, quantile=q,
                                      rank_cache=cache, engine=engine)
            _same_schedule(got, want)


@settings(max_examples=4, deadline=None)
@given(seed=st.integers(0, 10 ** 6))
def test_fused_engines_match_on_constrained_replans(seed):
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
        want = heft_schedule_matrix(dag, nodes, mat, quantile=0.95,
                                    ready_at=ref_ready, node_available=avail)
        for engine in ("numpy", "jit"):
            got = fused_heft_schedule(dag, nodes, mat, quantile=0.95,
                                      ready_at=ready, node_available=avail,
                                      engine=engine)
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


def test_upward_rank_kernel_matches_host_recurrence():
    import jax

    from repro.kernels import decision_plane as dp
    from repro.sched.heft import comm_structure
    dag, nodes, svc = _build(30, 4, 11)
    mat = _matrix(dag, nodes, svc)
    order = dag.topo_order()
    names = [n.name for n in nodes]
    W = mat.costs(order, names, quantile=0.5)
    same, gbps_min = comm_structure(nodes)
    want = upward_ranks(dag, nodes, W, order, same, gbps_min)

    row_of = {u: i for i, u in enumerate(order)}
    succ = dag.successors()
    width = max(max((len(v) for v in succ.values()), default=1), 1)
    succ_pad = np.full((len(order), width), -1, np.int32)
    for i, u in enumerate(order):
        for k, v in enumerate(succ[u]):
            succ_pad[i, k] = row_of[v]
    n_nodes = len(nodes)
    avg_comm = np.asarray(
        [float(np.where(same, 0.0,
                        (dag.tasks[u].output_gb * 8.0)
                        / gbps_min).ravel().cumsum()[-1]) / n_nodes ** 2
         for u in order])
    w_avg = W.cumsum(axis=1)[:, -1] / n_nodes
    with jax.enable_x64(True):
        got = np.asarray(dp.upward_rank(w_avg, avg_comm, succ_pad))
    want_arr = np.asarray([want[u] for u in order])
    assert np.array_equal(got, want_arr)


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
        want = heft_schedule_matrix(dag, nodes, fresh, quantile=0.95)
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
    _same_schedule(scheds[0], heft_schedule_matrix(dag, nodes, mats[0],
                                                   quantile=0.95))
    _same_schedule(scheds[1], heft_schedule_matrix(dag2, nodes, mats[1],
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
        _same_schedule(s, heft_schedule_matrix(dag, nodes, mat, quantile=q))


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


# --- Pallas kernel forms (interpret mode) ----------------------------------------

def _dyadic_post(T, rng):
    """Posterior rows with dyadic-rational leaves, exact in float32."""
    def d(lo, hi):
        return rng.integers(lo, hi, size=T) / 16.0
    mu = np.stack([d(1, 32), d(1, 16)], axis=1)
    sigma = np.zeros((T, 2, 2))
    sigma[:, 0, 0] = d(1, 8)
    sigma[:, 1, 1] = d(1, 8)
    sigma[:, 0, 1] = sigma[:, 1, 0] = d(0, 4)
    return {"mu": mu, "sigma": sigma, "beta_prec": 1.0 + d(1, 8),
            "x_mu": d(0, 8), "x_sd": 1.0 + d(0, 8),
            "y_mu": d(0, 8), "y_sd": 1.0 + d(0, 8)}


def test_fused_cost_pallas_interpret_matches_ref():
    import jax.numpy as jnp

    from repro.kernels import decision_plane as dp
    rng = np.random.default_rng(23)
    T, N = 12, 8
    x = jnp.asarray(rng.integers(1, 64, size=T) / 16.0, jnp.float32)
    post = {k: jnp.asarray(v, jnp.float32)
            for k, v in _dyadic_post(T, rng).items()}
    factors = jnp.asarray(rng.integers(1, 32, size=(T, N)) / 8.0,
                          jnp.float32)
    for z in (0.0, 1.5):
        want = np.asarray(dp.fused_cost_ref(x, post, factors, z))
        got = np.asarray(dp.fused_cost(x, post, factors, z=z,
                                       interpret=True))
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=0.0)


def test_eft_sweep_pallas_interpret_matches_jit_float32():
    from repro.kernels import decision_plane as dp
    dag, nodes, svc = _build(16, 4, 29)
    mat = _matrix(dag, nodes, svc)
    ctx = fused_mod._PlanContext(dag, nodes)
    W = mat.costs(ctx.order, ctx.names, quantile=0.5)
    rank = ctx.ranks(dag, W)
    pack = fused_mod._sweep_inputs(ctx, dag, nodes, W, rank, None, None)
    f32 = [np.asarray(a, np.float32 if a.dtype.kind == "f" else a.dtype)
           for a in pack]
    want = dp.eft_sweep(*f32, ctx.same.astype(np.float32),
                        np.asarray(ctx.gbps_min, np.float32), S=16)
    got = dp.eft_sweep_pallas(*f32, ctx.same.astype(np.float32),
                              np.asarray(ctx.gbps_min, np.float32),
                              S=16, interpret=True)
    n = len(ctx.order)      # padded (masked) rows are don't-care outputs
    for g, w in zip(got[:3], want[:3]):
        assert np.array_equal(np.asarray(g)[:n], np.asarray(w)[:n])


# --- roofline --------------------------------------------------------------------

def test_decision_plane_roofline_model():
    from repro.perf.roofline import decision_plane_roofline
    t = decision_plane_roofline(1000, 100, dep_width=10)
    d = t.to_dict()
    assert d["bottleneck"] in ("compute", "memory")
    assert 0.0 < d["device_time_model"] < 1e-3    # fleet replan target
    assert t.achieved_fraction(d["device_time_model"]) == pytest.approx(1.0)
    # scaling sanity: 10x the work costs more on both axes
    big = decision_plane_roofline(10000, 100, dep_width=10)
    assert big.flops > t.flops and big.hbm_bytes > t.hbm_bytes


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
