"""The in-program tracer: off by default, self times of nested spans,
threads recording at once, and the spans and counters of one front-end
dispatch, one fleet refresh pass and one workflow run replanned on
drift."""
import sys
import threading
import time

import numpy as np
import pytest

from repro import obs
from repro.online import (FleetRefresher, OnlinePredictor,
                          OnlineReschedulingPlanner, PredictionService,
                          RefreshPolicy)
from repro.sched.cluster import TARGET_MACHINES
from repro.store import AsyncPredictionFrontend, PosteriorStore
from repro.workflow.simulator import execute_adaptive

from test_online import _experiment
from test_refresh import _observe_local
from test_store import _benches, _fit, _queries


@pytest.fixture
def tracing():
    """The process's tracer, on and empty for one test, off after it."""
    obs.reset()
    obs.enable()
    try:
        yield
    finally:
        obs.disable()
        obs.reset()


def test_off_by_default_records_nothing():
    assert not obs.enabled()
    obs.reset()
    assert obs.span("lotaru.a.b", dispatch=1) is obs.span("lotaru.c.d")
    with obs.span("lotaru.a.b"):
        obs.count("lotaru.a.bytes", 10)
    assert obs.stamp() is None
    obs.since("lotaru.a.queue", [1.0])
    assert obs.snapshot() == {"spans": {}, "counters": {}}


def test_nested_self_time_is_total_minus_children():
    t = obs.Tracer()
    t.enable()
    with t.span("lotaru.x.outer"):
        time.sleep(0.01)
        with t.span("lotaru.x.inner"):
            time.sleep(0.02)
        with t.span("lotaru.x.inner"):
            time.sleep(0.01)
    t.disable()
    s = t.snapshot()["spans"]
    outer, inner = s["lotaru.x.outer"], s["lotaru.x.inner"]
    assert outer["count"] == 1 and inner["count"] == 2
    assert inner["self_s"] == inner["total_s"] >= 0.03
    assert outer["total_s"] >= 0.04
    assert outer["self_s"] == pytest.approx(
        outer["total_s"] - inner["total_s"], abs=1e-9)
    assert 0.01 <= outer["self_s"] < outer["total_s"]


def test_two_threads_record_at_once():
    """Each thread's stack is its own: no child time leaks into the other
    thread's parent, and no count or counter update is lost."""
    t = obs.Tracer()
    t.enable()
    n = 300
    barrier = threading.Barrier(2)

    def work(name):
        barrier.wait()
        for _ in range(n):
            with t.span(f"lotaru.{name}.outer"):
                with t.span(f"lotaru.{name}.inner"):
                    pass
                t.count("lotaru.both.calls", 1)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=work, args=(k,))
                   for k in ("a", "b")]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=30)
        assert not any(th.is_alive() for th in threads)
    finally:
        sys.setswitchinterval(old)
    snap = t.snapshot()
    assert snap["counters"]["lotaru.both.calls"] == 2 * n
    for k in ("a", "b"):
        outer = snap["spans"][f"lotaru.{k}.outer"]
        inner = snap["spans"][f"lotaru.{k}.inner"]
        assert outer["count"] == inner["count"] == n
        assert outer["self_s"] == pytest.approx(
            outer["total_s"] - inner["total_s"], abs=1e-9)


def test_one_frontend_dispatch(tracing):
    """Three caller batches, one manual flush: three queue waits, one each
    of flush, gather, predict, host packing and readback, and the bytes
    really shipped: the packed float32 buffer of 11 planes over the padded
    queries in, the mean and std planes out."""
    store = PosteriorStore()
    svc = PredictionService(_fit(("bwa", "idx")), _benches(), store=store,
                            tenant="a", workflow="w")
    fe = AsyncPredictionFrontend(store, impl="interpret", auto_flush=False)
    batches = [_queries(["bwa", "idx"], [None, "N1"], xs=(0.3 * i + 0.5,))
               for i in range(3)]
    futs = [fe.predict_async(qs, tenant="a", workflow="w") for qs in batches]
    assert fe.flush() == 3
    q = sum(len(qs) for qs in batches)
    snap = obs.snapshot()
    sp, ct = snap["spans"], snap["counters"]
    assert sp["lotaru.frontend.queue"]["count"] == 3
    for name in ("lotaru.frontend.flush", "lotaru.store.gather",
                 "lotaru.compute.predict", "lotaru.compute.readback"):
        assert sp[name]["count"] == 1, name
    assert sp["lotaru.compute.pad"]["count"] == 1
    qp = 1024                                          # one padded tile
    assert q < qp
    assert ct["lotaru.compute.h2d_bytes"] == 4 * 11 * qp
    assert ct["lotaru.compute.d2h_bytes"] == 4 * 2 * qp
    flush = sp["lotaru.frontend.flush"]
    children = (sp["lotaru.store.gather"]["total_s"]
                + sp["lotaru.compute.predict"]["total_s"])
    assert flush["self_s"] == pytest.approx(flush["total_s"] - children,
                                            abs=1e-9)
    for qs, fut in zip(batches, futs):
        np.testing.assert_allclose(fut.result(timeout=5),
                                   svc.predict_batch(qs), rtol=1e-5)


def test_one_refresh_pass(tracing, rng):
    store = PosteriorStore()
    for tenant in ("acme", "globex"):
        online = OnlinePredictor(_fit(("bwa", "idx")))
        svc = PredictionService(online, store=store, tenant=tenant,
                                workflow="w")
        _observe_local(online, "bwa", 6, rng)
        _observe_local(online, "idx", 5, rng, slope=12.0)
        svc.predict_batch(_queries(["bwa"], [None]))
    report = FleetRefresher(store, RefreshPolicy(every_n=4)).refresh()
    assert report.n_tasks == 4
    snap = obs.snapshot()
    sp, ct = snap["spans"], snap["counters"]
    phases = ("due", "prepare", "fit", "apply")
    for name in ("pass",) + phases:
        assert sp[f"lotaru.refresh.{name}"]["count"] == 1, name
    total = sp["lotaru.refresh.pass"]["total_s"]
    inside = sum(sp[f"lotaru.refresh.{p}"]["total_s"] for p in phases)
    assert inside <= total
    assert sp["lotaru.refresh.pass"]["self_s"] == pytest.approx(
        total - inside, abs=1e-9)
    assert 0 < ct["lotaru.refresh.fit_points"] <= ct[
        "lotaru.refresh.fit_cells"]
    assert ct["lotaru.refresh.fit_cells"] % 4 == 0     # 4 rows x N columns


def _replanned_run():
    """eager on the five Table 2 targets with C2 four times slower than
    benchmarked: the planner replans on drift."""
    gt, dag, lot, benches = _experiment("eager")
    nodes = list(TARGET_MACHINES)
    true_rt = lambda u, n: gt.runtime(dag.tasks[u].task_name,
                                      dag.tasks[u].input_gb, n, u) \
        * {"C2": 4.0}.get(n.name, 1.0)
    planner = OnlineReschedulingPlanner(
        dag, nodes, OnlinePredictor(lot, benches=benches), benches=benches,
        quantile=0.95)
    execute_adaptive(dag, nodes, planner, true_rt)
    return planner, dag, nodes


def test_replanned_run_spans_agree_with_planner_stats(tracing):
    planner, dag, nodes = _replanned_run()
    st, ps = planner.stats, planner.plane.stats
    assert st.reschedules >= 1
    passes = 1 + st.reschedules                  # the first plan, replans
    snap = obs.snapshot()
    sp, ct = snap["spans"], snap["counters"]
    assert (ct["lotaru.plan.completions"] == st.completions
            == sp["lotaru.plan.completion"]["count"]
            == sp["lotaru.plan.observe"]["count"] == len(dag.tasks))
    assert (ct["lotaru.plan.replans"] == sp["lotaru.plan.replan"]["count"]
            == st.reschedules)
    assert sp["lotaru.plan.initial"]["count"] == 1
    assert st.drift_events <= sp["lotaru.plan.drift"]["count"] < len(
        dag.tasks)
    cells = ct["lotaru.plan.frontier_cells"]
    assert cells % len(nodes) == 0
    assert st.reschedules * len(nodes) <= cells < passes * len(
        dag.tasks) * len(nodes)
    assert ct["lotaru.plane.rows_refreshed"] == ps.rows_refreshed
    assert ct["lotaru.plane.predict_dispatches"] == ps.predict_dispatches
    assert sp["lotaru.plane.sync"]["count"] == ps.rounds == passes
    assert sp["lotaru.plane.cost"]["count"] == 2 * passes
    assert sp["lotaru.sched.rank"]["count"] == 2 * passes
    assert sp["lotaru.sched.ready"]["count"] == st.reschedules
    for name in ("lotaru.sched.sweep", "lotaru.sched.build"):
        assert sp[name]["count"] == passes, name
    # every predict call is a drift check, a replan's running tasks or a
    # plane dispatch; each drift check asks at least one query
    calls = sp["lotaru.compute.predict"]["count"]
    assert (sp["lotaru.plan.drift"]["count"] + ps.predict_dispatches
            <= calls <= sp["lotaru.plan.drift"]["count"]
            + ps.predict_dispatches + st.reschedules)
    assert ct["lotaru.compute.queries"] >= (ps.rows_refreshed
                                            + sp["lotaru.plan.drift"]["count"])
    done = sp["lotaru.plan.completion"]
    inside = sum(sp[f"lotaru.plan.{k}"]["total_s"]
                 for k in ("observe", "drift", "replan"))
    assert done["self_s"] == pytest.approx(done["total_s"] - inside,
                                           abs=1e-9)


def test_replanned_run_records_nothing_with_tracer_off():
    obs.reset()
    assert not obs.enabled()
    planner, _, _ = _replanned_run()
    assert planner.stats.reschedules >= 1
    assert obs.snapshot() == {"spans": {}, "counters": {}}


def _pass_with_one_stale_task(monkeypatch, rng):
    """One refresh pass over two tenants' two due tasks each, one of which
    is observed while the fit runs (refused by its seq check)."""
    import repro.store.compute as compute
    store = PosteriorStore()
    onlines = []
    for tenant in ("acme", "globex"):
        online = OnlinePredictor(_fit(("bwa", "idx")))
        store.bind(tenant, "w", online)
        _observe_local(online, "bwa", 4, rng)
        _observe_local(online, "idx", 4, rng, slope=12.0)
        onlines.append(online)
    real = compute.fit_stacked

    def fit_and_race(x, y, m, impl="auto"):
        _observe_local(onlines[1], "idx", 1, rng)
        return real(x, y, m, impl)
    monkeypatch.setattr(compute, "fit_stacked", fit_and_race)
    return FleetRefresher(store, RefreshPolicy(every_n=4)).refresh()


def test_refresh_counts_published_and_stale_rows(tracing, monkeypatch, rng):
    report = _pass_with_one_stale_task(monkeypatch, rng)
    assert (report.n_tasks, report.n_stale) == (3, 1)
    ct = obs.snapshot()["counters"]
    assert ct["lotaru.refresh.published_rows"] == 3
    assert ct["lotaru.refresh.stale_rows"] == 1


def test_refresh_counts_nothing_with_tracer_off(monkeypatch, rng):
    obs.reset()
    assert not obs.enabled()
    report = _pass_with_one_stale_task(monkeypatch, rng)
    assert (report.n_tasks, report.n_stale) == (3, 1)
    assert obs.snapshot() == {"spans": {}, "counters": {}}
