"""The in-program tracer: off by default, self times of nested spans,
threads recording at once, and the spans and counters of one front-end
dispatch and one fleet refresh pass."""
import sys
import threading
import time

import numpy as np
import pytest

from repro import obs
from repro.online import (FleetRefresher, OnlinePredictor, PredictionService,
                          RefreshPolicy)
from repro.store import AsyncPredictionFrontend, PosteriorStore

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
