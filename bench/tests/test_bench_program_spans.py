"""The readings of the program's own spans and counters: each per-layer
reading on a synthetic tracer snapshot, nothing from a program without the
tracer, the gap naming against the trace reduction's, and a small run of
both cells on the CPU."""
import json
import os

import pytest

from bench import trace
from bench.tests import _small
from bench.tools import program_spans as ps

FIXTURE = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "fixtures")


def _span(count, total_s, self_s=None):
    return {"count": count, "total_s": total_s,
            "self_s": total_s if self_s is None else self_s}


SNAPSHOT = {
    "spans": {
        "lotaru.frontend.queue": _span(10, 0.025),
        "lotaru.frontend.flush": _span(8, 0.080, 0.016),
        "lotaru.store.gather": _span(8, 0.004),
        "lotaru.compute.predict": _span(8, 0.060, 0.020),
        "lotaru.refresh.due": _span(4, 0.200),
        "lotaru.refresh.pass": _span(2, 0.500, 0.001),
        "lotaru.refresh.prepare": _span(2, 0.100),
        "lotaru.refresh.fit": _span(2, 0.020),
        "lotaru.refresh.apply": _span(2, 0.300),
    },
    "counters": {
        "lotaru.compute.h2d_bytes": 8 * 13680,
        "lotaru.refresh.fit_cells": 4096 * 64 * 2,
        "lotaru.refresh.fit_points": 4096 * 16 * 2,
    },
}

EXPECTED = {
    "frontend_queue_ms.serial": 2.5,
    "frontend_host_ms.serial": 2.0,
    "store_gather_ms.serial": 0.5,
    "predict_call_ms.serial": 7.5,
    "h2d_bytes_per_dispatch.serial": 13680.0,
    "refresh_due_ms.refresh": 50.0,
    "refresh_prepare_ms.refresh": 50.0,
    "refresh_fit_ms.refresh": 10.0,
    "refresh_apply_ms.refresh": 150.0,
    "fit_pad_share.refresh": 75.0,
}


@pytest.mark.parametrize("metric", sorted(EXPECTED))
def test_reading_of_a_snapshot(metric):
    assert ps.readings(SNAPSHOT)[metric] == pytest.approx(EXPECTED[metric])
    assert ps.METRICS[metric][1] in ("ms", "bytes", "%")


@pytest.mark.parametrize("metric", sorted(EXPECTED))
def test_no_reading_without_the_tracer(metric):
    """A program without `repro.obs` records nothing: every reading is
    None, and none raises."""
    read, _ = ps.METRICS[metric]
    assert read({}) is None
    assert read({"spans": {}, "counters": {}}) is None


def test_coverage_needs_both_sides():
    class Spans:
        total = {"plan.round": 0.1, "refresh.pass": 0.6}
        count = {"plan.round": 10, "refresh.pass": 2}
    cov = ps.coverage(SNAPSHOT, Spans)
    assert cov["plan_coverage"] == pytest.approx((2.5 + 10.0) / 10.0)
    assert cov["refresh_coverage"] == pytest.approx(
        (50.0 + 50.0 + 10.0 + 150.0) / 300.0)
    assert ps.coverage({}, Spans) == {}


def test_idle_gaps_match_the_trace_reduction():
    """With no program span in the trace the sweep names every gap as the
    benchmark's reduction does."""
    with open(os.path.join(FIXTURE, "small.json")) as f:
        meta = json.load(f)
    path = os.path.join(FIXTURE, "small.xplane.pb")
    gaps = ps.idle_gaps(path, meta["t0_ns"], meta["t1_ns"])
    want = trace.reduce_file(path, meta["t0_ns"], meta["t1_ns"]).gaps
    assert set(gaps) == set(want)
    for k, v in want.items():
        assert gaps[k] == pytest.approx(v)


@pytest.mark.parametrize("cell, metrics, key", [
    # on the CPU the predictive runs on the host in float64: no bytes
    # are shipped to a device
    ("nfcore-serve-2048.plan-serial",
     [m for m in EXPECTED if m.endswith(".serial")
      and not m.startswith("h2d_")], "plan_coverage"),
    ("nfcore-serve-2048.refresh-fleet",
     [m for m in EXPECTED if m.endswith(".refresh")], "refresh_coverage"),
])
def test_small_run_on_the_cpu(monkeypatch, cell, metrics, key):
    monkeypatch.setattr(_small.serve, "profile",
                        lambda workflows, s, spans: _small.experiments(s))
    import io
    recs = ps.run(cell, [_small.SEED], 1.0, pairs=1, trace=False, cpu=True,
                  edit=_small.small, out=io.StringIO())
    assert [r["mode"] for r in recs] == ["off", "on"]
    assert all(r["correct"] for r in recs)
    off, on = recs
    assert "metrics" not in off
    assert set(on["metrics"]) == set(metrics)
    assert 0.5 < on["coverage"][key] < 1.5
    if key == "refresh_coverage":        # nested inside the benchmark span
        assert on["coverage"][key] <= 1.0 + 1e-6
        assert on["metrics"]["fit_pad_share.refresh"] < 100.0
