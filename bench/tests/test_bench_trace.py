"""The trace reduction, on a small trace recorded on a TPU v5e by
`bench/tools/record_fixture.py`: three 96-query predict dispatches and one
256 x 64 fit, each inside a benchmark span, with 10 ms host sleeps
between them."""
import json
import os

import pytest

from bench import trace
from bench.layers import _shared

HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURE = os.path.join(os.path.dirname(HERE), "fixtures")


@pytest.fixture(scope="module")
def reduced():
    with open(os.path.join(FIXTURE, "small.json")) as f:
        meta = json.load(f)
    return meta, trace.reduce_file(os.path.join(FIXTURE, "small.xplane.pb"),
                                   meta["t0_ns"], meta["t1_ns"])


def test_busy_and_idle(reduced):
    meta, r = reduced
    assert r.n_chips == 1
    assert 0.0 < r.busy_s < r.window_s
    assert r.busy_s == pytest.approx(meta["busy_s"])
    # three 10 ms host sleeps: the device idles most of the window
    assert r.idle_share > 0.5


def test_kernels_found_by_name(reduced):
    _, r = reduced
    secs, n = r.kernel_seconds(_shared.is_predict_kernel)
    assert n == 3 and secs > 0
    secs, n = r.kernel_seconds(_shared.is_fit_kernel)
    assert n == 1 and secs > 0


def test_idle_gaps_named_by_the_host_span(reduced):
    _, r = reduced
    assert set(r.gaps) == {"bench.predict", "bench.host_wait", "bench.fit"}
    # the three 10 ms sleeps, less the device work that overlaps them
    assert 0.025 < r.gaps["bench.host_wait"] < 0.031
    assert sum(r.gaps.values()) == pytest.approx(r.window_s - r.busy_s)


def test_breakdown_is_bounded(reduced):
    _, r = reduced
    b = r.breakdown()
    assert 0 < len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert all(v > 0 for _, v in b["device_ops"])


def test_union_and_gaps():
    assert trace._union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    assert list(trace._gaps([(1, 2), (4, 5)], 0, 6)) == [(0, 1), (2, 4),
                                                         (5, 6)]
    assert trace.module_name("jit__pad(8170685879760021664)") == "jit__pad"
