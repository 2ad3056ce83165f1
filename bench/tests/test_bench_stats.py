"""Rates are taken over the whole window and every request counts."""
import math
from types import SimpleNamespace

import numpy as np
import pytest

from bench import common
from bench.drivers import plan, refresh


def test_rate_is_work_over_whole_window():
    assert common.rate(500, 2.0) == 250.0
    with pytest.raises(ValueError):
        common.rate(1, 0.0)


def _req(i, submit, done, ok=True, k=16):
    r = plan.Req(idx=i, tenant=0, k=k)
    r.submit, r.done = submit, done
    r.result = np.zeros((k * len(plan.NODES), 3)) if ok else None
    return r


def _run(reqs, t0=0.0, t1=2.0):
    run = plan.Run(None, None, {}, 0, None)
    run.t0, run.t1 = t0, t1
    run.reqs = reqs
    return run


def test_qps_counts_answers_inside_the_window_over_all_of_it():
    run = _run([_req(0, 0.0, 0.5), _req(1, 0.5, 1.9),
                _req(2, 1.9, 2.1), _req(3, 0.0, 1.0, ok=False)])
    qps = plan.end_to_end(run, 2.0)["predict_qps"]
    assert qps == pytest.approx(2 * 16 * len(plan.NODES) / 2.0)


def test_qps_weighs_each_round_by_its_queries():
    run = _run([_req(0, 0.0, 0.1, k=1), _req(1, 0.1, 1.0, k=100)])
    qps = plan.end_to_end(run, 2.0)["predict_qps"]
    assert qps == pytest.approx(101 * len(plan.NODES) / 2.0)


def test_observes_are_not_counted_as_attempts():
    obs = plan.Req(idx=9, tenant=0, obs=("t", 1.0, 2.0))
    run = _run([_req(0, 0.0, 0.5), _req(1, 0.5, math.nan, ok=False), obs])
    assert plan.counts(run) == {"attempted": 2, "failed": 1}


def test_refresh_rate_counts_every_task_over_the_whole_window():
    s = SimpleNamespace(reports=[(4096, 0, 1)] * 3 + [(4000, 96, 1)],
                        t0=10.0, t1=12.0, traffic={"tasks_per_pass": 4096})
    assert refresh.end_to_end(s, 2.0)["refresh_tasks_per_s"] == \
        pytest.approx((3 * 4096 + 4000) / 2.0)
    assert refresh.counts(s) == {"attempted": 4 * 4096, "failed": 96}


def test_fit_points_count_profiling_points_and_the_capped_ring():
    feed = refresh.Feed([(0, "a"), (1, "a")], np.ones((2, 4)), np.ones((2, 4)))
    other = refresh.Feed([(0, "a")], np.ones((1, 200)), np.ones((1, 200)))
    model = SimpleNamespace(tasks={"a": SimpleNamespace(fit_x=[1.0] * 5)})
    s = SimpleNamespace(
        warm=feed, feeds=[other, feed], reports=[(1, 0, 1)] * 3,
        fleet=SimpleNamespace(models={"w": model},
                              services=[SimpleNamespace(workflow="w")] * 2))
    c = refresh.counters(s)
    # tenant 0 holds 4, then 204, 208, 408 -> capped at 256 in the ring
    assert c["fit_points"] == [5 + 204, 5 + 208, 5 + 8, 5 + 256]
    assert c["passes"] == 3 and c["refreshed"] == 3
