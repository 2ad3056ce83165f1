"""The replan cell at a small size on the CPU, and its HEFT reference.

A 20-node cluster, two workflows and a 2 s window through the harness:
the run is correct, the bfloat16 control fails `predict_rel_err`, a
schedule with two placements swapped fails `schedule_mismatch`, and a
planner that replans on every completion fails `replan_mismatch`.  The plain
HEFT of `bench/reference_heft.py` equals the program's scalar reference on
seeded random DAGs and clusters."""
import io

import jax
import numpy as np
import pytest

from bench import reference_heft as rh
from bench import run as harness
from bench.drivers import serve
from bench.tests import _small
from bench.tools import readings

CELL = "nfcore-cluster-100n.replan"


def small(c: dict) -> dict:
    cfg = c["cfg"]
    cfg.update(tenants=24, nodes=20, workflows=["bacass", "chipseq"],
               profile_seed=_small.SEED)
    cfg["limits"].update(min_checked_replans=8, min_checked_queries=100,
                         min_checked_drift_checks=8)
    c["traffic"]["check_sample"] = 8
    return c


def _profiled(monkeypatch):
    monkeypatch.setattr(serve, "profile",
                        lambda workflows, s, spans: _small.experiments(s))


def _run_small(monkeypatch, seconds: float = 2.0):
    load = harness.load_cell
    monkeypatch.setattr(harness, "load_cell", lambda n: small(load(n)))
    _profiled(monkeypatch)
    out, err = io.StringIO(), io.StringIO()
    res = harness.run_cell(CELL, _small.SEED, seconds, False,
                           chips_check=lambda n: jax.devices()[:n],
                           out=out, err=err)
    res["stdout"], res["stderr"] = out.getvalue(), err.getvalue()
    return res


def _checks(res) -> dict:
    return {c["name"]: c for c in res["checks"]}


def test_small_run_is_correct(monkeypatch):
    res = _run_small(monkeypatch)
    assert res["correct"], res["stderr"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert "compiles_in_window=0 " in res["stderr"]
    assert res["metrics"]["predict_qps"]["value"] > 0
    ch = _checks(res)
    assert ch["checked_replans"]["value"] >= 9       # 8 sampled + largest
    assert ch["checked_drift_checks"]["value"] == 8
    assert ch["schedule_mismatch"]["value"] == 0
    assert ch["replan_mismatch"]["value"] == 0


def test_control_fails_predict_rel_err(monkeypatch):
    _profiled(monkeypatch)
    rec, = readings.readings(CELL, [_small.SEED], 2.0, cpu=True, edit=small)
    lim = harness.load_cell(CELL)["cfg"]["limits"]["predict_rel_err"]
    assert rec["predict_rel_err"] <= lim
    assert rec["control.predict_rel_err"] > 3 * lim
    assert rec["schedule_mismatch"] == rec["control.schedule_mismatch"] == 0
    assert rec["replan_mismatch"] == rec["control.replan_mismatch"] == 0


def test_swapped_placements_are_not_correct(monkeypatch):
    """Two tasks of every replan on different nodes trade nodes (and
    places in the node orders): the schedule check must see it."""
    import repro.online.rescheduler as rs
    real = rs.fused_heft_schedule

    def swapped(dag, nodes, matrix, **kw):
        sched = real(dag, nodes, matrix, **kw)
        if kw.get("ready_at") is None:
            return sched                     # a run's first plan
        a = sched.assignment
        u = next(iter(a))
        v = next((w for w in a if a[w] != a[u]), None)
        if v is not None:
            nu, nv = a[u], a[v]
            a[u], a[v] = nv, nu
            sched.order[nu] = [v if w == u else w for w in sched.order[nu]]
            sched.order[nv] = [u if w == v else w for w in sched.order[nv]]
        return sched
    monkeypatch.setattr(rs, "fused_heft_schedule", swapped)
    res = _run_small(monkeypatch)
    assert not res["correct"]
    ch = _checks(res)
    assert ch["schedule_mismatch"]["value"] > 0
    assert ch["predict_rel_err"]["value"] <= ch["predict_rel_err"]["limit"]


def test_replanning_on_every_completion_is_not_correct(monkeypatch):
    """A planner that skips the band test and replans whenever the
    frontier is not empty serves right estimates and exact schedules;
    the replayed band test must see the replans nothing called for."""
    import repro.online.rescheduler as rs
    real = rs.OnlineReschedulingPlanner._drifted
    monkeypatch.setattr(rs.OnlineReschedulingPlanner, "_drifted",
                        lambda self, frontier: real(self, frontier) or True)
    res = _run_small(monkeypatch)
    assert not res["correct"]
    ch = _checks(res)
    assert ch["replan_mismatch"]["value"] > 0
    assert ch["schedule_mismatch"]["value"] == 0
    assert ch["predict_rel_err"]["value"] <= ch["predict_rel_err"]["limit"]


def _random_case(seed: int):
    from repro.core.microbench import NodeSpec
    from repro.workflow.dag import TaskInstance, WorkflowDAG
    rng = np.random.default_rng(seed)
    nodes = [NodeSpec(f"M{k % 3}-{k}", cpu=200 + 50 * k, mem=1000,
                      io_read=300, io_write=300, cores=8,
                      net_gbps=float(rng.choice([1.0, 10.0, 16.0])))
             for k in range(int(rng.integers(2, 7)))]
    dag = WorkflowDAG("rand")
    n = int(rng.integers(3, 25))
    for i in range(n):
        deps = [f"t{j:02d}" for j in range(i) if rng.random() < 0.25]
        dag.add(TaskInstance(f"t{i:02d}", "task", "rand", 1.0,
                             output_gb=float(rng.uniform(0.0, 3.0)),
                             deps=deps))
    # integer-valued costs make equal finish times, so ties are exercised
    W = rng.integers(1, 6, size=(n, len(nodes))).astype(np.float64)
    W += rng.uniform(0.0, 1.0, size=W.shape) * (rng.random() < 0.5)
    return dag, nodes, W, rng


@pytest.mark.parametrize("seed", range(10))
@pytest.mark.parametrize("constrained", [False, True])
def test_reference_heft_equals_program_reference(seed, constrained):
    from repro.sched.heft import heft_schedule_reference
    dag, nodes, W, rng = _random_case(seed)
    uids = list(dag.tasks)
    row = {u: i for i, u in enumerate(uids)}
    col = {n.name: j for j, n in enumerate(nodes)}
    ready = avail = None
    kw = {}
    if constrained:
        ready = rng.uniform(0.0, 4.0, size=W.shape)
        avail = rng.uniform(0.0, 3.0, size=len(nodes)) * (
            rng.random(len(nodes)) < 0.7)
        kw = {"ready_at": lambda u, n: float(ready[row[u], col[n.name]]),
              "node_available": {n.name: float(a)
                                 for n, a in zip(nodes, avail)}}
    want = heft_schedule_reference(
        dag, nodes, lambda u, n: float(W[row[u], col[n.name]]), **kw)
    got = rh.heft(uids, {u: dag.tasks[u].deps for u in uids},
                  {u: dag.tasks[u].output_gb for u in uids}, W,
                  [n.name for n in nodes], [n.net_gbps for n in nodes],
                  ready, avail)
    assert got["assignment"] == want.assignment
    assert got["order"] == want.order
    assert got["est"] == want.est
    assert rh.mismatches(want.assignment, want.order, want.est, got) == 0
