"""Planning rounds against the multi-tenant prediction service.

Each request is one tenant's planning round: the last k unstarted task
instances of its workflow's DAG, k uniform in 1..|DAG|, on the local
machine and the five Table 2 targets, every task at its input size times
U(0.5, 2).  A share of the requests are local completions of one task
instead (observe).  Tenants are drawn by Zipf popularity, and the rank
order is re-drawn every `reshuffle_requests` requests.  One client sends
the rounds back to back through the front end, each after the answer to
the one before, as a scheduler's planning loop does.
"""
from __future__ import annotations

import math
import time
from dataclasses import dataclass, field
from typing import List, Optional

import numpy as np

from bench import reference as ref
from bench.common import check, rate
from bench.drivers.serve import Fleet, build_fleet, warm_predict

NODES = ("local", "A1", "A2", "N1", "N2", "C2")


@dataclass
class Req:
    idx: int
    tenant: int
    k: int = 0                       # tasks in a planning round
    xf: Optional[np.ndarray] = None  # per-task input factors
    obs: Optional[tuple] = None      # (task, input_gb, runtime_s)
    submit: float = math.nan
    done: float = math.nan
    result: Optional[np.ndarray] = None
    error: Optional[str] = None

    @property
    def n_queries(self) -> int:
        return self.k * len(NODES)


class Mix:
    """Request generator: one stream of requests per seed, the same sizes
    and shares in distribution for every seed."""

    def __init__(self, fleet: Fleet, traffic: dict, seed: int):
        self.fleet = fleet
        self.t = traffic
        self.rng = np.random.default_rng([seed % (2 ** 63), 0])
        n = len(fleet.services)
        w = 1.0 / np.arange(1, n + 1) ** traffic["zipf_s"]
        self.cdf = np.cumsum(w / w.sum())
        self._epoch = -1
        self._perm = None
        self._seed = seed

    def _tenant(self, epoch: int) -> int:
        if epoch != self._epoch:
            perm_rng = np.random.default_rng([self._seed % (2 ** 63), 7919,
                                              epoch])
            self._perm = perm_rng.permutation(len(self.cdf))
            self._epoch = epoch
        rank = int(np.searchsorted(self.cdf, self.rng.random(), "right"))
        return int(self._perm[min(rank, len(self.cdf) - 1)])

    def make(self, idx: int) -> Req:
        i = self._tenant(idx // self.t["reshuffle_requests"])
        svc = self.fleet.services[i]
        dag = self.fleet.dags[svc.workflow]
        r = Req(idx=idx, tenant=i)
        if self.rng.random() < self.t["observe_share"]:
            from repro.sched.cluster import LOCAL
            e = self.fleet.experiments[svc.workflow]
            uids = list(dag.tasks)
            inst = dag.tasks[uids[int(self.rng.integers(len(uids)))]]
            gb = inst.input_gb * float(self.rng.uniform(0.5, 2.0))
            y = e.gt.runtime(inst.task_name, gb, LOCAL,
                             f"{svc.tenant}/{idx}")
            r.obs = (inst.task_name, gb, y)
            return r
        r.k = int(self.rng.integers(1, len(dag.tasks) + 1))
        r.xf = self.rng.uniform(0.5, 2.0, size=r.k)
        return r


def queries(fleet: Fleet, r: Req) -> list:
    """The planning round's (task, node, input_gb) triples, in order."""
    svc = fleet.services[r.tenant]
    dag = fleet.dags[svc.workflow]
    order = fleet.topo[svc.workflow][-r.k:]
    out = []
    for u, f in zip(order, r.xf):
        t = dag.tasks[u]
        x = t.input_gb * float(f)
        out.extend((t.task_name, n, x) for n in NODES)
    return out


@dataclass
class Run:
    fleet: Fleet
    frontend: object
    traffic: dict
    seed: int
    spans: object
    reqs: List[Req] = field(default_factory=list)
    obs_log: List[tuple] = field(default_factory=list)  # (tenant, t_ack, obs)
    t0: float = 0.0
    t1: float = 0.0
    dispatches: int = 0


def setup(cfg: dict, traffic: dict, seed: int, spans) -> Run:
    from repro.store import AsyncPredictionFrontend
    fleet = build_fleet(cfg, seed, spans)
    biggest = max(len(d.tasks) for d in fleet.dags.values())
    warm_predict(fleet, [k * len(NODES) for k in range(1, biggest + 1)],
                 spans)
    fe = AsyncPredictionFrontend(fleet.store,
                                 window_s=cfg["frontend_window_s"])
    run = Run(fleet, fe, traffic, seed, spans)
    # one round per workflow through the whole front end
    with spans.span("setup.warm_frontend"):
        for w in cfg["workflows"]:
            i = next(j for j, s in enumerate(fleet.services)
                     if s.workflow == w)
            _submit(run, Req(idx=-1, tenant=i, k=1,
                             xf=np.ones(1))).result(timeout=120)
    return run


def _submit(run: Run, r: Req):
    svc = run.fleet.services[r.tenant]
    from repro.online import PredictionQuery
    with run.spans.span("plan.client"):
        qs = [PredictionQuery(t, n, x) for t, n, x in queries(run.fleet, r)]
    r.submit = time.perf_counter()
    return run.frontend.predict_async(qs, svc.tenant, svc.workflow)


def _answer(r: Req, fut) -> bool:
    """Wait for one round's answer, a minute at most; False if none
    came."""
    try:
        exc = fut.exception(timeout=60)
    except TimeoutError:
        r.error = "no answer within a minute"
        return False
    r.done = time.perf_counter()
    if exc is not None:
        r.error = repr(exc)
    else:
        r.result = fut.result()
    return True


def _observe(run: Run, r: Req) -> None:
    from repro.online import TaskCompletion
    svc = run.fleet.services[r.tenant]
    task, gb, y = r.obs
    comp = TaskCompletion(svc.workflow, f"{svc.tenant}/{r.idx}", task,
                          "local", gb, y)
    with run.spans.span("ingest.observe"):
        svc.predictor.observe(comp)
    run.obs_log.append((r.tenant, time.perf_counter(), r.obs))


def window(run: Run, seconds: float) -> None:
    """Rounds back to back for `seconds`; the round in flight at the close
    is answered, and counts for the check but not for the rate."""
    run.dispatches = run.frontend.dispatch_count
    mix = Mix(run.fleet, run.traffic, run.seed)
    run.t0 = time.perf_counter()
    i = 0
    while time.perf_counter() - run.t0 < seconds:
        r = mix.make(i)
        i += 1
        run.reqs.append(r)
        if r.obs is not None:
            _observe(run, r)
            continue
        fut = _submit(run, r)
        with run.spans.span("plan.round"):
            answered = _answer(r, fut)
        if not answered:
            break
    run.t1 = run.t0 + seconds
    run.dispatches = run.frontend.dispatch_count - run.dispatches


def _predicts(run: Run) -> List[Req]:
    return [r for r in run.reqs if r.obs is None]


def _answered_in_window(run: Run) -> List[Req]:
    return [r for r in _predicts(run)
            if r.result is not None and r.done <= run.t1]


def end_to_end(run: Run, seconds: float) -> dict:
    answered = sum(r.n_queries for r in _answered_in_window(run))
    return {"predict_qps": rate(answered, run.t1 - run.t0)}


def counts(run: Run) -> dict:
    preds = _predicts(run)
    return {"attempted": len(preds),
            "failed": sum(r.result is None for r in preds)}


def counters(run: Run) -> dict:
    """What the per-layer readers read: program counters and the
    benchmark's own records."""
    answered = _answered_in_window(run)
    return {"dispatches": run.dispatches,
            "answered_requests": len(answered),
            "answered_queries": sum(r.n_queries for r in answered)}


# ---------------------------------------------------------------------------
# correctness
# ---------------------------------------------------------------------------

def _sample(run: Run) -> List[Req]:
    """Answered requests drawn from the seed, with the largest round in
    it."""
    ok = [r for r in _predicts(run) if r.result is not None]
    if not ok:
        return []
    rng = np.random.default_rng([run.seed % (2 ** 63), 31337])
    n = min(run.traffic["check_sample"], len(ok))
    pick = set(rng.choice(len(ok), size=n, replace=False).tolist())
    pick.add(max(range(len(ok)), key=lambda j: ok[j].n_queries))
    return sorted((ok[j] for j in pick), key=lambda r: r.submit)


def reference_answers(run: Run, sample: List[Req], dt=np.float64):
    """Per sampled request: (mean, std, floor) from the plain reference,
    replaying the tenant's observes acknowledged before it was sent."""
    obs = {}
    for ten, ack, o in run.obs_log:
        obs.setdefault(ten, []).append((ack, o))
    models, pos, out = {}, {}, []
    for r in sample:
        svc = run.fleet.services[r.tenant]
        m = models.get(r.tenant)
        if m is None:
            m = models[r.tenant] = run.fleet.models[svc.workflow].copy()
            pos[r.tenant] = 0
        mine = obs.get(r.tenant, [])
        while pos[r.tenant] < len(mine) and mine[pos[r.tenant]][0] < r.submit:
            task, gb, y = mine[pos[r.tenant]][1]
            m.observe(task, "local", gb, y)
            pos[r.tenant] += 1
        out.append(m.predict(queries(run.fleet, r), dt))
    return out


def compare(answers, want) -> float:
    """Largest relative error of served means and stds, z = 1.96 bands."""
    worst = 0.0
    for got, (mean, std, floor) in zip(answers, want):
        if got is None or np.shape(got) != (len(mean), 3):
            return math.inf
        got_std = (got[:, 2] - got[:, 0]) / 1.96
        worst = max(worst, ref.rel_err(got[:, 0], mean, floor),
                    ref.rel_err(got_std, std))
    return worst


def verify(run: Run, cfg: dict, control: bool = False) -> List[dict]:
    sample = _sample(run)
    want = reference_answers(run, sample)
    if control:
        answers = [np.stack([m, np.maximum(m - 1.96 * s, 0.0), m + 1.96 * s],
                            1)
                   for m, s, _ in reference_answers(run, sample,
                                                    ref.bfloat16())]
    else:
        answers = [r.result for r in sample]
    preds = _predicts(run)
    lim = cfg["limits"]
    return [check("predict_rel_err", compare(answers, want),
                  lim["predict_rel_err"]),
            check("checked_queries", sum(r.n_queries for r in sample),
                  lim["min_checked_queries"], ">="),
            check("unanswered", sum(r.result is None and r.error is None
                                    for r in preds), 0),
            check("errors", sum(r.error is not None for r in preds), 0)]


def release(run: Run) -> None:
    """Free the program's state before the reference runs: the store and
    the predictors go; what the check needs (answers, the workflow of each
    tenant, the reference models) stays."""
    run.frontend.close()
    run.fleet.store = None
    run.frontend = None
    run.fleet.services = [_Tenant(s.tenant, s.workflow)
                          for s in run.fleet.services]
    run.fleet.experiments = None


@dataclass
class _Tenant:
    tenant: str
    workflow: str
