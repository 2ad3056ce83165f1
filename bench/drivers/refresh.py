"""Fleet evidence refresh: a closed loop of refresh passes over the store.

Each pass feeds `completions_per_task` local completions to every
regression task of the next tenants in a seed-drawn round-robin order,
until exactly `tasks_per_pass` tasks are due, ingesting each tenant's batch
through `observe_many`; then one `FleetRefresher.refresh()` re-fits all of
them in one batched dispatch and publishes them in one store generation.

Set-up draws `feed_passes` passes of completions from the seed; the window
feeds them in turn, round after round, so that it times only the program's
calls: `observe_many`, `due()` and `refresh()`.  The rate counts every task
refreshed and published over the whole window, ingest included.  Once the
window has closed, a seed-drawn sample of the tasks it refreshed is read
back from the store and compared with a plain evidence fit of everything
the run fed them.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Dict, List, Tuple

import numpy as np

from bench import reference as ref
from bench.common import check, rate
from bench.drivers.serve import Fleet, build_fleet

Key = Tuple[int, str]           # (tenant index, task)


@dataclass
class Feed:
    """One pass's completions: per fed task its tenant, name and the
    `completions_per_task` inputs and runtimes, and the program's
    per-tenant `TaskCompletion` batches."""
    keys: List[Key]
    x: np.ndarray               # (tasks, completions_per_task)
    y: np.ndarray
    batches: List[Tuple[object, list]] = field(default_factory=list)


@dataclass
class State:
    fleet: Fleet
    refresher: object
    cfg: dict
    traffic: dict
    seed: int
    spans: object
    regression: Dict[str, List[str]]      # workflow -> regression tasks
    order: np.ndarray                     # tenant visiting order
    rng: np.random.Generator
    cursor: int = 0
    feeds: List[Feed] = field(default_factory=list)
    warm: Feed = None                     # fed during set-up
    reports: List[tuple] = field(default_factory=list)  # per window pass
    published: Dict[Key, dict] = field(default_factory=dict)
    t0: float = 0.0
    t1: float = 0.0


def setup(cfg: dict, traffic: dict, seed: int, spans) -> State:
    from repro.online import FleetRefresher, RefreshPolicy
    from repro.store.compute import fit_stacked
    fleet = build_fleet(cfg, seed, spans)
    regression = {}
    for svc in fleet.services:
        if svc.workflow not in regression:
            regression[svc.workflow] = [
                t for t, st in svc.predictor.tasks.items()
                if st.nig is not None]
    rng = np.random.default_rng([seed % (2 ** 63), 5])
    s = State(fleet, FleetRefresher(fleet.store, RefreshPolicy(
        every_n=traffic["completions_per_task"])), cfg, traffic, seed, spans,
        regression, rng.permutation(len(fleet.services)), rng)
    t = traffic["tasks_per_pass"]
    with spans.span("setup.feed"):
        s.warm = _feed(s)
        s.feeds = [_feed(s) for _ in range(traffic["feed_passes"])]
    with spans.span("setup.warm_fit"):
        for n in traffic["warm_cols"]:
            fit_stacked(np.ones((t, n), np.float32),
                        np.ones((t, n), np.float32),
                        np.ones((t, n), np.float32))
    with spans.span("setup.warm_pass"):
        one_pass(s, s.warm)
    return s


def _feed(s: State) -> Feed:
    """The next pass's completions: runtimes follow the workflow's
    ground-truth work model with its lognormal noise, drawn from the
    seed."""
    from repro.online import TaskCompletion
    from repro.workflow.generator import BASE_SCALE, NOISE_SCALE
    k = s.traffic["completions_per_task"]
    want = s.traffic["tasks_per_pass"]
    keys: List[Key] = []
    while len(keys) < want:
        i = int(s.order[s.cursor % len(s.order)])
        s.cursor += 1
        w = s.fleet.services[i].workflow
        keys.extend((i, task) for task in s.regression[w][:want - len(keys)])
    base, per_gb, sigma, sizes = [], [], [], []
    for i, task in keys:
        w = s.fleet.services[i].workflow
        m = s.fleet.experiments[w].gt.models[task]
        base.append(m.base_s if m.merge else m.base_s * BASE_SCALE)
        per_gb.append(m.per_gb_s)
        sigma.append(m.noise * NOISE_SCALE * (6.0 if m.weak_corr else 1.0))
        sizes.append(s.fleet.inputs[w][task])
    pick = s.rng.random((len(keys), k))
    gb = np.stack([sz[(p * len(sz)).astype(int)] for sz, p in
                   zip(sizes, pick)]) * s.rng.uniform(0.5, 2.0, (len(keys), k))
    noise = s.rng.lognormal(0.0, 1.0, (len(keys), k)) ** np.asarray(
        sigma)[:, None]
    y = (np.asarray(base)[:, None] + np.asarray(per_gb)[:, None] * gb) * noise
    f = Feed(keys, gb, y)
    j = 0
    while j < len(keys):
        i = keys[j][0]
        svc = s.fleet.services[i]
        comps = []
        while j < len(keys) and keys[j][0] == i:
            task = keys[j][1]
            comps.extend(TaskCompletion(svc.workflow, f"{svc.tenant}/{task}",
                                        task, "local", float(a), float(b))
                         for a, b in zip(gb[j], y[j]))
            j += 1
        f.batches.append((svc.predictor, comps))
    return f


def one_pass(s: State, f: Feed):
    """Ingest one feed, then one refresh of every due task."""
    with s.spans.span("ingest.observe_many"):
        for predictor, comps in f.batches:
            predictor.observe_many(comps)
    with s.spans.span("refresh.pass"):
        rep = s.refresher.refresh(s.refresher.due())
    return rep


def window(s: State, seconds: float) -> None:
    s.t0 = time.perf_counter()
    n = 0
    while time.perf_counter() - s.t0 < seconds:
        rep = one_pass(s, s.feeds[n % len(s.feeds)])
        s.reports.append((rep.n_tasks, rep.n_stale, rep.n_dispatches))
        n += 1
    s.t1 = time.perf_counter()
    _capture(s)


def _fed(s: State) -> List[Feed]:
    """Every feed in the order the run ingested it."""
    return [s.warm] + [s.feeds[n % len(s.feeds)]
                       for n in range(len(s.reports))]


def _capture(s: State) -> None:
    """Read back, once the window has closed, the published posterior of a
    seed-drawn sample of the tasks the window refreshed."""
    keys = sorted({key for f in _fed(s)[1:] for key in f.keys})
    if not keys:
        return
    rng = np.random.default_rng([s.seed % (2 ** 63), 6])
    n = min(s.traffic["check_tasks"], len(keys))
    snap = s.fleet.store.snapshot()
    for j in sorted(rng.choice(len(keys), size=n, replace=False)):
        i, task = keys[j]
        s.published[keys[j]] = snap.get(
            s.fleet.services[i]._binding.key_str(task))


def end_to_end(s: State, seconds: float) -> dict:
    return {"refresh_tasks_per_s": rate(sum(r[0] for r in s.reports),
                                        s.t1 - s.t0)}


def counts(s: State) -> dict:
    want = s.traffic["tasks_per_pass"]
    return {"attempted": len(s.reports) * want,
            "failed": sum(want - r[0] for r in s.reports)}


def counters(s: State) -> dict:
    """Passes, tasks refreshed, and the valid points of every fit the
    window ran (profiling points plus the ring of completions fed)."""
    seen: Dict[Key, int] = {key: s.warm.x.shape[1] for key in s.warm.keys}
    points = []
    for f in _fed(s)[1:]:
        for key in f.keys:
            seen[key] = seen.get(key, 0) + f.x.shape[1]
            w = s.fleet.services[key[0]].workflow
            points.append(len(s.fleet.models[w].tasks[key[1]].fit_x)
                          + min(seen[key], ref.RING))
    return {"passes": len(s.reports), "fit_points": points,
            "refreshed": sum(r[0] for r in s.reports)}


def release(s: State) -> None:
    s.fleet.store = None
    s.refresher = None
    s.feeds = [Feed(f.keys, f.x, f.y) for f in s.feeds]
    s.warm = Feed(s.warm.keys, s.warm.x, s.warm.y)
    s.fleet.services = [_Tenant(x.workflow) for x in s.fleet.services]


@dataclass
class _Tenant:
    workflow: str


def verify(s: State, cfg: dict, control: bool = False) -> List[dict]:
    """Each sampled task's published posterior against the plain evidence
    fit of the same data: its fit-time profiling points and the newest
    `RING` completions the run fed it.  Compared as predictive mean and
    std at the fitted points and at the workflow's production input
    sizes."""
    fed: Dict[Key, Tuple[list, list]] = {key: ([], []) for key in s.published}
    for f in _fed(s):
        for j, key in enumerate(f.keys):
            if key in fed:
                fed[key][0].extend(f.x[j].tolist())
                fed[key][1].extend(f.y[j].tolist())
    worst = 0.0
    for key, row in s.published.items():
        w = s.fleet.services[key[0]].workflow
        st = s.fleet.models[w].tasks[key[1]]
        xs, ys = fed[key]
        x = np.asarray(st.fit_x + xs[-ref.RING:])
        y = np.asarray(st.fit_y + ys[-ref.RING:])
        at = np.concatenate([x, s.fleet.inputs[w][key[1]]])
        want = ref.fit_evidence(x[None], y[None], np.ones((1, len(x))))
        mean, std = ref.predictive({k: v[0] for k, v in want.items()}, at)
        if control:
            got = ref.fit_evidence(x[None], y[None], np.ones((1, len(x))),
                                   ref.bfloat16())
            gm, gs = ref.predictive({k: v[0] for k, v in got.items()}, at)
        elif row is None:
            gm = gs = np.full(len(at), np.nan)
        else:
            post = dict(row)
            post["beta"] = post.pop("beta_prec")
            gm, gs = ref.predictive(post, at)
        floor = float(want["y_sd"][0])
        worst = max(worst, ref.rel_err(gm, mean, floor),
                    ref.rel_err(gs, std))
    lim = cfg["limits"]
    want_tasks = s.traffic["tasks_per_pass"]
    return [check("fit_rel_err", worst, lim["fit_rel_err"]),
            check("checked_tasks", len(s.published),
                  lim["min_checked_tasks"], ">="),
            check("stale_or_missing", sum(want_tasks - n + stale
                                          for n, stale, _ in s.reports), 0),
            check("dispatches_per_pass", max(
                (d for *_, d in s.reports), default=0), 1)]
