"""Replanning after every completion on a heterogeneous cluster.

A closed loop of workflow runs, back to back, as a workflow engine's event
loop runs them.  Each run executes one nf-core workflow's DAG
(`build_workflow(w, seed_i)`, 17-145 tasks) with `execute_adaptive` on the
configuration's cluster, with true runtimes drifted by machine type, under
an `OnlineReschedulingPlanner` bound to a namespace of its own in the
shared store (which also holds the serving fleet's tenants).  After every
completion the planner observes it, checks the unstarted frontier for
drift and, on drift, replans the frontier.  A finished run's namespace is
evicted.  The workflow of each run is uniform over the five: every block of
five runs is a seed-drawn order of the five workflows.

The deployment's fitted state is the same for every seed: the workflows
are profiled, and the store's tenants built, from the configuration's
`profile_seed`.  The run's seed draws the cluster, the workflow runs,
their inputs and the noise of their runtimes.

The window times the whole loop: run set-up (DAG, predictor, planner,
binding), the simulator's event loop and the planner's calls.  A run in
flight at the close stops at its next completion; completions handled
before the close count.  `predict_qps` counts the runtime estimates the
planner consumed, by the trajectory and not by the implementation: for
every completion, |frontier| drift-check queries, and on a replan
|frontier| x nodes matrix cells plus |running| queries; for each run's
first plan, tasks x nodes.

Each run's planner is observed through its public surface: this module
wraps its `initial_schedule` and `on_completion`, its service's
`predict_batch` and its plane's `matrix` to count and keep what was served
(references only).  Once the window has closed:

- every completion's decision to replan or not is replayed: the band test
  (z, cooldown) on the program's own drift-check answers against the bands
  taken from the matrix it served at its last plan, exactly;
- a seed-drawn sample of the replans, plus the largest, and a seed-drawn
  sample of the completions that did not replan have their served
  estimates compared with the plain float64 reference replaying that
  run's completions;
- each sampled replan's schedule is compared with plain HEFT
  (`bench/reference_heft.py`) on the served cost matrix under the
  execution state's constraints.
"""
from __future__ import annotations

import gc
import math
import statistics
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import numpy as np

from bench import reference as ref
from bench import reference_heft as rh
from bench.common import check, rate
from bench.drivers.plan import compare
from bench.drivers.serve import Fleet, build_fleet


def machine(node: str) -> str:
    """Node instance name -> its machine type ('A1-3' -> 'A1')."""
    return node.rsplit("-", 1)[0]


class _Closed(Exception):
    """The window closed before this completion was handled."""


@dataclass
class Plan:
    """One replan: what the planner was given and what it served."""
    run: int
    step: int                   # completions of the run handled, this one too
    state: object               # the SimState handed to the planner
    drift: tuple                # (queries, answers) of the drift check
    running: tuple              # (queries, answers) for the running tasks
    matrix: object              # the plane's served PredictionMatrix
    sched: object               # the Schedule returned
    cells: int                  # |frontier| x nodes


@dataclass(slots=True)
class Check:
    """One completion's drift check as the planner ran it.  Every
    completion keeps one, so it holds no per-query objects: the window's
    garbage collections would pay for them.  Its queries are kept only
    while it is in the sample."""
    run: int
    step: int                   # completions of the run handled, this one too
    started: set                # the tasks booked when it was handled
    answers: Optional[object]   # (F, 3); None on an empty frontier
    plan: Optional[tuple] = None    # on a replan: (served matrix, assignment)
    queries: Optional[list] = None  # while sampled


@dataclass
class Run:
    index: int
    workflow: str
    dag: object = None          # built at the run's start unless given
    records: List[tuple] = field(default_factory=list)
    # (task, node, input_gb, runtime_s, attempt) per completion handled
    first: Optional[tuple] = None       # (served matrix, assignment)
    checks: List[Check] = field(default_factory=list)
    error: Optional[str] = None


@dataclass
class State:
    fleet: Fleet
    cfg: dict
    traffic: dict
    seed: int
    spans: object
    nodes: list
    benches: Dict[str, dict]            # workflow -> node -> bench
    rng: np.random.Generator            # the check's sample
    runs: List[Run] = field(default_factory=list)
    plans: List[Plan] = field(default_factory=list)  # reservoir sample
    largest: Optional[Plan] = None
    n_replans: int = 0
    drifts: List[Check] = field(default_factory=list)  # reservoir sample
    n_drifts: int = 0                   # completions checked, no replan
    handled: int = 0                    # completions handled in the window
    firsts: int = 0                     # first plans made in the window
    queries: int = 0                    # estimates consumed in the window
    program: Optional[dict] = None      # repro.obs snapshot, traced runs
    t0: float = 0.0
    t1: float = 0.0


def workflow_of(seed: int, i: int, workflows) -> str:
    block = np.random.default_rng([seed % (2 ** 63), 23, i // len(workflows)])
    return workflows[int(block.permutation(len(workflows))[i % len(
        workflows)])]


def run_seed(seed: int, i: int) -> int:
    return int(np.random.default_rng([seed % (2 ** 63), 17, i]).integers(
        2 ** 31))


def setup(cfg: dict, traffic: dict, seed: int, spans) -> State:
    from repro.online import OnlineReschedulingPlanner
    from repro.sched.cluster import PAPER_MACHINES
    from repro.workflow.simulator import random_cluster
    if not isinstance(getattr(OnlineReschedulingPlanner, "plane", None),
                      property):
        raise RuntimeError("the program has no OnlineReschedulingPlanner."
                           "plane: this cell reads what the plane served")
    fleet = build_fleet(cfg, cfg["profile_seed"], spans)
    pool = [PAPER_MACHINES[m] for m in cfg["targets"]]
    nodes = random_cluster(np.random.default_rng(seed % (2 ** 63)), pool,
                           cfg["nodes"])
    benches = {}
    for w, e in fleet.experiments.items():
        b = dict(e.benches)
        b.update({n.name: e.benches[machine(n.name)] for n in nodes})
        benches[w] = b
    s = State(fleet, cfg, traffic, seed, spans, nodes, benches,
              np.random.default_rng([seed % (2 ** 63), 31337]))
    with spans.span("setup.warm_runs"):
        for k, w in enumerate(cfg["workflows"]):
            _run(s, Run(-1 - k, w, fleet.dags[w]), math.inf, record=False)
    with spans.span("setup.warm_shapes"):
        _warm_shapes(s)
    gc.collect()        # every window starts from the same collector state
    return s


def _warm_shapes(s: State) -> None:
    """Compile what a window can meet that the warm runs may not have:
    a jitted sweep is shaped by its frontier's task bucket and its run's
    dependency width (the whole DAG's fan-in), so plan one sub-DAG per
    task bucket of every workflow's DAG (its last k tasks in topological
    order, k the bucket's largest size) on the cluster at that width; and
    refit a few points, as a median task's promotion does."""
    from repro.core.bayes import refresh_fit
    from repro.sched.fused import _TASK_BUCKET, fused_heft_schedule
    from repro.sched.plane import PredictionMatrix
    from repro.workflow.dag import TaskInstance, WorkflowDAG
    names = [n.name for n in s.nodes]
    for w in s.cfg["workflows"]:
        dag = s.fleet.dags[w]
        order = dag.topo_order()
        width = max(len(t.deps) for t in dag.tasks.values())
        for k in sorted({min(e, len(order)) for e in range(
                _TASK_BUCKET, len(order) + _TASK_BUCKET, _TASK_BUCKET)}):
            keep = set(order[-k:])
            sub = WorkflowDAG(dag.name)
            for u in order[-k:]:
                t = dag.tasks[u]
                sub.add(TaskInstance(u, t.task_name, t.workflow, t.input_gb,
                                     t.output_gb, t.sample,
                                     [d for d in t.deps if d in keep]))
            mat = PredictionMatrix(order[-k:], names,
                                   np.ones((k, len(names))),
                                   np.ones((k, len(names))))
            fused_heft_schedule(sub, s.nodes, mat,
                                node_available={n: 1.0 for n in names},
                                quantile=s.cfg["quantile"], dep_width=width)
    refresh_fit([], [], [1.0, 2.0, 3.0, 4.0], [2.0, 4.1, 5.9, 8.0])


def _run(s: State, run: Run, close: float, record: bool = True) -> None:
    """Execute one workflow run under its planner, until its end or the
    first completion after `close`."""
    from repro.online import OnlinePredictor, OnlineReschedulingPlanner
    from repro.workflow.generator import build_workflow
    from repro.workflow.simulator import execute_adaptive
    cfg, n_nodes = s.cfg, len(s.nodes)
    e = s.fleet.experiments[run.workflow]
    ns = f"{run.workflow}-run{run.index}"
    with s.spans.span("plan.run_setup"):
        if run.dag is None:
            run.dag = build_workflow(run.workflow, run_seed(s.seed,
                                                            run.index))
        online = OnlinePredictor(e.predictors["lotaru-g"], benches=e.benches)
        planner = OnlineReschedulingPlanner(
            run.dag, s.nodes, online, s.benches[run.workflow], z=cfg["z"],
            cooldown=cfg["cooldown"], store=s.fleet.store,
            tenant=cfg["tenant"], workflow=ns, quantile=cfg["quantile"])
    calls, served = [], []
    svc, plane = planner.service, planner.plane
    real_predict, real_matrix = svc.predict_batch, plane.matrix
    real_first, real_done = planner.initial_schedule, planner.on_completion

    def predict_batch(queries):
        out = real_predict(queries)
        calls.append((queries, out))
        return out

    def matrix():
        m = real_matrix()
        served.append(m)
        return m

    def initial_schedule():
        sched = real_first()
        if record:
            run.first = (served[-1], sched.assignment)
            if time.perf_counter() <= close:
                s.firsts += 1
                s.queries += len(run.dag.tasks) * n_nodes
        return sched

    def on_completion(rec, state):
        if time.perf_counter() > close:
            raise _Closed
        calls.clear()
        served.clear()
        with s.spans.span("plan.completion"):
            out = real_done(rec, state)
        done = time.perf_counter()
        if not record:
            return out
        t = run.dag.tasks[rec.uid]
        run.records.append((t.task_name, rec.node, t.input_gb,
                            rec.finish - rec.start, rec.attempt))
        frontier = len(run.dag.tasks) - len(state.started)
        q = frontier
        step = len(run.records)
        check = Check(run.index, step, state.started,
                      calls[0][1] if calls else None)
        run.checks.append(check)
        if out is not None:
            q += frontier * n_nodes + len(state.running)
            check.plan = (served[-1], out.assignment)
            _keep(s, Plan(run.index, step, state, calls[0], calls[1],
                          served[-1], out, frontier * n_nodes))
        elif calls:
            _keep_drift(s, check, calls[0][0])
        if done <= close:
            s.handled += 1
            s.queries += q
        return out

    svc.predict_batch = predict_batch
    plane.matrix = matrix
    planner.initial_schedule = initial_schedule
    planner.on_completion = on_completion

    def truth(u, node):
        t = run.dag.tasks[u]
        return (e.gt.runtime(t.task_name, t.input_gb, node,
                             f"{s.seed}/{ns}/{u}")
                * cfg["drift"][machine(node.name)])

    try:
        with s.spans.span("plan.engine"):
            execute_adaptive(run.dag, s.nodes, planner, truth)
    except _Closed:
        pass
    except Exception as exc:          # a fault of the program: counted
        run.error = repr(exc)
        if not record:
            raise
    finally:
        s.fleet.store.evict(cfg["tenant"], ns)


def _keep(s: State, p: Plan) -> None:
    """Reservoir sample of `check_sample` replans, plus the largest."""
    k = s.traffic["check_sample"]
    if s.largest is None or p.cells > s.largest.cells:
        s.largest = p
    if s.n_replans < k:
        s.plans.append(p)
    else:
        j = int(s.rng.integers(s.n_replans + 1))
        if j < k:
            s.plans[j] = p
    s.n_replans += 1


def _keep_drift(s: State, c: Check, queries: list) -> None:
    """Reservoir sample of `check_sample` completions that did not
    replan; a sampled one keeps its queries."""
    k = s.traffic["check_sample"]
    j = s.n_drifts if s.n_drifts < k else int(s.rng.integers(s.n_drifts + 1))
    s.n_drifts += 1
    if j >= k:
        return
    c.queries = queries
    if j < len(s.drifts):
        s.drifts[j].queries = None
        s.drifts[j] = c
    else:
        s.drifts.append(c)


def window(s: State, seconds: float) -> None:
    """Workflow runs back to back for `seconds`.  In a traced run the
    program's own spans and counters are on (annotated) for the window."""
    from repro import obs
    traced = s.spans.annotate
    if traced:
        obs.reset()
        obs.enable(annotate=True)
    s.t0 = time.perf_counter()
    close = s.t0 + seconds
    try:
        i = 0
        while time.perf_counter() < close:
            run = Run(i, workflow_of(s.seed, i, s.cfg["workflows"]))
            s.runs.append(run)
            _run(s, run, close)
            i += 1
    finally:
        if traced:
            s.program = obs.snapshot()
            obs.disable()
    s.t1 = s.t0 + seconds


def end_to_end(s: State, seconds: float) -> dict:
    return {"predict_qps": rate(s.queries, s.t1 - s.t0)}


def counts(s: State) -> dict:
    """Planning operations (completions handled, first plans) in the
    window; a run whose planner raised counts one failed operation."""
    failed = sum(r.error is not None for r in s.runs)
    return {"attempted": s.handled + s.firsts + failed, "failed": failed}


def counters(s: State) -> dict:
    """What the per-layer readers read: the program's spans and counters
    (traced runs) and the benchmark's own tallies."""
    return {"program": s.program or {}, "completions": s.handled,
            "replans": s.n_replans, "answered_queries": s.queries}


# ---------------------------------------------------------------------------
# correctness
# ---------------------------------------------------------------------------

def _sample(s: State) -> List[Plan]:
    out = list(s.plans)
    if s.largest is not None and all(p is not s.largest for p in out):
        out.append(s.largest)
    return sorted(out, key=lambda p: (p.run, p.step))


def _frontier(dag, started) -> List[str]:
    """The unstarted tasks in the planner's order (the DAG's own)."""
    return [u for u in dag.tasks if u not in started]


def _ref_matrix(model, dag, frontier, names, dt):
    """(mean, std, floor), each (T, N), from the reference: one query per
    task and machine type, spread over that type's nodes."""
    kinds = sorted({machine(n) for n in names})
    qs = [(dag.tasks[u].task_name, k, dag.tasks[u].input_gb)
          for u in frontier for k in kinds]
    cols = [kinds.index(machine(n)) for n in names]
    return tuple(np.asarray(v).reshape(len(frontier), len(kinds))[:, cols]
                 for v in model.predict(qs, dt))


def _served(p: Plan, frontier):
    rows = [p.matrix.uid_index[u] for u in frontier]
    return p.matrix.means[rows], p.matrix.stds[rows]


def _answers_from(mean, std, z):
    return np.stack([mean, np.maximum(mean - z * std, 0.0), mean + z * std],
                    1)


def _triples(queries):
    return [(q.task, q.node, q.input_gb) for q in queries]


def predict_error(s: State, sample: List[Plan], drifts: List[Check],
                  control: bool = False):
    """(largest relative error, estimates compared): each sampled
    replan's drift-check answers, served matrix and running-task answers,
    and each sampled drift check that did not replan, against the
    reference that replayed that run's completions up to it.  With
    `control`, the reference in bfloat16 takes the program's place."""
    names = [n.name for n in s.nodes]
    runs = {r.index: r for r in s.runs}
    worst, n = 0.0, 0
    model, at, cur = None, 0, None
    for p in sorted(sample + drifts, key=lambda p: (p.run, p.step)):
        run = runs[p.run]
        if p.run != cur:
            model, at, cur = s.fleet.models[run.workflow].copy(), 0, p.run
        for task, node, x, y, attempt in run.records[at:p.step]:
            if attempt == 0:
                model.observe(task, node, x, y)
        at = p.step
        if isinstance(p, Check):
            answered = [(p.queries, p.answers)]
        else:
            answered = [p.drift, p.running]
            frontier = _frontier(run.dag, p.state.started)
            want = _ref_matrix(model, run.dag, frontier, names, np.float64)
            mean, std = _served(p, frontier)
            if control:
                mean, std, _ = _ref_matrix(model, run.dag, frontier, names,
                                           ref.bfloat16())
            worst = max(worst, ref.rel_err(mean, want[0], want[2]),
                        ref.rel_err(std, want[1]))
            n += mean.size
        for queries, got in answered:
            if not queries:
                continue
            q = _triples(queries)
            if control:
                m, sd, _ = model.predict(q, ref.bfloat16())
                got = _answers_from(m, sd, s.cfg["z"])
            worst = max(worst, compare([got], [model.predict(q)]))
            n += len(q)
    return worst, n


def schedule_mismatch(s: State, sample: List[Plan]) -> int:
    """Tasks placed or timed otherwise, and node orders that differ,
    between each sampled replan and plain HEFT on the cost matrix the
    plane served (mean + z(quantile) std) under the execution state's
    ready times and node availability."""
    names = [n.name for n in s.nodes]
    rates = [float(n.net_gbps) for n in s.nodes]
    z = statistics.NormalDist().inv_cdf(s.cfg["quantile"])
    runs = {r.index: r for r in s.runs}
    bad = 0
    for p in sample:
        dag = runs[p.run].dag
        frontier = _frontier(dag, p.state.started)
        inside = set(frontier)
        deps = {u: list(dag.tasks[u].deps) for u in dag.tasks}
        out_gb = {u: dag.tasks[u].output_gb for u in dag.tasks}
        running = [(u, name, start)
                   for u, (name, start) in p.state.running.items()]
        ready, avail = rh.frontier_constraints(
            frontier, deps, out_gb, names, rates, p.state.now,
            p.state.finished, running, p.running[1][:, 0])
        mean, std = _served(p, frontier)
        want = rh.heft(frontier,
                       {u: [d for d in deps[u] if d in inside]
                        for u in frontier},
                       out_gb, mean + z * std, names, rates, ready, avail)
        bad += rh.mismatches(p.sched.assignment, p.sched.order, p.sched.est,
                             want)
    return bad


def _bands(band: dict, node: dict, plan: tuple) -> None:
    """The planner's drift bands after a plan: each planned task's served
    (mean, std) on the node it was assigned."""
    mat, assignment = plan
    for u, name in assignment.items():
        i, j = mat.uid_index[u], mat.node_index[name]
        band[u] = (float(mat.means[i, j]), float(mat.stds[i, j]))
        node[u] = name


def _asked(dag, frontier, node, queries) -> bool:
    """Did the drift check ask about each frontier task, in order, on the
    node it was last assigned?"""
    return len(queries) == len(frontier) and all(
        (q.task, q.node, q.input_gb)
        == (dag.tasks[u].task_name, node[u], dag.tasks[u].input_gb)
        for q, u in zip(queries, frontier))


def replan_mismatch(s: State) -> int:
    """Completions whose decision differs from the band test replayed on
    the program's own answers: a frontier task whose served mean left
    z x its band's std (bands from the matrix served at the task's last
    plan) calls for a replan unless within `cooldown` completions of the
    last one.  A drift check made (or missed) on an empty frontier, or
    answering another number of tasks, is a mismatch too, and so is a
    sampled one (replan or not) that asked about other tasks or nodes.
    The replay follows the program's own decisions, so a wrong one counts
    once."""
    z, cooldown = s.cfg["z"], s.cfg["cooldown"]
    asked = {(p.run, p.step): p.drift[0] for p in _sample(s)}
    asked.update(((c.run, c.step), c.queries) for c in s.drifts)
    bad = 0
    for run in s.runs:
        if run.first is None:
            continue
        band, node = {}, {}
        _bands(band, node, run.first)
        since = 10 ** 9
        for c in run.checks:
            since += 1
            frontier = _frontier(run.dag, c.started)
            ans = c.answers
            ok = (ans is not None and len(ans) == len(frontier)
                  if frontier else ans is None)
            queries = asked.get((c.run, c.step))
            if ok and queries is not None:
                ok = _asked(run.dag, frontier, node, queries)
            # the planner's own expression, on its own answers
            want = ok and bool(frontier) and since > cooldown and any(
                abs(mean - band[u][0]) > z * max(band[u][1], 1e-9)
                for u, (mean, _, _) in zip(frontier, ans))
            bad += (not ok) or want != (c.plan is not None)
            if c.plan is not None:
                since = 0
                _bands(band, node, c.plan)
    return bad


def verify(s: State, cfg: dict, control: bool = False) -> List[dict]:
    sample, drifts = _sample(s), list(s.drifts)
    err, n = predict_error(s, sample, drifts, control)
    lim = cfg["limits"]
    return [check("predict_rel_err", err if sample else math.inf,
                  lim["predict_rel_err"]),
            check("schedule_mismatch", schedule_mismatch(s, sample),
                  lim["schedule_mismatch"]),
            check("replan_mismatch", replan_mismatch(s),
                  lim["replan_mismatch"]),
            check("checked_replans", len(sample),
                  lim["min_checked_replans"], ">="),
            check("checked_drift_checks", len(drifts),
                  lim["min_checked_drift_checks"], ">="),
            check("checked_queries", n, lim["min_checked_queries"], ">="),
            check("errors", sum(r.error is not None for r in s.runs), 0)]


def release(s: State) -> None:
    """Free the program's state before the reference runs: the store and
    the serving fleet go; the runs, the sampled replans and the reference
    models stay."""
    s.fleet.store = None
    s.fleet.services = None
