"""Refresh-overhead benchmark: the posterior maintenance plane.

Two claims:

  * **Batched refresh is one dispatch, not a fleet of scalar refits.**
    >= 64 due tasks across >= 2 tenants are re-fit by ONE padded/masked
    batched evidence fixed-point dispatch (`FleetRefresher.refresh`) and
    published in ONE copy-on-write store generation; the benchmark asserts
    both and reports the wall-clock speedup over per-task scalar refits
    (one jit'd `fit_blr` dispatch per task — the loop the plane replaces),
    plus numerical parity between the two.

  * **Refreshing actually helps the online-adaptation scenario.**
    On a drifted cluster with heteroscedastic production-scale noise, the
    streaming-only predictor keeps the (alpha, beta) evidence lift frozen
    at profile scale; periodic refresh re-chooses it from the accumulated
    observations.  Reported: median APE and 95%-interval coverage on the
    remaining tasks, frozen vs refreshed.

  PYTHONPATH=src python -m benchmarks.refresh_overhead
"""
from __future__ import annotations

import time
from typing import Dict, List

import numpy as np

from benchmarks.common import build_experiment, fmt_table
from repro.core import bayes
from repro.online import (FleetRefresher, OnlinePredictor, PredictionService,
                          RefreshPolicy, TaskCompletion)
from repro.sched.cluster import LOCAL, TARGET_MACHINES
from repro.sched.fused import fused_heft_schedule
from repro.workflow.simulator import execute_schedule

N_TASKS_PER_TENANT = 40
TENANTS = ("acme", "globex")
OBS_PER_TASK = 12
DRIFT = {"A1": 1.5, "A2": 0.7, "N1": 1.4, "N2": 0.6, "C2": 2.0}


def _make_tenant(tenant: str, store, rng) -> OnlinePredictor:
    from repro.core.microbench import simulate_microbench
    from repro.core.predictor import LotaruPredictor
    from repro.core.traces import TraceRow
    traces = []
    for j in range(N_TASKS_PER_TENANT):
        slope, base = 15.0 + 2.0 * j, 2.0 + 0.5 * j
        traces += [TraceRow("wf", f"t{j}", "local", s, base + slope * s)
                   for s in np.linspace(0.05, 0.4, 6)]
    lot = LotaruPredictor(
        "G", local_bench=simulate_microbench(LOCAL, 1)).fit(traces)
    online = OnlinePredictor(lot)
    PredictionService(online, store=store, tenant=tenant, workflow="wf")
    for j in range(N_TASKS_PER_TENANT):
        for i in range(OBS_PER_TASK):
            x = float(rng.uniform(0.5, 8.0))
            online.observe(TaskCompletion(
                "wf", f"u{j}-{i}", f"t{j}", "local", x,
                float(2.0 + (18.0 + 2.0 * j) * x + rng.normal(0, 1.0))))
    return online


def run_fleet_refresh(seed: int = 0, quiet: bool = False) -> dict:
    """One batched dispatch for the whole fleet vs per-task scalar refits."""
    import jax

    from repro.store import PosteriorStore
    rng = np.random.default_rng(seed)
    store = PosteriorStore()
    onlines = {t: _make_tenant(t, store, rng) for t in TENANTS}
    policy = RefreshPolicy(every_n=OBS_PER_TASK)
    refresher = FleetRefresher(store, policy)

    due = refresher.due()
    n_due = len(due)
    n_tenants = len({b.tenant for b, _ in due})
    assert n_due >= 64, f"scenario must make >=64 tasks due, got {n_due}"
    assert n_tenants >= 2, "scenario must span >=2 tenants"

    # warm pass: compiles the batched fit for this shape, refreshes fleet
    report0 = refresher.refresh(due)
    assert report0.n_dispatches == 1, "fleet refresh must be ONE dispatch"
    assert report0.n_tasks >= 64 and report0.n_tenants >= 2
    gen_delta = 1  # every refresh pass publishes exactly one generation

    # re-arm every task and time a warm refresh end to end
    for online in onlines.values():
        for j in range(N_TASKS_PER_TENANT):
            for i in range(OBS_PER_TASK):
                x = float(rng.uniform(0.5, 8.0))
                online.observe(TaskCompletion(
                    "wf", f"w{j}-{i}", f"t{j}", "local", x,
                    float(2.0 + (18.0 + 2.0 * j) * x + rng.normal(0, 1.0))))
    due = refresher.due()
    gen0 = store.generation
    t0 = time.perf_counter()
    report = refresher.refresh(due)
    batched_s = time.perf_counter() - t0
    assert report.n_dispatches == 1
    assert report.n_tasks == len(due)
    assert store.generation == gen0 + gen_delta

    # scalar baseline: one jit'd fit dispatch per task over the same data
    # (shapes padded to a common N so the scalar fit compiles once)
    snaps = []
    for online in onlines.values():
        snaps.extend(online.refresh_snapshot(list(online.tasks)).values())
    n_max = max(len(s[1]) for s in snaps)

    def _padded(s):
        x = np.zeros(n_max, np.float32)
        y = np.zeros(n_max, np.float32)
        m = np.zeros(n_max, np.float32)
        k = len(s[1])
        x[:k], y[:k], m[:k] = s[1], s[2], 1.0
        return x, y, m

    scalar_fit = jax.jit(bayes.fit_blr)
    x0, y0, m0 = _padded(snaps[0])
    warm = scalar_fit(x0, y0, m0)
    jax.block_until_ready(warm["mu"])
    t0 = time.perf_counter()
    scalar_posts = []
    for s in snaps:
        x, y, m = _padded(s)
        scalar_posts.append(scalar_fit(x, y, m))
    jax.block_until_ready(scalar_posts[-1]["mu"])
    scalar_s = time.perf_counter() - t0

    # parity: batched refresh state vs the scalar refit, per task
    max_dq = 0.0
    for online in onlines.values():
        for task, st in online.tasks.items():
            ref = bayes.nig_to_blr(bayes.nig_from_blr(
                bayes.refresh_fit(st.fit_xs, st.fit_ys, st.xs, st.ys)))
            got = bayes.nig_to_blr(st.nig)
            for xq in (1.0, 6.0):
                m1, s1 = bayes.predict_blr_np(got, xq)
                m2, s2 = bayes.predict_blr_np(ref, xq)
                q1, q2 = m1 + 1.645 * s1, m2 + 1.645 * s2
                max_dq = max(max_dq, abs(float(q1 - q2))
                             / max(abs(float(q2)), 1.0))

    out = {"n_tasks": report.n_tasks, "n_tenants": report.n_tenants,
           "n_dispatches": report.n_dispatches,
           "batched_ms": 1e3 * batched_s, "scalar_ms": 1e3 * scalar_s,
           "speedup": scalar_s / max(batched_s, 1e-9),
           "max_quantile_rel_diff": max_dq}
    if not quiet:
        print(f"Fleet refresh: {report.n_tasks} tasks / "
              f"{report.n_tenants} tenants in {report.n_dispatches} "
              f"dispatch, ONE store generation")
        print(f"  batched {out['batched_ms']:.1f}ms vs scalar per-task "
              f"{out['scalar_ms']:.1f}ms -> {out['speedup']:.1f}x")
        print(f"  predictive-quantile parity vs scalar refits: "
              f"max rel diff {max_dq:.2e}")
        print(f"[claim] >=64 tasks, >=2 tenants, ONE batched dispatch -> "
              f"{'PASS' if report.n_tasks >= 64 and report.n_tenants >= 2 and report.n_dispatches == 1 else 'FAIL'}")
    return out


def run_adaptation_gain(seed: int = 0, quiet: bool = False) -> dict:
    """Frozen streaming lift vs periodic refresh on the drifted-cluster
    online-adaptation scenario.  The cluster mixes several local-class
    instances with the paper's target machines so regression posteriors
    actually stream (only local-attributable completions feed a task
    model), and true runtimes carry per-execution heteroscedastic noise —
    the production-scale noise level the profile-time evidence lift has
    never seen, which is exactly what a periodic refresh re-estimates."""
    from repro.core.microbench import NodeSpec
    from repro.store import PosteriorStore, resolve_bench
    exp = build_experiment("eager", training_set=0, seed=seed)
    lot = exp.predictors["lotaru-g"]
    local_pool = [NodeSpec(f"local-{i}", LOCAL.cpu, LOCAL.mem, LOCAL.io_read,
                           LOCAL.io_write, LOCAL.cores, LOCAL.power_watts,
                           LOCAL.price_per_hour, LOCAL.net_gbps)
                  for i in range(4)]
    nodes = local_pool + list(TARGET_MACHINES)
    rng = np.random.default_rng(seed)
    noise = {u: float(np.exp(rng.normal(0, 0.25))) for u in exp.dag.tasks}

    def true_rt(u, n):
        t = exp.dag.tasks[u]
        base = n.name.rsplit("-", 1)[0] if "-" in n.name else n.name
        return exp.gt.runtime(t.task_name, t.input_gb, n, u) \
            * DRIFT.get(base, 1.0) * noise[u]

    pred_rt = lambda u, n: lot.predict(
        exp.dag.tasks[u].task_name, exp.dag.tasks[u].input_gb,
        resolve_bench(exp.benches, n.name))[0]
    sched = fused_heft_schedule(exp.dag, nodes, pred_rt)
    recs = sorted(execute_schedule(exp.dag, sched, nodes, true_rt).records,
                  key=lambda r: r.finish)
    half = int(0.6 * len(recs))

    variants: Dict[str, OnlinePredictor] = {}
    refreshers = {}
    for name, every_n in (("frozen", None), ("refreshed", 8)):
        online = OnlinePredictor(lot, benches=exp.benches)
        variants[name] = online
        if every_n is not None:
            store = PosteriorStore()
            PredictionService(online, exp.benches, store=store,
                              tenant="bench", workflow="eager")
            refreshers[name] = FleetRefresher(
                store, RefreshPolicy(every_n=every_n, drift_ratio=4.0))
    for i, r in enumerate(recs[:half]):
        t = exp.dag.tasks[r.uid]
        comp = TaskCompletion("eager", r.uid, t.task_name, r.node,
                              t.input_gb, r.finish - r.start, r.finish)
        for name, online in variants.items():
            online.observe(comp)
            if name in refreshers:
                refreshers[name].maybe_refresh()

    # evaluate the task models where refresh acts: LOCAL-node predictions
    # for tasks whose posterior actually streamed (cross-node queries mix
    # in extrapolation-factor error, which no refit can remove and which
    # would drown the calibration signal)
    rem = [r.uid for r in recs[half:]]
    streamed = {t for t, st in variants["frozen"].tasks.items()
                if st.nig is not None and st.nig["n_obs"] > 0}
    out: Dict[str, Dict[str, float]] = {}
    for name, online in variants.items():
        errs: List[float] = []
        covered = 0
        total = 0
        for u in rem:
            t = exp.dag.tasks[u]
            if t.task_name not in streamed:
                continue
            actual = true_rt(u, local_pool[0])
            mean, lo, hi = online.predict(t.task_name, t.input_gb, None)
            errs.append(abs(mean - actual) / actual)
            covered += int(lo <= actual <= hi)
            total += 1
        out[name] = {"median_ape_pct": 100.0 * float(np.median(errs)),
                     "coverage_95_pct": 100.0 * covered / max(total, 1),
                     "n_eval": total}
    out["refresh_passes"] = sum(
        1 for rep in refreshers["refreshed"].reports if rep.n_tasks > 0)
    if not quiet:
        rows = [[name, f"{v['median_ape_pct']:.2f}%",
                 f"{v['coverage_95_pct']:.1f}%"]
                for name, v in out.items() if isinstance(v, dict)]
        print(fmt_table(["variant", "median APE", "95% coverage"], rows,
                        "Online adaptation with periodic evidence refresh "
                        "(remaining tasks after 60% completions, local "
                        "task-model predictions)"))
        print(f"  refresh passes that rewrote rows: {out['refresh_passes']}")
        f, r = out["frozen"], out["refreshed"]
        print(f"[claim] refresh does not degrade MPE and moves 95% coverage "
              f"toward nominal: APE {f['median_ape_pct']:.2f}% -> "
              f"{r['median_ape_pct']:.2f}%, coverage "
              f"{f['coverage_95_pct']:.1f}% -> {r['coverage_95_pct']:.1f}%")
    return out


def run(seed: int = 0, quiet: bool = False) -> dict:
    fleet = run_fleet_refresh(seed, quiet)
    if not quiet:
        print()
    gain = run_adaptation_gain(seed, quiet)
    return {"fleet_refresh": fleet, "adaptation_gain": gain}


if __name__ == "__main__":
    run()
