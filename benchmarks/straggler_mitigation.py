"""Beyond-paper experiment: uncertainty-driven straggler mitigation
(the paper's Section 9 future work — "leverage the uncertainty estimates in
schedulers").

Setup: eager workflow on a Section 8.1-style 20-node heterogeneous cluster
(drawn from the paper's machine pool); a fraction of task executions are
stragglers (true runtime inflated 3-8x, e.g. I/O contention).
Policies compared:
  * none          — run to completion
  * fixed-1.5x    — speculate when elapsed > 1.5x predicted mean (Hadoop-style)
  * posterior-q95 — speculate when elapsed exceeds Lotaru's posterior
                    95%-quantile (mean + 1.645 sigma) for that (task, node)
  * adaptive-q95  — the wired-end-to-end path: `execute_adaptive` with a
                    `SpeculationPolicy` — the event loop fires progress
                    checks, the planner reads its decision-plane matrix
                    rows, and flagged stragglers get real backup launches
                    (first finisher wins, the loser is cancelled)

The first three are analytic (speculation folded into the runtime
closure); adaptive-q95 actually duplicates tasks in the event loop.
Metric: makespan vs the no-straggler ideal, plus wasted duplicate seconds.

  PYTHONPATH=src python -m benchmarks.straggler_mitigation
"""
from __future__ import annotations

import numpy as np

from benchmarks.common import build_experiment, fmt_table
from repro.online import OnlinePredictor, OnlineReschedulingPlanner
from repro.sched.cluster import TARGET_MACHINES
from repro.sched.fused import fused_heft_schedule
from repro.sched.straggler import straggler_threshold
from repro.store import resolve_bench
from repro.workflow.simulator import (SpeculationPolicy, execute_adaptive,
                                      execute_schedule, random_cluster)


def run(straggler_frac: float = 0.08, factor: float = 5.0, seed: int = 0,
        n_nodes: int = 20, quiet: bool = False) -> dict:
    exp = build_experiment("eager", training_set=0, seed=seed)
    rng = np.random.default_rng(seed)
    nodes = random_cluster(rng, list(TARGET_MACHINES), n_nodes=n_nodes)
    uids = sorted(exp.dag.tasks)
    stragglers = {u for u in uids if rng.random() < straggler_frac}

    def true_rt(uid, node):
        t = exp.dag.tasks[uid]
        return exp.gt.runtime(t.task_name, t.input_gb, node, uid)

    def pred(uid, node):
        t = exp.dag.tasks[uid]
        return exp.predictors["lotaru-g"].predict(
            t.task_name, t.input_gb, resolve_bench(exp.benches, node.name))

    sched = fused_heft_schedule(exp.dag, nodes, lambda u, n: pred(u, n)[0])
    ideal = execute_schedule(exp.dag, sched, nodes, true_rt).makespan

    results = {}
    for policy in ("none", "fixed-1.5x", "posterior-q95"):
        extra_work = 0.0

        def runtime(uid, node):
            base = true_rt(uid, node)
            if uid not in stragglers:
                return base
            slow = base * factor
            mean, lo, hi = pred(uid, node)
            std = max((hi - mean) / 1.96, 1e-3)
            if policy == "none":
                return slow
            thr = (1.5 * mean if policy == "fixed-1.5x"
                   else straggler_threshold(mean, std, 0.95))
            if slow <= thr:
                return slow                      # never flagged
            # speculate at thr on the fastest other node; first finisher wins
            backup = min((true_rt(uid, n) for n in nodes
                          if n.name != node.name), default=slow)
            finish = min(slow, thr + backup)
            nonlocal_extra[0] += min(backup, max(slow - thr, 0.0))
            return finish

        nonlocal_extra = [0.0]
        res = execute_schedule(exp.dag, sched, nodes, runtime)
        results[policy] = {"makespan_min": res.makespan / 60.0,
                           "vs_ideal_pct": 100 * (res.makespan / ideal - 1),
                           "duplicate_work_min": nonlocal_extra[0] / 60.0}

    # the wired path: real backup launches in the event loop, decisions
    # from the planner's decision-plane matrix rows.  "adaptive-nospec"
    # isolates what rescheduling alone recovers, so the adaptive-q95 delta
    # is attributable to speculation, not re-planning.
    sf = lambda u: factor if u in stragglers else 1.0

    def _planner():
        return OnlineReschedulingPlanner(
            exp.dag, nodes,
            OnlinePredictor(exp.predictors["lotaru-g"], benches=exp.benches),
            benches=exp.benches)

    nospec = execute_adaptive(exp.dag, nodes, _planner(), true_rt,
                              straggler_factor=sf)
    results["adaptive-nospec"] = {
        "makespan_min": nospec.makespan / 60.0,
        "vs_ideal_pct": 100 * (nospec.makespan / ideal - 1),
        "duplicate_work_min": 0.0}
    res = execute_adaptive(exp.dag, nodes, _planner(), true_rt,
                           straggler_factor=sf,
                           speculation=SpeculationPolicy(
                               q=0.95, check_interval_s=15.0))
    results["adaptive-q95"] = {
        "makespan_min": res.makespan / 60.0,
        "vs_ideal_pct": 100 * (res.makespan / ideal - 1),
        "duplicate_work_min": res.backup_waste_s / 60.0,
        "n_backups": res.n_backups}

    rows = [[p, f"{v['makespan_min']:.1f}", f"{v['vs_ideal_pct']:+.1f}%",
             f"{v['duplicate_work_min']:.1f}"] for p, v in results.items()]
    table = fmt_table(["policy", "makespan", "vs no-stragglers", "dup work"],
                      rows, f"Straggler mitigation ({len(stragglers)} "
                            f"stragglers x{factor:.0f})")
    if not quiet:
        print(table)
        q95 = results["posterior-q95"]["vs_ideal_pct"]
        none = results["none"]["vs_ideal_pct"]
        print(f"[claim] posterior-quantile speculation recovers most of the "
              f"straggler penalty: {none:.0f}% -> {q95:.0f}% -> "
              f"{'PASS' if q95 < 0.5 * none else 'FAIL'}")
        adaptive = results["adaptive-q95"]["makespan_min"]
        nospec_ms = results["adaptive-nospec"]["makespan_min"]
        print(f"[claim] event-loop speculation (execute_adaptive) beats "
              f"no-speculation: {nospec_ms:.1f}m -> {adaptive:.1f}m "
              f"({res.n_backups} backups) -> "
              f"{'PASS' if adaptive < nospec_ms else 'FAIL'}")
    return results


if __name__ == "__main__":
    run()
