"""Evaluation B.3 (Tables 7-8): cloud cost prediction under hourly and
minute billing.  Paper claims: Lotaru-A median |dev| lowest (<5% hourly,
<6.5% minute), Lotaru ~2.5-3x better than Online-M/P, Naive worst;
minute billing increases deviations for all but Naive."""
from __future__ import annotations

from typing import Dict

import numpy as np

from benchmarks.common import build_experiment, fmt_table
from repro.sched.cluster import PAPER_MACHINES
from repro.sched.cost import actual_cost, cost_deviation_pct, predicted_cost
from repro.sched.fused import fused_heft_schedule
from repro.workflow.generator import WORKFLOWS
from repro.workflow.simulator import execute_schedule
from repro.core.microbench import NodeSpec

COST_METHODS = ("naive", "online-m", "online-p", "lotaru-g", "lotaru-a")


def _cloud(n_each: int = 4):
    nodes = []
    for name in ("N1", "N2", "C2"):
        spec = PAPER_MACHINES[name]
        for i in range(n_each):
            nodes.append(NodeSpec(f"{name}-{i}", spec.cpu, spec.mem,
                                  spec.io_read, spec.io_write, spec.cores,
                                  spec.power_watts, spec.price_per_hour,
                                  spec.net_gbps))
    return nodes


def run(seed: int = 0, quiet: bool = False) -> dict:
    nodes = _cloud()
    out: Dict[str, Dict[str, Dict[str, float]]] = {"hourly": {}, "minute": {}}
    for wf in WORKFLOWS:
        for ts in (0, 1):
            exp = build_experiment(wf, training_set=ts, seed=seed)

            def true_rt(uid, node):
                t = exp.dag.tasks[uid]
                return exp.gt.runtime(t.task_name, t.input_gb, node, uid)

            for meth in COST_METHODS:
                def pred_rt(uid, node):
                    t = exp.dag.tasks[uid]
                    bench = exp.benches[node.name.rsplit("-", 1)[0]]
                    return exp.predictors[meth].predict(t.task_name,
                                                        t.input_gb, bench)[0]
                sched = fused_heft_schedule(exp.dag, nodes, pred_rt)
                res = execute_schedule(exp.dag, sched, nodes, true_rt)
                for billing in ("hourly", "minute"):
                    pred_c = predicted_cost(sched, nodes, billing)
                    act_c = actual_cost(res, nodes, billing)
                    out[billing].setdefault(f"{wf}/{ts}", {})[meth] = \
                        cost_deviation_pct(pred_c, act_c)

    results = {}
    for billing in ("hourly", "minute"):
        rows = []
        for key in sorted(out[billing]):
            rows.append([key] + [f"{out[billing][key][m]:+.2f}"
                                 for m in COST_METHODS])
        med = {m: float(np.median([abs(v[m]) for v in out[billing].values()]))
               for m in COST_METHODS}
        rows.append(["median(abs)"] + [f"{med[m]:.2f}" for m in COST_METHODS])
        results[billing] = {"per_wf": out[billing], "median_abs": med}
        print(fmt_table(["workflow/set"] + list(COST_METHODS), rows,
                        f"Table {'7' if billing == 'hourly' else '8'} - "
                        f"% cost deviation, {billing} billing"))
        print()
    if not quiet:
        mh = results["hourly"]["median_abs"]
        mm = results["minute"]["median_abs"]
        base_h = min(mh["online-m"], mh["online-p"])
        print(f"[claim] lotaru-a best hourly -> "
              f"{'PASS' if mh['lotaru-a'] <= min(mh['lotaru-g'], base_h) else 'FAIL'};"
              f"  >=2x better than online -> "
              f"{'PASS' if base_h >= 2 * mh['lotaru-a'] else 'FAIL'};"
              f"  minute >= hourly deviation for lotaru -> "
              f"{'PASS' if mm['lotaru-a'] >= mh['lotaru-a'] - 0.5 else 'FAIL'}")
    return results


if __name__ == "__main__":
    run()
