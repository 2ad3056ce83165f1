"""The multi-tenant prediction service: store set-up shared by every
driver that serves a fleet of nf-core tenants from one PosteriorStore."""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List

import numpy as np

from bench import reference as ref


@dataclass
class Fleet:
    store: object
    services: List[object]            # one PredictionService per tenant
    experiments: Dict[str, object]    # workflow -> profiled experiment
    models: Dict[str, "ref.Model"]    # workflow -> plain reference model
    dags: Dict[str, object]           # workflow -> physical DAG
    topo: Dict[str, list] = None      # workflow -> DAG uids, topo order
    inputs: Dict[str, dict] = None    # workflow -> task -> DAG input sizes


def reference_models(experiments) -> Dict[str, "ref.Model"]:
    """Per workflow, the plain float64 model from the run's raw data: the
    local profiling traces and the microbenchmark readings."""
    out = {}
    for w, e in experiments.items():
        benches = {name: ref.Bench(b.name, b.cpu, b.io_read, b.io_write)
                   for name, b in e.benches.items()}
        rows = [(t.task, t.input_gb, t.runtime_s) for t in e.traces]
        out[w] = ref.Model.from_traces(rows, benches["local"], benches)
    return out


def profile(workflows, seed: int, spans) -> Dict[str, object]:
    """Local profiling and the Lotaru-G fit of every workflow, as a
    deployment does before it serves."""
    from benchmarks.common import build_experiment
    with spans.span("setup.profile"):
        return {w: build_experiment(w, seed=seed, methods=("lotaru-g",))
                for w in workflows}


def build_fleet(cfg: dict, seed: int, spans) -> Fleet:
    from repro.online import OnlinePredictor, PredictionService
    from repro.store import PosteriorStore
    workflows = cfg["workflows"]
    exps = profile(workflows, seed, spans)
    with spans.span("setup.store"):
        store = PosteriorStore()
        services = []
        for i in range(cfg["tenants"]):
            w = workflows[i % len(workflows)]
            e = exps[w]
            online = OnlinePredictor(e.predictors["lotaru-g"],
                                     benches=e.benches)
            services.append(PredictionService(
                online, e.benches, store=store, tenant=f"tenant-{i:04d}",
                workflow=w))
    return Fleet(store, services, exps, reference_models(exps),
                 {w: e.dag for w, e in exps.items()},
                 {w: e.dag.topo_order() for w, e in exps.items()},
                 {w: _inputs(e.dag) for w, e in exps.items()})


def _inputs(dag) -> Dict[str, np.ndarray]:
    out: Dict[str, list] = {}
    for t in dag.tasks.values():
        out.setdefault(t.task_name, []).append(t.input_gb)
    return {k: np.asarray(v, np.float64) for k, v in out.items()}


def warm_predict(fleet: Fleet, sizes, spans) -> None:
    """Run the predictive path once at each batch size the window can
    reach.  Every new size compiles several programs (the padding before
    the kernel is traced per size), so a size first met inside the window
    would compile there."""
    from repro.store.compute import predict_stacked
    snap = fleet.store.snapshot()
    keys = fleet.store.task_keys()
    with spans.span("setup.warm_predict"):
        for q in sizes:
            post = snap.gather([keys[i % len(keys)] for i in range(q)])
            predict_stacked(np.ones(q), post)

