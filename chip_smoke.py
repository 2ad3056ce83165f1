#!/usr/bin/env python3
"""Chip smoke test: the in-process Lotaru service, once, on one TPU chip.

  python chip_smoke.py [--seed N]

One process drives the service through the entry points its users call,
at a state size they would call real, and checks every answer against a
plain float64 reference:

  store    2,048 tenant namespaces (tenant/workflow) in one PosteriorStore,
           drawn from the five nf-core workflows, each bound through
           PredictionService; every stored row equals its predictor's.
  predict  8 caller threads send bursts through
           AsyncPredictionFrontend.predict_async; one coalesced dispatch
           carries all 16,384 queries.  Mean and std against
           bayes.predict_blr_np on the same gathered rows.
  refresh  one FleetRefresher.refresh over >= 4,096 due tasks of >= 64
           tenants: one bayes_fit_ragged dispatch.  Predictive mean and
           std of the fit against a float64 NumPy evidence fit of the same
           buffers.
  ingest   2,000 records over 4 tenants through observe_many (host
           float64 by contract): state digests equal the scalar chain.
  replan   FusedPlane.schedule on a 1000-task x 100-node DAG through the
           jit engine, and replan_many over 8 tenants sharing the cluster:
           schedules bitwise equal to heft_schedule_matrix.

Each phase prints one JSON line: the device it ran on, compile seconds
(JAX's own trace, lowering and compile-or-cache-fetch events), the wall
time of its first (cold) and of a repeated (warm) run, and the largest
error against the reference beside its tolerance.  Every timed call ends
in host arrays, so it waits for the device.  The last line is
{"ok": true, "device": {...}}; a missing TPU, an exception or an error
beyond tolerance exits non-zero without it.  No child process is started.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import threading
import time
from dataclasses import dataclass
from typing import Dict, List

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks.common import build_experiment  # noqa: E402
from benchmarks.ingest_throughput import _fleet, _stream  # noqa: E402
from benchmarks.replan_latency import _build  # noqa: E402
from repro.core import bayes  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.kernels.bayes_fit import (bayes_fit_ragged,  # noqa: E402
                                     pack_predict_planes, pad_ragged)
from repro.online import (FleetRefresher, OnlinePredictor,  # noqa: E402
                          PredictionQuery, PredictionService, RefreshPolicy,
                          TaskCompletion)
from repro.sched.cluster import LOCAL, TARGET_MACHINES  # noqa: E402
from repro.sched.fused import (FusedPlane, ReplanRequest,  # noqa: E402
                               replan_many, sweep_device)
from repro.sched.heft import heft_schedule_matrix  # noqa: E402
from repro.serve import state_digest  # noqa: E402
from repro.store import AsyncPredictionFrontend, PosteriorStore  # noqa: E402
from repro.store.compute import fit_stacked, scale  # noqa: E402
from repro.workflow.generator import WORKFLOWS  # noqa: E402

PLATFORM = "tpu"          # the platform every phase must run on
# Float32 device kernels against float64 references, as relative errors.
# A predicted mean is measured against the larger of itself and its task's
# runtime spread y_sd: a mean near zero is the difference of two terms of
# that size, and float32 keeps that difference only to eps * y_sd.
PREDICT_TOL = 1e-5        # ~10 float32 ops (eps 1.2e-7); v5e measured 3.1e-7
FIT_TOL = 1e-4            # 30 float32 fixed-point steps; v5e measured 2.8e-6
CALLERS = 8               # predict caller threads
QUERIES_PER_TENANT = 8    # 2,048 tenants x 8 = 16,384 in one dispatch
REFRESH_EVERY = 4         # completions that make a task due for refresh
INGEST_WINDOW = 128       # records per observe_many window
REPLAN_QUANTILES = (0.95, None, 0.5, 0.6, 0.7, 0.8, 0.9, 0.99)  # 8 tenants


@dataclass(frozen=True)
class Sizes:
    tenants: int = 2048             # namespaces in the store
    refresh_tasks: int = 4096       # due tasks in the one refresh pass
    refresh_tenants: int = 64       # ... spread over at least this many
    ingest_records: int = 2000
    replan_tasks: int = 1000
    replan_nodes: int = 100


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling (or fetching a
    compiled program from the persistent cache), from its own events."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_) -> None:
        if event in self.EVENTS:
            self.seconds += secs

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def close(self) -> None:
        jax.monitoring.unregister_event_duration_listener(self._duration)
        jax.monitoring.unregister_event_listener(self._event)


def _device() -> dict:
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind}


def _timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def _rel_err(got: np.ndarray, ref: np.ndarray, floor=0.0) -> float:
    return float(np.max(np.abs(got - ref) / np.maximum(np.abs(ref), floor)))


def _lowers_to_pallas(fn, *args) -> bool:
    """Whether `fn` at these arguments compiles to a Pallas TPU kernel
    call (`tpu_custom_call`) on the default device; never off a TPU,
    where such a kernel cannot even be lowered."""
    if jax.default_backend() != "tpu":
        return False
    text = jax.jit(fn).lower(*args).compile().as_text()
    return "tpu_custom_call" in text


# ---------------------------------------------------------------------------
# plain float64 reference for the evidence fit
# ---------------------------------------------------------------------------

def fit_blr_f64(x: np.ndarray, y: np.ndarray, m: np.ndarray) -> dict:
    """`core.bayes.fit_blr` (MacKay evidence fixed point over a
    standardized [1, x] design) in float64 NumPy, over (T, N) masked rows."""
    eps, n_iters = bayes.EPS, bayes.N_ITERS
    x, y, m = (np.asarray(a, np.float64) for a in (x, y, m))
    n = np.maximum(m.sum(1), 1.0)
    x_mu = (x * m).sum(1) / n
    y_mu = (y * m).sum(1) / n
    x_sd = np.sqrt(((x - x_mu[:, None]) ** 2 * m).sum(1) / n + eps)
    y_sd = np.sqrt(((y - y_mu[:, None]) ** 2 * m).sum(1) / n + eps)
    xs = (x - x_mu[:, None]) / x_sd[:, None] * m
    ys = (y - y_mu[:, None]) / y_sd[:, None] * m
    phi = np.stack([np.ones_like(xs), xs], axis=-1) * m[..., None]
    gram = np.einsum("tni,tnj->tij", phi, phi)
    phi_y = np.einsum("tni,tn->ti", phi, ys)
    eye = np.eye(2)
    alpha = np.ones(len(x))
    beta = np.ones(len(x))

    def posterior(alpha, beta):
        sigma = np.linalg.inv(alpha[:, None, None] * eye
                              + beta[:, None, None] * gram)
        return beta[:, None] * np.einsum("tij,tj->ti", sigma, phi_y), sigma

    for _ in range(n_iters):
        mu, _ = posterior(alpha, beta)
        lam = np.linalg.eigvalsh(beta[:, None, None] * gram)
        gamma = (lam / (alpha[:, None] + lam)).sum(1)
        resid = ((ys - np.einsum("tni,ti->tn", phi, mu)) ** 2 * m).sum(1)
        alpha = np.clip(gamma / np.maximum((mu * mu).sum(1), eps),
                        1e-6, 1e6)
        beta = np.clip(np.maximum(n - gamma, eps) / np.maximum(resid, eps),
                       1e-6, 1e8)
    mu, sigma = posterior(alpha, beta)
    return {"mu": mu, "sigma": sigma, "beta_prec": beta, "x_mu": x_mu,
            "x_sd": x_sd, "y_mu": y_mu, "y_sd": y_sd}


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

@dataclass
class Fleet:
    store: PosteriorStore
    services: List[PredictionService]
    experiments: Dict[str, object]      # workflow -> benchmarks Experiment


def phase_store(sizes: Sizes, seed: int) -> tuple:
    """Bind `sizes.tenants` namespaces over the five nf-core workflows."""
    def build(exps):
        store = PosteriorStore()
        services = []
        for i in range(sizes.tenants):
            w = WORKFLOWS[i % len(WORKFLOWS)]
            e = exps[w]
            online = OnlinePredictor(e.predictors["lotaru-g"],
                                     benches=e.benches)
            services.append(PredictionService(
                online, e.benches, store=store,
                tenant=f"tenant-{i:04d}", workflow=w))
        return Fleet(store, services, exps)

    exps, setup = _timed(lambda: {
        w: build_experiment(w, seed=seed, methods=("lotaru-g",))
        for w in WORKFLOWS})
    fleet, cold = _timed(lambda: build(exps))
    _, warm = _timed(lambda: build(exps))
    snap = fleet.store.snapshot()
    err = 0.0
    for svc in fleet.services:
        binding = fleet.store.binding(svc.tenant, svc.workflow)
        for task in svc.predictor.task_names():
            want = svc.predictor.export_posterior(task)
            got = snap.get(binding.key_str(task))
            for leaf, v in got.items():
                err = max(err, float(np.max(np.abs(
                    v - np.asarray(want[leaf], np.float64)))))
    n_ns = len(fleet.store.namespaces())
    return fleet, {"namespaces": n_ns, "rows": len(fleet.store),
                   "setup_s": setup, "cold_s": cold, "warm_s": warm,
                   "max_err": err, "tol": 0.0,
                   "ok": n_ns >= sizes.tenants and err == 0.0}


def _bursts(fleet: Fleet, rng) -> list:
    """Per namespace, one burst of queries over its workflow's task
    instances, local and on every target machine."""
    nodes = [None] + [n.name for n in TARGET_MACHINES]
    insts = {w: list(e.dag.tasks.values())
             for w, e in fleet.experiments.items()}
    out = []
    for svc in fleet.services:
        pool = insts[svc.workflow]
        qs = [PredictionQuery(pool[i].task_name, nodes[j],
                              pool[i].input_gb * f)
              for i, j, f in zip(
                  rng.integers(len(pool), size=QUERIES_PER_TENANT),
                  rng.integers(len(nodes), size=QUERIES_PER_TENANT),
                  rng.uniform(0.5, 2.0, size=QUERIES_PER_TENANT))]
        out.append((svc, qs))
    return out


def phase_predict(fleet: Fleet, sizes: Sizes, seed: int) -> dict:
    bursts = _bursts(fleet, np.random.default_rng(seed))
    frontend = AsyncPredictionFrontend(fleet.store, auto_flush=False)

    def one_round():
        futs = [None] * len(bursts)

        def caller(k):
            for j in range(k, len(bursts), CALLERS):
                svc, qs = bursts[j]
                futs[j] = frontend.predict_async(qs, svc.tenant,
                                                 svc.workflow)
        threads = [threading.Thread(target=caller, args=(k,))
                   for k in range(CALLERS)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60.0)
            if t.is_alive():
                raise RuntimeError("predict caller thread hung")
        d0 = frontend.dispatch_count
        frontend.flush()
        return [f.result(timeout=60.0) for f in futs], \
            frontend.dispatch_count - d0

    try:
        (outs, dispatches), cold = _timed(one_round)
        (outs, warm_dispatches), warm = _timed(one_round)
    finally:
        frontend.close()

    snap = fleet.store.snapshot()
    keys, xs, fs = [], [], []
    for svc, qs in bursts:
        binding = fleet.store.binding(svc.tenant, svc.workflow)
        keys += [binding.key_str(q.task) for q in qs]
        xs += [q.input_gb for q in qs]
        fs.append(binding.factors(qs))
    post = snap.gather(keys)
    x = np.asarray(xs, np.float64)
    f = np.concatenate(fs)
    mean, std = scale(*bayes.predict_blr_np(post, x), f)
    got = np.concatenate(outs)
    got_std = (got[:, 2] - got[:, 0]) / frontend.z
    err = max(_rel_err(got[:, 0], mean, post["y_sd"] * f),
              _rel_err(got_std, std))
    pallas = _lowers_to_pallas(
        functools.partial(ops._bayes_predict_jit, impl="auto"),
        jnp.asarray(pack_predict_planes(x, post)))
    n_q = len(x)
    return {"queries_per_dispatch": n_q, "callers": CALLERS,
            "dispatches": dispatches, "pallas": pallas,
            "cold_s": cold, "warm_s": warm, "max_err": err,
            "tol": PREDICT_TOL,
            "ok": (dispatches == 1 and warm_dispatches == 1
                   and n_q >= sizes.tenants * QUERIES_PER_TENANT
                   and err <= PREDICT_TOL)}


def _arm_refresh(fleet: Fleet, sizes: Sizes, rng, round_: int) -> None:
    """Feed REFRESH_EVERY local completions to every regression task of
    the first tenants, enough of them for `sizes.refresh_tasks` due
    tasks across `sizes.refresh_tenants` tenants."""
    n_tenants, n_tasks = 0, 0
    for svc in fleet.services:
        if (n_tasks >= sizes.refresh_tasks
                and n_tenants >= sizes.refresh_tenants):
            break
        online = svc.predictor
        tasks = [t for t, st in online.tasks.items() if st.nig is not None]
        n_tenants += 1
        n_tasks += len(tasks)
        e = fleet.experiments[svc.workflow]
        inputs = {}
        for inst in e.dag.tasks.values():
            inputs.setdefault(inst.task_name, []).append(inst.input_gb)
        comps = []
        for task in tasks:
            for k in range(REFRESH_EVERY):
                gb = float(rng.choice(inputs[task]) * rng.uniform(0.5, 2.0))
                uid = f"{svc.tenant}/{task}/{round_}/{k}"
                comps.append(TaskCompletion(
                    svc.workflow, uid, task, "local", gb,
                    e.gt.runtime(task, gb, LOCAL, uid)))
        online.observe_many(comps)


def phase_refresh(fleet: Fleet, sizes: Sizes, seed: int) -> dict:
    rng = np.random.default_rng(seed + 1)
    refresher = FleetRefresher(fleet.store,
                               RefreshPolicy(every_n=REFRESH_EVERY))
    _arm_refresh(fleet, sizes, rng, 0)
    cold_report, cold = _timed(refresher.refresh)
    _arm_refresh(fleet, sizes, rng, 1)
    due = refresher.due()
    bufs = []                       # the buffers this pass re-fits
    for b, task in due:
        (_, bx, by), = b.predictor.refresh_snapshot([task]).values()
        bufs.append((bx, by))
    report, warm = _timed(lambda: refresher.refresh(due))

    x, y, m = pad_ragged([b[0] for b in bufs], [b[1] for b in bufs])
    got = fit_stacked(x, y, m)
    ref = fit_blr_f64(x, y, m)
    leaves = ("mu", "sigma", "beta_prec", "x_mu", "x_sd", "y_mu", "y_sd")
    sel = m > 0
    pg = bayes.predict_blr_np({k: got[k][:, None] for k in leaves}, x)
    pr = bayes.predict_blr_np({k: ref[k][:, None] for k in leaves}, x)
    y_sd = np.broadcast_to(ref["y_sd"][:, None], x.shape)
    err = max(_rel_err(pg[0][sel], pr[0][sel], y_sd[sel]),
              _rel_err(pg[1][sel], pr[1][sel]))
    pallas = _lowers_to_pallas(bayes_fit_ragged, jnp.asarray(x),
                               jnp.asarray(y), jnp.asarray(m))
    ok = all(r.n_dispatches == 1 and r.n_stale == 0
             and r.n_tasks >= sizes.refresh_tasks
             and r.n_tenants >= sizes.refresh_tenants
             for r in (cold_report, report))
    ok = (ok and refresher.failure_count == 0
          and refresher.last_error is None and err <= FIT_TOL)
    return {"tasks": report.n_tasks, "tenants": report.n_tenants,
            "fit_shape": list(x.shape), "dispatches": report.n_dispatches,
            "pallas": pallas, "cold_s": cold, "warm_s": warm,
            "max_err": err, "tol": FIT_TOL, "ok": ok}


def phase_ingest(sizes: Sizes, seed: int) -> dict:
    """Batched ingest folds on the host in float64 by contract: the
    digest of every tenant's state equals the scalar observe chain."""
    stream = _stream(sizes.ingest_records, seed=seed)

    _, want = _fleet()
    for t, w, c in stream:
        want[(t, w)].observe(c)

    def batched(store, preds):
        bindings = {ns: store.binding(*ns) for ns in preds}
        for i in range(0, len(stream), INGEST_WINDOW):
            groups: Dict[tuple, list] = {}
            for t, w, c in stream[i:i + INGEST_WINDOW]:
                groups.setdefault((t, w), []).append(c)
            for ns, comps in groups.items():
                preds[ns].observe_many(comps)
            store.sync_bindings([bindings[ns] for ns in groups])
        return preds

    fleets = [_fleet(), _fleet()]
    cold_got, cold = _timed(lambda: batched(*fleets[0]))
    warm_got, warm = _timed(lambda: batched(*fleets[1]))
    mismatches = sum(state_digest(got[ns]) != state_digest(p)
                     for got in (cold_got, warm_got)
                     for ns, p in want.items())
    return {"device": {"platform": "host", "kind": "numpy float64"},
            "records": len(stream), "tenants": len(want),
            "cold_s": cold, "warm_s": warm, "max_err": float(mismatches),
            "tol": 0.0, "ok": mismatches == 0}


def _schedule_diff(got, want) -> tuple:
    """(tasks placed elsewhere or in another order, largest |start| or
    |finish| difference in seconds)."""
    moved = sum(got.assignment[u] != want.assignment[u]
                for u in want.assignment) + (got.order != want.order)
    err = max(max(abs(a - b) for a, b in zip(got.est[u], want.est[u]))
              for u in want.est)
    return moved, err


def phase_replan(sizes: Sizes, seed: int) -> dict:
    dag, nodes, svc = _build(sizes.replan_tasks, sizes.replan_nodes, seed)
    q = REPLAN_QUANTILES[0]
    plane = FusedPlane(svc, nodes, dag=dag)
    _, cold = _timed(lambda: plane.schedule(dag, quantile=q, engine="jit"))
    got, warm = _timed(lambda: plane.schedule(dag, quantile=q, engine="jit"))
    pairs = [(got, heft_schedule_matrix(dag, nodes, plane.matrix(),
                                        quantile=q))]

    reqs = [ReplanRequest(
        plane=FusedPlane(PredictionService(svc.predictor, svc.benches,
                                           store=svc.store,
                                           tenant=f"tenant-{i}",
                                           workflow="replan"),
                         nodes, dag=dag),
        dag=dag, quantile=qq) for i, qq in enumerate(REPLAN_QUANTILES)]
    _, many_cold = _timed(lambda: replan_many(reqs))
    scheds, many_warm = _timed(lambda: replan_many(reqs))
    pairs += [(s, heft_schedule_matrix(dag, nodes, r.plane.matrix(),
                                       quantile=r.quantile))
              for s, r in zip(scheds, reqs)]
    diffs = [_schedule_diff(g, w) for g, w in pairs]
    moved = sum(d[0] for d in diffs)
    err = max(d[1] for d in diffs)
    return {"tasks": len(dag.tasks), "nodes": len(nodes),
            "tenants": len(reqs),
            "sweep_device": sweep_device().platform,
            "sweep_dispatches": reqs[0].plane.stats.sweep_dispatches,
            "cold_s": cold, "warm_s": warm,
            "many_cold_s": many_cold, "many_warm_s": many_warm,
            "moved": moved, "max_err": err, "tol": 0.0,
            "ok": moved == 0 and err == 0.0}


def run(sizes: Sizes, seed: int, clock: CompileClock, emit=print) -> list:
    """Run every phase in order; emit one JSON line per phase and return
    the records.  A phase that raises ends the run."""
    records = []

    def phase(name, fn, *args):
        c0, h0 = clock.seconds, clock.cache_hits
        out = fn(*args)
        state, rec = out if isinstance(out, tuple) else (None, out)
        rec = {"phase": name, "device": _device(), **rec,
               "compile_s": clock.seconds - c0,
               "cache_hits": clock.cache_hits - h0}
        if PLATFORM == "tpu" and "pallas" in rec:
            rec["ok"] = rec["ok"] and rec["pallas"]
        records.append(rec)
        emit(json.dumps(rec))
        return state

    fleet = phase("store", phase_store, sizes, seed)
    phase("predict", phase_predict, fleet, sizes, seed)
    phase("refresh", phase_refresh, fleet, sizes, seed)
    phase("ingest", phase_ingest, sizes, seed)
    phase("replan", phase_replan, sizes, seed)
    return records


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()
    backend = jax.default_backend()
    if backend != PLATFORM:
        sys.exit(f"chip_smoke: no TPU found: JAX's default backend is "
                 f"{backend!r}, and this smoke runs only on a TPU")
    clock = CompileClock()
    try:
        records = run(Sizes(), args.seed, clock)
    finally:
        clock.close()
    failed = [r["phase"] for r in records if not r["ok"]]
    if failed:
        sys.exit(f"chip_smoke: phases failed: {', '.join(failed)}")
    d = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
