"""Benchmark: the distributed serving plane vs the single-process frontend.

Phases:
  1. baseline — the canonical `service_throughput` single-process async
     serving path (its exact configuration: 16 caller threads against
     one `AsyncPredictionFrontend`), measured fresh in this run on this
     machine; plus an in-process "ceiling" row — the same multi-tenant
     fleet driven by direct in-process `predict_async` calls with zero
     wire cost — reported for context, not claimed against.  The gap
     between the two is the point: caller threads sharing the serving
     process steal core time from compute (GIL + window stalls), while
     the sharded tier moves callers into separate processes so the
     serving core runs decode+compute only;
  2. spawn N real shard processes (`repro.serve.shard`) behind a
     consistent-hash map, each with its own store slice, frontend,
     oplog, and checkpoint directory;
  3. drive them with K client *processes* (one event loop each — a
     single client process bottlenecks on wire serialization long before
     the shards saturate), each running concurrent `predict_many`
     fan-out workers (one coalesced RPC per shard per round) over a
     fixed wall-clock window — aggregate predictions/sec, per-round
     p50/p99 latency;
  4. failover drill under load: observe a stream of acked completions,
     checkpoint, observe more, SIGKILL the owning shard mid-load, warm
     failover (restore checkpoint + replay oplog tail), readmit via
     `ShardMap.with_address`, and verify the restored posterior digest
     is bit-identical with zero lost acknowledged observations.

The throughput claim is hardware-aware.  On a multi-core host the shard
processes add real compute capacity, and the tier must beat the
single-process baseline outright (speedup > 1).  On a single-core host
(CI containers) every process timeshares one core, so a multi-process
tier can never exceed an in-process baseline — the total per-query work
is a strict superset — and the honest bound is per-core serving
efficiency: the sharded tier must hold > 50% of the same-fleet
in-process ceiling while paying for real sockets, serialization, and
process isolation (and must still beat the committed single-process
async snapshot rate).  The claim line states which bound was applied.
The default fleet size is hardware-aware too: 2 shards x 2 client
processes on hosts with < 4 cores (more processes on one core only add
context-switch overhead), 3 x 3 with 4+ cores.

  PYTHONPATH=src python -m benchmarks.distributed_serving [--smoke]
"""
from __future__ import annotations

import asyncio
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time
from concurrent.futures import ThreadPoolExecutor
from typing import List, Tuple

import numpy as np

from benchmarks.common import fmt_table
from repro.core.microbench import simulate_microbench
from repro.core.predictor import LotaruPredictor
from repro.core.traces import TraceRow
from repro.online import OnlinePredictor, TaskCompletion
from repro.sched.cluster import LOCAL, TARGET_MACHINES
from repro.store import AsyncPredictionFrontend, PosteriorStore

TENANTS: List[Tuple[str, str]] = [
    (f"tenant{i:02d}", wf) for i, wf in enumerate(
        ["rnaseq", "atacseq", "chipseq", "mag", "eager", "ampliseq"])]
TASKS = ("bwa", "idx", "sort", "dedup")

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _make_predictor(salt: int = 0) -> OnlinePredictor:
    lot = LotaruPredictor("G", local_bench=simulate_microbench(LOCAL, 1))
    traces = []
    for j, t in enumerate(TASKS):
        traces += [TraceRow("wf", t, "local", s,
                            2.0 + j + (20.0 + 7 * j + salt) * s)
                   for s in np.linspace(0.05, 0.4, 6)]
    return OnlinePredictor(lot.fit(traces))


def _benches():
    return {n.name: simulate_microbench(n, 1) for n in TARGET_MACHINES}


def bootstrap(shard_id, shard_map):
    """Shard child entry point (`benchmarks.distributed_serving:bootstrap`):
    deterministic rebuild of the whole fleet; the shard keeps what the
    map places on it."""
    benches = _benches()
    return {(t, w): (_make_predictor(salt=i), benches)
            for i, (t, w) in enumerate(TENANTS)}


def _queries(rng, n) -> List[tuple]:
    nodes = [None] + [m.name for m in TARGET_MACHINES]
    return [(TASKS[int(rng.integers(0, len(TASKS)))],
             nodes[int(rng.integers(0, len(nodes)))],
             float(rng.uniform(0.05, 12.0))) for _ in range(n)]


# ---- phase 1: single-process async baseline ---------------------------------
class _Q:
    __slots__ = ("task", "node", "input_gb")

    def __init__(self, t, n, gb):
        self.task, self.node, self.input_gb = t, n, gb


def _canonical_async_qps(seed: int) -> float:
    """The committed `service_throughput` async baseline, re-measured on
    this machine in this run (same config the snapshot was taken with)."""
    from benchmarks.service_throughput import run as st_run
    return float(st_run(seed=seed, quiet=True)["async_qps"])


def _inproc_ceiling_qps(queries_per_tenant: int, n_callers: int,
                        repeats: int, seed: int) -> float:
    rng = np.random.default_rng(seed)
    store = PosteriorStore()
    benches = _benches()
    for i, (t, w) in enumerate(TENANTS):
        store.bind(t, w, _make_predictor(salt=i), benches)
    chunks = [(t, w, [_Q(*q) for q in _queries(rng, queries_per_tenant)])
              for t, w in TENANTS]
    with AsyncPredictionFrontend(store, window_s=0.002) as fe:
        fe.predict(chunks[0][2][:8], *TENANTS[0])              # warm
        t0 = time.perf_counter()
        with ThreadPoolExecutor(max_workers=n_callers) as pool:
            for _ in range(repeats):
                futs = list(pool.map(
                    lambda c: fe.predict_async(c[2], c[0], c[1]), chunks))
                for f in futs:
                    f.result(timeout=120)
        dt = time.perf_counter() - t0
    return repeats * queries_per_tenant * len(TENANTS) / dt


# ---- client driver (also the --client-worker subprocess entry) ---------------
async def _client_load(map_wire: dict, duration_s: float,
                       queries_per_tenant: int, n_workers: int,
                       seed: int, start_at: float = 0.0) -> dict:
    from repro.serve import ServingClient, ShardMap
    client = ServingClient(ShardMap.from_wire(map_wire))
    lat: List[float] = []
    stats = {"q": 0, "errors": 0}
    if start_at:
        await asyncio.sleep(max(0.0, start_at - time.time()))
    t_end = time.perf_counter() + duration_s
    t0 = time.perf_counter()

    async def worker(wid: int) -> None:
        # pregenerated rotating batches: the baseline phase serves
        # pregenerated queries too — generation cost must not be billed
        # to either tier
        wrng = np.random.default_rng(seed + wid)
        variants = [[(t, w, _queries(wrng, queries_per_tenant))
                     for t, w in TENANTS] for _ in range(4)]
        n = 0
        while time.perf_counter() < t_end:
            batch = variants[n % len(variants)]
            n += 1
            r0 = time.perf_counter()
            try:
                outs = await client.predict_many(batch)
            except (ConnectionError, OSError, RuntimeError):
                stats["errors"] += 1
                continue
            lat.append(time.perf_counter() - r0)
            stats["q"] += sum(len(o) for o in outs)

    await asyncio.gather(*[worker(i) for i in range(n_workers)])
    elapsed = time.perf_counter() - t0
    await client.close()
    return {"q": stats["q"], "errors": stats["errors"],
            "elapsed_s": elapsed, "lat": lat}


def _spawn_client_procs(n_procs: int, map_wire: dict, duration_s: float,
                        queries_per_tenant: int, n_workers: int,
                        seed: int) -> List[dict]:
    start_at = time.time() + 20.0          # let every proc finish importing
    procs = []
    for i in range(n_procs):
        args = {"map": map_wire, "duration_s": duration_s,
                "queries_per_tenant": queries_per_tenant,
                "n_workers": n_workers, "seed": seed + 1000 * (i + 1),
                "start_at": start_at}
        env = dict(os.environ)
        env["PYTHONPATH"] = os.path.join(_REPO_ROOT, "src") + os.pathsep \
            + env.get("PYTHONPATH", "")
        env["JAX_PLATFORMS"] = "cpu"    # the parent may hold the chip
        procs.append(subprocess.Popen(
            [sys.executable, "-m", "benchmarks.distributed_serving",
             "--client-worker", json.dumps(args)],
            cwd=_REPO_ROOT, env=env, stdout=subprocess.PIPE, text=True))
    results = []
    for p in procs:
        out, _ = p.communicate(timeout=600)
        if p.returncode != 0:
            raise RuntimeError(f"client driver failed (rc={p.returncode})")
        results.append(json.loads(out.strip().splitlines()[-1]))
    return results


# ---- phases 2-4 --------------------------------------------------------------
async def _drive(n_shards: int, n_client_procs: int, duration_s: float,
                 queries_per_tenant: int, n_workers: int, seed: int) -> dict:
    from repro.serve import (ServingClient, ShardInfo, ShardMap, ShardSpec,
                             ShardSupervisor)
    rng = np.random.default_rng(seed + 1)
    tmp = tempfile.mkdtemp(prefix="dist_serving_")
    shard_ids = [f"s{i}" for i in range(n_shards)]
    m = ShardMap([ShardInfo(s, "127.0.0.1", 0) for s in shard_ids])
    out: dict = {"n_shards": n_shards, "n_client_procs": n_client_procs}
    sup = ShardSupervisor(repo_root=_REPO_ROOT, ready_timeout_s=300)
    try:
        t0 = time.perf_counter()
        for sid in shard_ids:
            spec = ShardSpec(sid, "benchmarks.distributed_serving:bootstrap",
                             os.path.join(tmp, sid + "_ckpt"),
                             os.path.join(tmp, sid + ".oplog"),
                             extra_args=["--window-s", "0.001"])
            port = sup.start(spec, json.dumps(m.to_wire()))
            m = m.with_address(sid, "127.0.0.1", port)
        out["spawn_s"] = time.perf_counter() - t0
        client = ServingClient(m)
        await client.update_maps()
        await client.predict_many(
            [(t, w, _queries(rng, 8)) for t, w in TENANTS])       # warm

        # phase 3: K client processes, fixed wall-clock window
        loop = asyncio.get_running_loop()
        results = await loop.run_in_executor(
            None, _spawn_client_procs, n_client_procs, m.to_wire(),
            duration_s, queries_per_tenant, n_workers, seed)
        all_lat = sorted(x for r in results for x in r["lat"])
        out.update(
            dist_qps=sum(r["q"] / r["elapsed_s"] for r in results),
            client_errors=sum(r["errors"] for r in results),
            p50_ms=float(np.percentile(all_lat, 50) * 1e3),
            p99_ms=float(np.percentile(all_lat, 99) * 1e3),
            rounds=len(all_lat))

        # phase 4: failover drill under load
        t, w = TENANTS[0]
        victim = m.shard_for(f"{t}/{w}")
        acked = []
        for i in range(24):
            acked.append(await client.observe(TaskCompletion(
                w, f"u{i}", TASKS[i % len(TASKS)], "local",
                1.0 + i * 0.3, 10.0 + 25.0 * (1.0 + i * 0.3)), t, w))
            if i == 11:
                await client.checkpoint(victim)    # later acks live only
        digest_before = await client.digest(t, w)  # in the oplog tail

        survivors = [(t2, w2) for t2, w2 in TENANTS
                     if m.shard_for(f"{t2}/{w2}") != victim]
        outage = {"ok": 0, "failed": 0}
        stop_load = asyncio.Event()

        async def outage_load() -> None:
            wrng = np.random.default_rng(seed + 99)
            batch = [(t2, w2, _queries(wrng, 32)) for t2, w2 in survivors]
            while not stop_load.is_set():
                try:
                    await client.predict_many(batch)
                    outage["ok"] += 1
                except (ConnectionError, OSError, RuntimeError):
                    outage["failed"] += 1
                await asyncio.sleep(0)

        loader = asyncio.ensure_future(outage_load())
        sup.kill(victim)
        t0 = time.perf_counter()
        port = await asyncio.get_running_loop().run_in_executor(
            None, sup.failover, victim, json.dumps(m.to_wire()))
        m = m.with_address(victim, "127.0.0.1", port)
        client.set_map(m)
        await client.update_maps()
        digest_after = await client.digest(t, w)
        recovery_s = time.perf_counter() - t0
        health = await client.health(victim)
        stop_load.set()
        await loader
        out.update(recovery_s=recovery_s,
                   digest_identical=digest_before == digest_after,
                   acked_observations=len(acked),
                   recovered_seq=int(health["seq"]),
                   lost_acked=int(acked[-1]) - int(health["seq"]),
                   surviving_rounds_during_outage=outage["ok"])
        await client.close()
    finally:
        sup.stop_all()
        shutil.rmtree(tmp, ignore_errors=True)
    return out


def run(n_shards: int | None = None, n_client_procs: int | None = None,
        duration_s: float = 8.0, queries_per_tenant: int = 256,
        n_callers: int = 4, repeats: int = 6, seed: int = 0,
        quiet: bool = False) -> dict:
    # Process counts scale with the host: every extra process on a
    # single-core machine only adds context-switch overhead, so the
    # fleet stays at the 2-shard minimum there and grows with cores.
    ncpu = os.cpu_count() or 1
    if n_shards is None:
        n_shards = 3 if ncpu >= 4 else 2
    if n_client_procs is None:
        n_client_procs = 3 if ncpu >= 4 else 2
    # queries_per_tenant fixes the per-round batch size for BOTH the
    # in-process ceiling and the distributed clients — the efficiency
    # ratio is only meaningful when the two serve identical rounds
    # (in-process dispatch overhead amortizes with batch size; wire
    # serialization is per-query and does not).
    baseline_qps = _canonical_async_qps(seed)
    ceiling_qps = _inproc_ceiling_qps(queries_per_tenant, n_callers,
                                      repeats, seed)
    dist = asyncio.run(_drive(n_shards, n_client_procs, duration_s,
                              queries_per_tenant, n_callers, seed))
    out = {"cpu_count": os.cpu_count() or 1,
           "baseline_async_qps": baseline_qps,
           "inproc_ceiling_qps": ceiling_qps, **dist,
           "speedup": dist["dist_qps"] / baseline_qps,
           "wire_efficiency": dist["dist_qps"] / ceiling_qps}
    if not quiet:
        rows = [["service_throughput async (baseline)",
                 f"{baseline_qps:,.0f}", "-", "-"],
                ["in-process frontend (no wire, ceiling)",
                 f"{ceiling_qps:,.0f}", "-", "-"],
                [f"{n_shards} shards x {dist['n_client_procs']} clients",
                 f"{out['dist_qps']:,.0f}",
                 f"{out['p50_ms']:.1f}", f"{out['p99_ms']:.1f}"]]
        print(fmt_table(["serving tier", "predictions/s", "p50 ms",
                         "p99 ms"],
                        rows, "Distributed serving throughput"))
    snap_path = os.path.join(_REPO_ROOT, "results", "bench",
                             "service_throughput.json")
    snap_qps = None
    if os.path.exists(snap_path):
        with open(snap_path) as f:
            snap_qps = json.load(f).get("async_qps")
    out["committed_async_qps"] = snap_qps
    multicore = out["cpu_count"] >= 2
    ok_tp = (out["speedup"] > 1.0 if multicore
             else out["wire_efficiency"] > 0.5
             and (snap_qps is None or out["dist_qps"] > snap_qps))
    out["throughput_bound"] = ("multicore_speedup" if multicore
                               else "single_core_efficiency")
    out["throughput_ok"] = bool(ok_tp)
    if not quiet:
        ok = (ok_tp and out["digest_identical"]
              and out["lost_acked"] == 0)
        bound = (f"{out['speedup']:.2f}x the fresh single-process "
                 f"service_throughput async rate"
                 if multicore else
                 f"{out['wire_efficiency']:.0%} of the in-process "
                 f"same-fleet ceiling on a single-core host (the "
                 f"multi-core speedup bound needs >1 core; the tier "
                 f"pays real sockets + serialization for isolation)")
        print(f"\n[claim] {n_shards} shards sustain {bound}; "
              f"failover recovered in {out['recovery_s']:.2f}s with a "
              f"bit-identical posterior digest and "
              f"{out['lost_acked']} lost acked observations "
              f"({out['surviving_rounds_during_outage']} surviving-shard "
              f"rounds served during the outage) -> "
              f"{'PASS' if ok else 'FAIL'}")
    return out


def _client_worker_main(arg: str) -> None:
    a = json.loads(arg)
    res = asyncio.run(_client_load(a["map"], a["duration_s"],
                                   a["queries_per_tenant"], a["n_workers"],
                                   a["seed"], a.get("start_at", 0.0)))
    print(json.dumps(res))


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized: 2 shards, short load")
    ap.add_argument("--client-worker", default=None, help=argparse.SUPPRESS)
    a = ap.parse_args()
    if a.client_worker:
        _client_worker_main(a.client_worker)
    elif a.smoke:
        run(n_shards=2, n_client_procs=2, duration_s=4.0,
            queries_per_tenant=256, n_callers=4, repeats=3)
    else:
        run()
