"""Fused decision plane benchmark: what a warm fleet-scale replan round
costs on the host CPU once posterior rows and the cost view stay resident
and the candidate-EFT sweep is one jitted dispatch.

Two measurements:

  * fused — `FusedPlane.schedule` on one 1000-task x 100-node planning
    problem: dirty-row sync only, and the whole insertion sweep as ONE
    jitted float64 dispatch (`kernels.decision_plane.eft_sweep`);
  * megabatch — `replan_many` over B tenants sharing one cluster: one
    coalesced predictive dispatch + one vmapped sweep for the whole
    batch (per-replan cost = batch / B), against B single replans.

At these sizes the scalar reference takes minutes, so the jit sweep is
held to the NumPy sweep instead: both schedules must be bit-identical,
asserted before anything is timed.  All times are host wall-clock on the
machine that runs this script, not device times.

  PYTHONPATH=src python -m benchmarks.fused_plane
"""
from __future__ import annotations

import time

from benchmarks.replan_latency import (_build, numpy_sweep_schedule,
                                       same_schedule)
from repro.sched.fused import FusedPlane, ReplanRequest, replan_many


def _timed(fn) -> float:
    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0


def run(n_tasks: int = 1000, n_nodes: int = 100, seed: int = 0,
        repeats: int = 5, quantile: float = 0.95,
        batch: int = 6, batch_tasks: int = 300, batch_nodes: int = 30,
        quiet: bool = False) -> dict:
    dag, nodes, svc = _build(n_tasks, n_nodes, seed)
    plane = FusedPlane(svc, nodes, dag=dag)

    def fused_round():
        return plane.schedule(dag, quantile=quantile)

    # correctness before speed: the jit sweep must equal the NumPy sweep
    got = fused_round()                      # also compiles the sweep
    parity = same_schedule(got, numpy_sweep_schedule(
        dag, nodes, plane.matrix(), quantile=quantile))
    assert parity, "jit sweep diverged from the NumPy sweep"
    fused_s = min(_timed(fused_round) for _ in range(repeats))

    # megabatch: B tenants replanning one cluster in one dispatch pair
    bdag, bnodes, bsvc = _build(batch_tasks, batch_nodes, seed + 1)
    planes = [FusedPlane(bsvc, bnodes, dag=bdag) for _ in range(batch)]
    reqs = [ReplanRequest(plane=p, dag=bdag, quantile=quantile)
            for p in planes]
    replan_many(reqs)                        # warm + compile
    mega_s = min(_timed(lambda: replan_many(reqs)) for _ in range(repeats))
    bwant = numpy_sweep_schedule(bdag, bnodes, planes[0].matrix(),
                                 quantile=quantile)
    mega_parity = all(same_schedule(s, bwant) for s in replan_many(reqs))
    assert mega_parity, "megabatched replan diverged from the NumPy sweep"
    single_s = min(_timed(lambda: planes[0].schedule(bdag,
                                                     quantile=quantile))
                   for _ in range(repeats))

    out = {
        "n_tasks": n_tasks, "n_nodes": n_nodes, "quantile": quantile,
        "fused_s": fused_s, "bit_parity": parity,
        "megabatch": {
            "batch": batch, "n_tasks": batch_tasks, "n_nodes": batch_nodes,
            "batch_s": mega_s, "per_replan_s": mega_s / batch,
            "single_replan_s": single_s,
            "batch_speedup": single_s * batch / mega_s,
            "bit_parity": mega_parity,
            "predict_dispatches": planes[0].stats.predict_dispatches,
            "sweep_dispatches": planes[0].stats.sweep_dispatches,
        },
        "plane_stats": vars(plane.stats),
    }
    if not quiet:
        print(f"Warm replan round ({n_tasks} tasks x {n_nodes} nodes, "
              f"q={quantile}), host wall-clock: {fused_s * 1e3:.2f} ms")
        print(f"Megabatch ({batch} x {batch_tasks}x{batch_nodes}), host "
              f"wall-clock: {mega_s * 1e3:.2f} ms batch, "
              f"{mega_s / batch * 1e3:.2f} ms/replan "
              f"(single {single_s * 1e3:.2f} ms -> "
              f"{single_s * batch / mega_s:.1f}x)")
        print(f"[claim] bit-identical schedules (jit vs NumPy sweep) -> "
              f"{'PASS' if parity and mega_parity else 'FAIL'}")
    return out


if __name__ == "__main__":
    run()
