"""Benchmark orchestrator: one module per paper table/figure + roofline.

  PYTHONPATH=src python -m benchmarks.run [--full] [--only NAME]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale sizes (200 scheduling clusters)")
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of benchmark names")
    ap.add_argument("--out", default="results/bench")
    args = ap.parse_args(argv)
    os.makedirs(args.out, exist_ok=True)

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    from benchmarks import (carbon, cost, distributed_serving, fused_plane,
                            ingest_throughput, online_adaptation,
                            prediction_error, profiling_time,
                            refresh_overhead, replan_latency,
                            resharding_drill, roofline_report,
                            scheduling_makespan, service_throughput,
                            straggler_mitigation)
    jobs = {
        "prediction_error": lambda: prediction_error.run(),
        "profiling_time": lambda: profiling_time.run(),
        "scheduling_makespan": lambda: scheduling_makespan.run(
            n_clusters=200 if args.full else 60),
        "carbon": lambda: carbon.run(),
        "cost": lambda: cost.run(),
        "online_adaptation": lambda: online_adaptation.run(),
        "service_throughput": lambda: service_throughput.run(),
        "straggler_mitigation": lambda: straggler_mitigation.run(),
        "replan_latency": lambda: replan_latency.run(),
        "fused_plane": lambda: fused_plane.run(),
        "ingest_throughput": lambda: ingest_throughput.run(
            n_records=2000 if not args.full else 8000),
        "refresh_overhead": lambda: refresh_overhead.run(),
        "roofline": lambda: roofline_report.run(),
        "distributed_serving": lambda: distributed_serving.run()
        if args.full else distributed_serving.run(
            n_shards=2, n_client_procs=2, duration_s=4.0,
            queries_per_tenant=256, n_callers=4, repeats=3),
        "resharding_drill": lambda: resharding_drill.run(
            duration_s=9.0 if args.full else 4.5),
    }
    full_only = {"straggler_mitigation"}
    only = set(args.only.split(",")) if args.only else None
    if only and only - set(jobs):
        ap.error(f"unknown benchmark(s) {sorted(only - set(jobs))}; "
                 f"known: {sorted(jobs)}")
    failures = 0
    for name, fn in jobs.items():
        if only and name not in only:
            continue
        if not args.only and not args.full and name in full_only:
            continue
        print("=" * 78)
        print(f"== {name}")
        print("=" * 78)
        t0 = time.time()
        try:
            res = fn()
            with open(os.path.join(args.out, f"{name}.json"), "w") as f:
                json.dump(res, f, indent=1, default=str)
            print(f"[{name}] done in {time.time()-t0:.1f}s\n")
        except Exception as e:
            failures += 1
            import traceback
            traceback.print_exc()
            print(f"[{name}] FAILED\n")
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
