#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip this process finds.

  python bench/run.py --workload <config>.<traffic> --seed N \\
      --seconds S --trace 0|1

The cell `<config>.<traffic>` resolves by name: `bench/configs/<config>.json`
holds the deployment (sizes, guarantees, correctness limits),
`bench/traffic/<traffic>.json` the mix, whose `driver` names the generator
in `bench/drivers/`.  With `--trace 1` every per-layer metric of the cell is
read by `bench/layers/<metric>.py`.  So a configuration, a mix or a metric
is added as new files plus entries in BENCHMARK.json.

One process: it builds its state from the seed, warms every shape the
window reaches (set-up), measures for `--seconds`, checks a sample of what
the window produced against the plain float64 reference, and prints the
result as the last line of stdout.  Without a TPU, or with fewer chips than
the cell asks for, it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

from bench import common  # noqa: E402


def load_cell(name: str) -> dict:
    """The cell's entry, configuration and mix, resolved by name."""
    spec = common.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = next((w for w in spec["workloads"] if w["name"] == name), None)
    if cell is None:
        raise SystemExit(f"bench: no workload {name!r} in BENCHMARK.json")
    conf = next(c for c in spec["configs"] if c["name"] == cell["config"])
    cfg = common.load_json(os.path.join(ROOT, conf["file"]))
    traffic = common.load_json(os.path.join(
        common.BENCH, "traffic", cell["traffic"] + ".json"))
    e2e = [m for m in spec["end_to_end"]
           if name in m.get("workloads", [name])]
    layers = [m for m in spec["per_layer"]
              if name in m.get("workloads", [name])]
    return {"spec": spec, "cell": cell, "cfg": cfg, "traffic": traffic,
            "end_to_end": e2e, "per_layer": layers}


def driver(traffic: dict):
    return importlib.import_module(f"bench.drivers.{traffic['driver']}")


def layer_reader(metric: str):
    path = os.path.join(common.BENCH, "layers", metric + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_layer_" + metric.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def configure_jax(cfg: dict) -> None:
    """Persistent compile cache in the checkout (or where
    JAX_COMPILATION_CACHE_DIR says), and the precision the configuration
    states: float32 matmuls on the TPU run as float32 only at 'highest'."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    if cfg.get("device_precision") == "float32":
        jax.config.update("jax_default_matmul_precision", "highest")


def run_cell(name: str, seed: int, seconds: float, trace: bool,
             chips_check=common.require_chips, out=None, err=None,
             t_start: float = T_START) -> dict:
    """One run of one cell; returns the result line's fields."""
    c = load_cell(name)
    cfg, traffic, cell = c["cfg"], c["traffic"], c["cell"]
    configure_jax(cfg)
    devs = chips_check(cell["chips"])
    drv = driver(traffic)
    clock = common.CompileClock()
    spans = common.Spans(annotate=trace)
    try:
        state = drv.setup(cfg, traffic, seed, spans)
        compiles0, hits0 = clock.compiles, clock.cache_hits
        tracer = None
        if trace:
            from bench import trace as tr
            tracer = tr.Tracer(os.path.join(common.OUT_DIR, "trace", name))
            tracer.start()
        import jax
        jax.config.update("jax_log_compiles", True)   # none expected
        try:
            drv.window(state, seconds)
        finally:
            jax.config.update("jax_log_compiles", False)
        if tracer is not None:
            tracer.stop()
        setup_s = state.t0 - t_start
        window_compiles = clock.compiles - compiles0
        window_hits = clock.cache_hits - hits0
    finally:
        clock.close()
    (err or sys.stderr).write(
        f"bench: setup_s={setup_s:.3f} compiles_in_window={window_compiles} "
        f"cache_fetches_in_window={window_hits}\n")
    device = common.device_record(devs)
    ctx = {"cell": cell, "cfg": cfg, "traffic": traffic, "seconds": seconds,
           "device_kind": device["kind"],
           "window_s": state.t1 - state.t0, "spans": spans,
           "counters": drv.counters(state),
           "compiles_in_window": window_compiles}
    metrics, breakdown = {}, None
    if trace:
        reduced = tracer.reduce(state.t0, state.t1)
        ctx["trace"] = reduced
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        breakdown = reduced.breakdown()
        for m in c["per_layer"]:
            v = layer_reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = common.metric(v, m["unit"])
        tracer.discard()
    else:
        values = drv.end_to_end(state, seconds)
        values["setup_s"] = setup_s
        for m in c["end_to_end"]:
            if m["name"] in values:
                metrics[m["name"]] = common.metric(values[m["name"]],
                                                   m["unit"])
    drv.release(state)
    checks = drv.verify(state, cfg)
    n = drv.counts(state)
    result = {"correct": all(ch["ok"] for ch in checks), **n,
              "metrics": metrics, "device": device, "checks": checks,
              "breakdown": breakdown}
    common.emit_result(result["correct"], n["attempted"], n["failed"],
                       metrics, device, checks, breakdown, out=out, err=err)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        run_cell(args.workload, args.seed, args.seconds, bool(args.trace))
    except common.NoChip as e:
        sys.stderr.write(f"bench: {e}\n")
        return 3
    return 0


if __name__ == "__main__":
    sys.exit(main())
