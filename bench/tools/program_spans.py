#!/usr/bin/env python3
"""Readings of the program's own spans and counters (`repro.obs`), and what
the tracer costs when it is on, for one cell, in one process.

  python bench/tools/program_spans.py --workload <cell> --seconds S \\
      [--pairs P] [--trace 0|1] SEED [SEED ...]

For each seed, `2 P` runs of the cell, each set up anew from the seed
and checked as the benchmark checks it, the program's tracer off and on
(`obs.enable(annotate=False)`) in turn, off first in even pairs and on
first in odd ones; with `--trace 1` a last run whose window is profiled
with the tracer annotating it.  One JSON line per run: the cell's
end-to-end number and `correct`, and with the tracer on, the per-layer
readings below, the mean of every program span and counter, and the
coverage of the benchmark's own span by the program's.  A profiled run
adds the device's idle share and its idle gaps, each named by the
innermost benchmark or program span (`lotaru.*`) the host was in at the
gap's midpoint.  The runs share one process, so later set-ups find their
programs compiled.  Without a TPU it exits non-zero.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

from bench import common  # noqa: E402
from bench import run as harness  # noqa: E402


def _mean_ms(p: dict, name: str, field: str = "total_s",
             per: str = None):
    """Mean of span `name` in ms, per call of itself or of span `per`."""
    sp = p.get("spans", {})
    s = sp.get(name)
    n = sp.get(per or name, {}).get("count", 0)
    if s is None or not n:
        return None
    return 1e3 * s[field] / n


def _h2d_per_dispatch(p: dict):
    n = p.get("spans", {}).get("lotaru.compute.predict", {}).get("count", 0)
    b = p.get("counters", {}).get("lotaru.compute.h2d_bytes")
    return b / n if n and b is not None else None


def _fit_pad_share(p: dict):
    c = p.get("counters", {})
    cells, points = c.get("lotaru.refresh.fit_cells"), c.get(
        "lotaru.refresh.fit_points")
    if not cells or points is None:
        return None
    return 100.0 * (1.0 - points / cells)


# metric -> (reading of a tracer snapshot, unit); a `.serial` metric belongs
# to the plan-serial cell, a `.refresh` metric to refresh-fleet
METRICS = {
    "frontend_queue_ms.serial": (
        lambda p: _mean_ms(p, "lotaru.frontend.queue"), "ms"),
    "frontend_host_ms.serial": (
        lambda p: _mean_ms(p, "lotaru.frontend.flush", "self_s"), "ms"),
    "store_gather_ms.serial": (
        lambda p: _mean_ms(p, "lotaru.store.gather"), "ms"),
    "predict_call_ms.serial": (
        lambda p: _mean_ms(p, "lotaru.compute.predict"), "ms"),
    "h2d_bytes_per_dispatch.serial": (_h2d_per_dispatch, "bytes"),
    "refresh_due_ms.refresh": (
        lambda p: _mean_ms(p, "lotaru.refresh.due"), "ms"),
    "refresh_prepare_ms.refresh": (
        lambda p: _mean_ms(p, "lotaru.refresh.prepare",
                           per="lotaru.refresh.pass"), "ms"),
    "refresh_fit_ms.refresh": (
        lambda p: _mean_ms(p, "lotaru.refresh.fit",
                           per="lotaru.refresh.pass"), "ms"),
    "refresh_apply_ms.refresh": (
        lambda p: _mean_ms(p, "lotaru.refresh.apply",
                           per="lotaru.refresh.pass"), "ms"),
    "fit_pad_share.refresh": (_fit_pad_share, "%"),
}


def readings(p: dict) -> dict:
    """Every per-layer reading the snapshot `p` holds (None where the
    program has no such span or counter)."""
    out = {}
    for name, (read, _unit) in METRICS.items():
        v = read(p)
        if v is not None:
            out[name] = v
    return out


def coverage(p: dict, spans) -> dict:
    """The program's spans against the benchmark's own around the same
    calls: plan-serial's queue wait plus flush over the mean `plan.round`;
    refresh-fleet's due, prepare, fit and apply over the mean
    `refresh.pass` (which holds one `due()` and one `refresh()`)."""
    def bench_ms(name):
        n = spans.count.get(name, 0)
        return 1e3 * spans.total[name] / n if n else None

    out = {}
    rnd = bench_ms("plan.round")
    q, f = _mean_ms(p, "lotaru.frontend.queue"), _mean_ms(
        p, "lotaru.frontend.flush")
    if rnd and q is not None and f is not None:
        out["plan.round_ms"] = rnd
        out["plan_coverage"] = (q + f) / rnd
    ps = bench_ms("refresh.pass")
    parts = [_mean_ms(p, "lotaru.refresh.due")] + [
        _mean_ms(p, f"lotaru.refresh.{k}", per="lotaru.refresh.pass")
        for k in ("prepare", "fit", "apply")]
    if ps and None not in parts:
        out["refresh.pass_ms"] = ps
        out["refresh_coverage"] = sum(parts) / ps
    return out


def span_means(p: dict) -> dict:
    """name -> [count, mean total ms, mean self ms] for every span."""
    return {k: [v["count"], 1e3 * v["total_s"] / v["count"],
                1e3 * v["self_s"] / v["count"]]
            for k, v in sorted(p.get("spans", {}).items()) if v["count"]}


def idle_gaps(path: str, t0_ns: float, t1_ns: float) -> dict:
    """Idle seconds of the first chip in [t0_ns, t1_ns] by the innermost
    benchmark or program span open on the host at each gap's midpoint."""
    from jax.profiler import ProfileData

    from bench import trace as tr
    busy, spans = None, []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:TPU:") and busy is None:
            lines = {ln.name: ln for ln in plane.lines}
            mod = lines.get("XLA Modules") or lines.get("XLA Ops")
            iv = [] if mod is None else [
                (e.start_ns, e.start_ns + e.duration_ns) for e in mod.events]
            busy = tr._union(tr._clip(iv, t0_ns, t1_ns))
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                spans.extend(
                    (e.start_ns, e.start_ns + e.duration_ns, e.name)
                    for e in ln.events
                    if e.name != tr.WINDOW_SPAN and (
                        e.name.startswith("lotaru.") or tr._is_span(e.name)))
    spans.sort()
    gaps, active, i = {}, [], 0
    for a, b in tr._gaps(busy or [], t0_ns, t1_ns):   # in time order
        mid = 0.5 * (a + b)
        while i < len(spans) and spans[i][0] <= mid:
            active.append(spans[i])
            i += 1
        active = [s for s in active if s[1] > mid]
        name = (min(active, key=lambda s: s[1] - s[0])[2] if active
                else "(no span)")
        gaps[name] = gaps.get(name, 0.0) + (b - a) * 1e-9
    return dict(sorted(gaps.items(), key=lambda kv: -kv[1]))


def window(name: str, c: dict, drv, seed: int, seconds: float,
           mode: str) -> dict:
    """One run of the cell, set up anew from `seed`, its window with the
    program's tracer `off`, `on` or `traced` (under the profiler, benchmark
    and program spans annotated), checked as the benchmark checks it."""
    from repro import obs
    state = drv.setup(c["cfg"], c["traffic"], seed,
                      common.Spans(annotate=(mode == "traced")))
    tracer = None
    if mode == "traced":
        from bench import trace as tr
        tracer = tr.Tracer(os.path.join(common.OUT_DIR, "program_spans",
                                        name))
        tracer.start()
    obs.reset()
    if mode != "off":
        obs.enable(annotate=(mode == "traced"))
    try:
        drv.window(state, seconds)
    finally:
        obs.disable()
    if tracer is not None:
        tracer.stop()
    rec = {"mode": mode, **drv.end_to_end(state, seconds)}
    if mode != "off":
        p = obs.snapshot()
        rec["metrics"] = readings(p)
        rec["coverage"] = coverage(p, state.spans)
        rec["spans_ms"] = span_means(p)
        rec["counters"] = p["counters"]
    if tracer is not None:
        red = tracer.reduce(state.t0, state.t1)
        a, b = tr.window_bounds(tracer.file(), tracer.t_enter, state.t0,
                                state.t1)
        gaps = idle_gaps(tracer.file(), a, b)
        idle = sum(gaps.values())
        rec["device_idle"] = 100.0 * red.idle_share
        rec["idle_gaps"] = [[k, v, v / idle if idle else 0.0]
                            for k, v in list(gaps.items())[:12]]
        rec["device_ops"] = red.breakdown()["device_ops"]
        tracer.discard()
    drv.release(state)
    checks = drv.verify(state, c["cfg"])
    rec["correct"] = all(ch["ok"] for ch in checks)
    return rec


def run(name: str, seeds, seconds: float, pairs: int = 3,
        trace: bool = True, cpu: bool = False, edit=None, out=None):
    c = harness.load_cell(name)
    if edit is not None:
        edit(c)
    harness.configure_jax(c["cfg"])
    if not cpu:
        common.require_chips(c["cell"]["chips"])
    drv = harness.driver(c["traffic"])
    out = out or sys.stdout
    modes = []
    for k in range(pairs):
        modes += ["off", "on"] if k % 2 == 0 else ["on", "off"]
    if trace:
        modes.append("traced")
    recs = []
    for seed in seeds:
        for mode in modes:
            rec = {"workload": name, "seed": seed,
                   **window(name, c, drv, seed, seconds, mode)}
            recs.append(rec)
            out.write(json.dumps(rec) + "\n")
            out.flush()
    return recs


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--pairs", type=int, default=3)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=1)
    ap.add_argument("seeds", type=int, nargs="+")
    args = ap.parse_args(argv)
    try:
        run(args.workload, args.seeds, args.seconds, args.pairs,
            bool(args.trace))
    except common.NoChip as e:
        sys.stderr.write(f"program_spans: {e}\n")
        return 3
    return 0


if __name__ == "__main__":
    sys.exit(main())
