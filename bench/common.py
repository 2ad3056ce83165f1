"""Shared plumbing of the benchmark: the chip check, the compile clock,
statistics over whole windows, benchmark-side spans, and the result line.

Nothing here imports the program; `run.py` puts `src/` on the path and the
drivers import the system under test themselves.
"""
from __future__ import annotations

import json
import os
import sys
import threading
import time
from contextlib import contextmanager
from typing import Dict, List, Optional

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")
OUT_DIR = os.path.join(ROOT, ".bench_out")     # traces; git-ignored


class NoChip(RuntimeError):
    """No accelerator of the kind the cell asks for."""


def require_chips(chips: int, platform: str = "tpu"):
    """The devices a run may measure on, or NoChip.  Never a fallback."""
    import jax
    devs = jax.devices()
    if jax.default_backend() != platform:
        raise NoChip(f"JAX's default backend is {jax.default_backend()!r}; "
                     f"this benchmark measures only on a {platform.upper()}")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX sees {len(devs)}")
    return devs[:chips]


def device_record(devs) -> dict:
    d = devs[0]
    peak = 0
    for dev in devs:
        stats = dev.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling (or fetching a
    compiled program from the persistent cache), and how many programs it
    compiled or fetched, from its own monitoring events."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")
    COMPILE = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self._jax = jax
        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_) -> None:
        if event in self.EVENTS:
            self.seconds += secs
        if event == self.COMPILE:
            self.compiles += 1

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def close(self) -> None:
        self._jax.monitoring.unregister_event_duration_listener(self._duration)
        self._jax.monitoring.unregister_event_listener(self._event)


# ---------------------------------------------------------------------------
# statistics over a whole window
# ---------------------------------------------------------------------------

def rate(count: float, window_s: float) -> float:
    """Work per second over the whole window."""
    if window_s <= 0:
        raise ValueError("window must be positive")
    return count / window_s


# ---------------------------------------------------------------------------
# benchmark-side spans
# ---------------------------------------------------------------------------

class Spans:
    """Host spans around the calls into each layer, written from the
    benchmark's own files.  Each span is also a profiler TraceAnnotation,
    so in a traced run it shares the device trace's clock; the in-memory
    totals feed the per-layer metrics either way."""

    def __init__(self, annotate: bool):
        self.annotate = annotate
        self.total: Dict[str, float] = {}
        self.count: Dict[str, int] = {}
        self._lock = threading.Lock()
        self._ann = None
        if annotate:
            import jax
            self._ann = jax.profiler.TraceAnnotation

    @contextmanager
    def span(self, name: str):
        t0 = time.perf_counter()
        if self._ann is not None:
            with self._ann(name):
                yield
        else:
            yield
        dt = time.perf_counter() - t0
        with self._lock:
            self.total[name] = self.total.get(name, 0.0) + dt
            self.count[name] = self.count.get(name, 0) + 1
        if name.startswith("setup."):
            sys.stderr.write(f"bench: {name} {dt:.3f} s\n")
            sys.stderr.flush()


# ---------------------------------------------------------------------------
# the result line
# ---------------------------------------------------------------------------

def metric(value: float, unit: str) -> dict:
    return {"value": float(value), "unit": unit}


def emit_result(correct: bool, attempted: int, failed: int,
                metrics: Dict[str, dict], device: dict,
                checks: List[dict], breakdown: Optional[dict] = None,
                out=None, err=None) -> None:
    """Print the compared numbers beside their limits as the last lines of
    stderr, then the one-line JSON result as the last line of stdout (the
    checks again under their own key, last)."""
    out = out or sys.stdout
    err = err or sys.stderr
    for c in checks:
        err.write(f"check {c['name']}: {c['value']!r} "
                  f"{c['op']} {c['limit']!r} -> "
                  f"{'ok' if c['ok'] else 'FAIL'}\n")
    err.flush()
    line = {"correct": bool(correct), "attempted": int(attempted),
            "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = [{"name": c["name"], "value": c["value"],
                       "limit": c["limit"], "op": c["op"]} for c in checks]
    out.write(json.dumps(line) + "\n")
    out.flush()


def check(name: str, value: float, limit: float, op: str = "<=") -> dict:
    """One compared number beside its limit.  A NaN never passes."""
    value = float(value)
    ok = (value <= limit) if op == "<=" else (value >= limit)
    if value != value:
        ok = False
    return {"name": name, "value": value, "limit": limit, "op": op, "ok": ok}


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)

