"""Profiler trace of the measured window, and its reduction to numbers.

The window is traced in a run of its own (`--trace 1`), with the Python
tracer off so that tracing costs the host little.  The reduction reads the
`.xplane.pb` file with JAX's own `ProfileData`:

  * device planes are those named `/device:TPU:<n>`; their `XLA Modules`
    line holds one event per program execution, the `XLA Ops` line one per
    operation (a Pallas kernel is a `tpu_custom_call`);
  * busy time is the union of the module intervals inside the window,
    averaged over the chips used; idle is the rest of the window;
  * each idle gap is named by the innermost benchmark span (`bench.*`,
    `setup.*`, layer spans) that the host was in at the gap's midpoint;
  * kernel time is the sum of the durations of a kernel's ops that start
    inside the window, found by the matcher its reader passes.

Host and device events share the trace's clock.  The window's bounds map
from the host clock through the `bench.window` span, whose host-clock
entry time the harness records.
"""
from __future__ import annotations

import glob
import os
import re
import shutil
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Tuple

WINDOW_SPAN = "bench.window"


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def _clip(iv, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in iv if b > lo and a < hi]


_HASH = re.compile(r"\(\d+\)$")


def module_name(name: str) -> str:
    """`jit__pad(8170685879760021664)` -> `jit__pad`."""
    return _HASH.sub("", name)


@dataclass
class Op:
    name: str           # full HLO text
    start_ns: float
    dur_ns: float


@dataclass
class Reduced:
    """The numbers a trace gives, for the per-layer readers."""
    window_s: float
    busy_s: float                           # mean over the chips used
    n_chips: int
    ops: List[Op] = field(default_factory=list)
    modules: Dict[str, float] = field(default_factory=dict)  # name -> s
    gaps: Dict[str, float] = field(default_factory=dict)     # span -> s

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def kernel_seconds(self, match: Callable[[str], bool]) -> Tuple[float, int]:
        """(total device seconds, event count) of ops `match` accepts."""
        sel = [o for o in self.ops if match(o.name)]
        return sum(o.dur_ns for o in sel) * 1e-9, len(sel)

    def breakdown(self) -> dict:
        top = sorted(self.modules.items(), key=lambda kv: -kv[1])[:10]
        gaps = sorted(self.gaps.items(), key=lambda kv: -kv[1])[:10]
        return {"device_ops": [[k, v] for k, v in top],
                "idle_gaps": [[k, v] for k, v in gaps]}


def reduce_file(path: str, t0_ns: float, t1_ns: float) -> Reduced:
    """Reduce one `.xplane.pb` over the window [t0_ns, t1_ns] (trace
    clock)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    busy, ops, modules, spans = [], [], {}, []
    n_chips = 0
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            n_chips += 1
            lines = {ln.name: ln for ln in plane.lines}
            mod = lines.get("XLA Modules") or lines.get("XLA Ops")
            iv = []
            if mod is not None:
                for e in mod.events:
                    iv.append((e.start_ns, e.start_ns + e.duration_ns))
                    if mod.name == "XLA Modules":
                        k = module_name(e.name)
                        modules[k] = modules.get(k, 0.0) + e.duration_ns * 1e-9
            busy.append(_union(_clip(iv, t0_ns, t1_ns)))
            if "XLA Ops" in lines:
                ops.extend(Op(e.name, e.start_ns, e.duration_ns)
                           for e in lines["XLA Ops"].events
                           if t0_ns <= e.start_ns < t1_ns)
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for e in ln.events:
                    if _is_span(e.name):
                        spans.append((e.start_ns, e.start_ns + e.duration_ns,
                                      e.name))
    if not n_chips:
        raise ValueError(f"no TPU device plane in {path}")
    window_s = (t1_ns - t0_ns) * 1e-9
    busy_s = sum(sum(b - a for a, b in iv) for iv in busy) * 1e-9 / n_chips
    gaps: Dict[str, float] = {}
    for a, b in _gaps(busy[0], t0_ns, t1_ns):
        mid = 0.5 * (a + b)
        inner = [s for s in spans if s[0] <= mid < s[1]
                 and s[2] != WINDOW_SPAN]
        name = (min(inner, key=lambda s: s[1] - s[0])[2] if inner
                else "(no benchmark span)")
        gaps[name] = gaps.get(name, 0.0) + (b - a) * 1e-9
    return Reduced(window_s, busy_s, n_chips, ops, modules, gaps)


def _is_span(name: str) -> bool:
    return name.split(".", 1)[0] in ("bench", "setup", "plan", "ingest",
                                     "refresh")


def _gaps(busy, t0, t1):
    prev = t0
    for a, b in busy:
        if a > prev:
            yield prev, a
        prev = max(prev, b)
    if t1 > prev:
        yield prev, t1


def window_bounds(path: str, t_enter: float, t0: float, t1: float):
    """Map the host-clock window [t0, t1] onto the trace clock through the
    `bench.window` span entered at host time `t_enter`."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for ln in plane.lines:
            for e in ln.events:
                if e.name == WINDOW_SPAN:
                    base = e.start_ns - t_enter * 1e9
                    return base + t0 * 1e9, base + t1 * 1e9
    raise ValueError(f"no {WINDOW_SPAN} span in {path}")


class Tracer:
    """Start and stop the profiler around the window, then reduce."""

    def __init__(self, directory: str):
        self.dir = directory
        self.t_enter = None
        self._ann = None

    def start(self) -> None:
        import time

        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self._ann = jax.profiler.TraceAnnotation(WINDOW_SPAN)
        self.t_enter = time.perf_counter()
        self._ann.__enter__()

    def stop(self) -> None:
        import jax
        self._ann.__exit__(None, None, None)
        jax.profiler.stop_trace()

    def file(self) -> str:
        files = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                          recursive=True)
        if not files:
            raise FileNotFoundError(f"no trace under {self.dir}")
        return max(files, key=os.path.getmtime)

    def reduce(self, t0: float, t1: float) -> Reduced:
        path = self.file()
        a, b = window_bounds(path, self.t_enter, t0, t1)
        return reduce_file(path, a, b)

    def discard(self) -> None:
        shutil.rmtree(self.dir, ignore_errors=True)
