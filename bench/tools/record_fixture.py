#!/usr/bin/env python3
"""Record the small trace that `bench/tests/test_bench_trace.py` reduces.

  python bench/tools/record_fixture.py OUT_DIR

On the chip: three predict dispatches of 96 queries and one 256 x 64 fit,
each inside a benchmark span, with host-side gaps between them; the
`.xplane.pb` is copied to OUT_DIR/small.xplane.pb and the window bounds
(trace clock) and reduced numbers go to OUT_DIR/small.json.
"""
from __future__ import annotations

import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

from bench import common, trace  # noqa: E402


def main(out_dir: str) -> int:
    import numpy as np
    from repro.launch.compile_cache import enable_compile_cache
    from repro.store.compute import fit_stacked, predict_stacked
    enable_compile_cache()
    devs = common.require_chips(1)
    post = {"mu": np.zeros((96, 2)), "sigma": np.zeros((96, 2, 2)),
            "beta_prec": np.ones(96), "x_mu": np.zeros(96),
            "x_sd": np.ones(96), "y_mu": np.zeros(96), "y_sd": np.ones(96)}
    xs = np.ones((256, 64), np.float32)
    ys = np.arange(256 * 64, dtype=np.float32).reshape(256, 64)
    predict_stacked(np.ones(96), post)            # compile outside
    fit_stacked(xs, ys, xs)
    spans = common.Spans(annotate=True)
    tr = trace.Tracer(os.path.join(common.OUT_DIR, "fixture"))
    tr.start()
    t0 = time.perf_counter()
    for _ in range(3):
        with spans.span("bench.predict"):
            predict_stacked(np.ones(96), post)
        with spans.span("bench.host_wait"):
            time.sleep(0.01)
    with spans.span("bench.fit"):
        fit_stacked(xs, ys, xs)
    t1 = time.perf_counter()
    tr.stop()
    path = tr.file()
    a, b = trace.window_bounds(path, tr.t_enter, t0, t1)
    red = trace.reduce_file(path, a, b)
    os.makedirs(out_dir, exist_ok=True)
    shutil.copy(path, os.path.join(out_dir, "small.xplane.pb"))
    meta = {"t0_ns": a, "t1_ns": b, "busy_s": red.busy_s,
            "window_s": red.window_s, "gaps": red.gaps,
            "modules": red.modules, "kind": devs[0].device_kind}
    with open(os.path.join(out_dir, "small.json"), "w") as f:
        json.dump(meta, f, indent=1)
    print(json.dumps(meta))
    tr.discard()
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
