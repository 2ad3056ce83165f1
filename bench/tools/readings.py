#!/usr/bin/env python3
"""Readings that set a cell's correctness limits, many seeds in one process.

  python bench/tools/readings.py --workload <cell> --seconds S SEED [SEED ...]

For each seed: set the cell up, run a short window at the cell's own load,
free the program's state, and print one JSON line with every compared
number twice: as the program produced it (the lower reading) and with the
plain reference put in the program's place one precision below float32
(the control, the upper reading).  The benchmark's own runs never run the
control.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

from bench import common  # noqa: E402
from bench import run as harness  # noqa: E402


def readings(name: str, seeds, seconds: float, cpu: bool = False,
             edit=None):
    c = harness.load_cell(name)
    if edit is not None:
        edit(c)
    harness.configure_jax(c["cfg"])
    if not cpu:
        common.require_chips(c["cell"]["chips"])
    drv = harness.driver(c["traffic"])
    out = []
    for seed in seeds:
        spans = common.Spans(annotate=False)
        t = time.perf_counter()
        state = drv.setup(c["cfg"], c["traffic"], seed, spans)
        drv.window(state, seconds)
        drv.release(state)
        rec = {"seed": seed, "setup_and_window_s": time.perf_counter() - t}
        for ch in drv.verify(state, c["cfg"]):
            rec[ch["name"]] = ch["value"]
        for ch in drv.verify(state, c["cfg"], control=True):
            rec["control." + ch["name"]] = ch["value"]
        out.append(rec)
        print(json.dumps(rec), flush=True)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("seeds", type=int, nargs="+")
    args = ap.parse_args(argv)
    readings(args.workload, args.seeds, args.seconds)
    return 0


if __name__ == "__main__":
    sys.exit(main())
