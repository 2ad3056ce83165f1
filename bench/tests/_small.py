"""Small runs of the benchmark's cells on the CPU, for the tests."""
from __future__ import annotations

import functools

import jax

from bench import run as harness
from bench.drivers import serve

SEED = 2 ** 33 + 17          # larger than 32 signed bits, as the driver's
_PROFILE = serve.profile


@functools.lru_cache(maxsize=None)
def experiments(seed: int = SEED):
    """The five profiled workflows, built once per test process."""
    from bench.common import Spans
    return _PROFILE(("bacass", "atacseq", "chipseq", "eager",
                          "methylseq"), seed, Spans(False))


def small(c: dict) -> dict:
    """Cut a cell to a size the CPU runs in seconds."""
    cfg, t = c["cfg"], c["traffic"]
    cfg["tenants"] = 24
    lim = cfg["limits"]
    lim["min_checked_queries"] = lim["min_checked_tasks"] = 1
    if t["driver"] == "plan":
        t.update(check_sample=20)
    if t["driver"] == "refresh":
        t.update(tasks_per_pass=64, feed_passes=2, check_tasks=16)
    return c


def run_small(monkeypatch, name: str, seconds: float = 1.5,
              seed: int = SEED):
    """One harness run of `name`, cut small, with the chip check skipped;
    returns the result fields."""
    load = harness.load_cell
    monkeypatch.setattr(harness, "load_cell", lambda n: small(load(n)))
    monkeypatch.setattr(serve, "profile",
                        lambda workflows, s, spans: experiments(s))
    import io
    out, err = io.StringIO(), io.StringIO()
    res = harness.run_cell(name, seed, seconds, False,
                           chips_check=lambda n: jax.devices()[:n],
                           out=out, err=err)
    res["stdout"], res["stderr"] = out.getvalue(), err.getvalue()
    return res
