"""Readings of the program's own spans and counters (`repro.obs`), as a
traffic generator snapshots them over a traced window under
`ctx["counters"]["program"]`.  Each returns None where the program has no
such span or counter.

The harness reads them in its `--trace 1` run only, with the profiler on.
Span times there include what the profiler adds: it traces the host-CPU
device's ops one by one, so host-CPU jit work (the replan cell's EFT sweep)
reads several times slower than untraced.  Compare span readings traced to
traced only; counters and ratios of counters are not affected."""


def _snap(ctx) -> dict:
    return ctx["counters"].get("program") or {}


def span(ctx, name: str):
    """{"count", "total_s", "self_s"} of span `name`, or None."""
    s = _snap(ctx).get("spans", {}).get(name)
    return s if s and s["count"] else None


def span_mean_ms(ctx, name: str):
    s = span(ctx, name)
    return None if s is None else 1e3 * s["total_s"] / s["count"]


def counter(ctx, name: str):
    return _snap(ctx).get("counters", {}).get(name)
