"""Share of planning time in the EFT sweep: `lotaru.sched.sweep` over
`lotaru.plan.replan` plus `lotaru.plan.initial` (every sweep runs inside
one of the two), in %.

Profiler-inflated: the jit sweep runs on the host CPU device, whose ops the
profiler traces one by one, so this share reads several times what an
untraced window gives (see `_program`).  Compare traced to traced only."""
from bench.layers._program import span


def read(ctx):
    sweep = span(ctx, "lotaru.sched.sweep")
    plans = [span(ctx, n) for n in ("lotaru.plan.replan",
                                    "lotaru.plan.initial")]
    total = sum(p["total_s"] for p in plans if p is not None)
    if sweep is None or total <= 0:
        return None
    return 100.0 * sweep["total_s"] / total
