"""Mean wall time of one replan of the frontier (plane sync and cost view,
running-task predict, ready times, rank, sweep, build): the program span
`lotaru.plan.replan`.

Profiler-inflated by the jit sweep it holds (see `_program`): compare
traced to traced only."""
from bench.layers._program import span_mean_ms


def read(ctx):
    return span_mean_ms(ctx, "lotaru.plan.replan")
