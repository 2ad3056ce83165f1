"""Mean wall time of the planner's handling of one completion (observe,
drift check, replan on drift): the program span `lotaru.plan.completion`."""
from bench.layers._program import span_mean_ms


def read(ctx):
    return span_mean_ms(ctx, "lotaru.plan.completion")
