"""Mean wall time of one drift check (the frontier's batched predict on its
assigned nodes and the band test): the program span `lotaru.plan.drift`."""
from bench.layers._program import span_mean_ms


def read(ctx):
    return span_mean_ms(ctx, "lotaru.plan.drift")
