"""Share of completions whose drift check led to a replan: the program
counters `lotaru.plan.replans` over `lotaru.plan.completions`, in %."""
from bench.layers._program import counter


def read(ctx):
    done = counter(ctx, "lotaru.plan.completions")
    if not done:
        return None
    return 100.0 * (counter(ctx, "lotaru.plan.replans") or 0) / done
