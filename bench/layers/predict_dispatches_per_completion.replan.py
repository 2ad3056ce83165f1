"""Predict dispatches (calls of `store.compute.predict_stacked`, the span
`lotaru.compute.predict`) per completion the planner handled, first plans
included in the numerator."""
from bench.layers._program import counter, span


def read(ctx):
    done = counter(ctx, "lotaru.plan.completions")
    calls = span(ctx, "lotaru.compute.predict")
    if not done or calls is None:
        return None
    return calls["count"] / done
