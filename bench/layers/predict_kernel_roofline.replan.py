"""Share of the chip's roofline that the `bayes_predict` Pallas kernel
reached over the window: the operations and bytes of the queries the
program predicted (the counter `lotaru.compute.queries`, padding
excluded) over the kernel's device time in the trace."""
from bench import roofline
from bench.layers._program import counter
from bench.layers._shared import is_predict_kernel, kernel_roofline


def read(ctx):
    q = counter(ctx, "lotaru.compute.queries")
    if not q:
        return None
    return kernel_roofline(ctx, is_predict_kernel, roofline.predict_cost(q))
