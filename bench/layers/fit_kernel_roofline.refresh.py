"""Share of the chip's roofline that the `bayes_fit` Pallas kernel reached
over the window: operations and bytes of the unpadded (task, points)
buffers it fitted, over the kernel's device time in the trace."""
from bench import roofline
from bench.layers._shared import is_fit_kernel, kernel_roofline


def read(ctx):
    points = ctx["counters"].get("fit_points")
    if not points:
        return None
    return kernel_roofline(ctx, is_fit_kernel, roofline.fit_cost(points))
