"""Operations and bytes of the service's two Pallas kernels, from the
shapes of the useful work, and their share of the chip's roofline.

Both kernels do float32 vector work; the table's compute peak is the bf16
matrix peak, an upper bound, so a share computed against it is a lower
bound of the real one.  Padding rows and columns are not useful work and
are not counted.
"""
from __future__ import annotations

import json
import os
from typing import Iterable, Tuple

PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "peaks.json")
F32 = 4
N_ITERS = 30                 # evidence fixed-point steps of the fit

# predictive, per query: x (1), mu (2), sigma (3), beta (1), x/y stats (4)
# in; mean and std out
PREDICT_WORDS = 11 + 2
# xs = (x - x_mu) / x_sd (2), mean_s (2), var_s (7), mean (2), std (3)
PREDICT_FLOPS = 16


def peaks(kind: str) -> dict:
    """The peaks of one device kind.  A kind not in the table is an
    error, never a default."""
    with open(PEAKS) as f:
        table = json.load(f)["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r}; known: "
                       f"{sorted(table)}")
    return table[kind]


def predict_cost(queries: int) -> Tuple[float, float]:
    """(flops, bytes) of `bayes_predict` over that many useful queries."""
    return float(PREDICT_FLOPS * queries), float(PREDICT_WORDS * F32
                                                 * queries)


def fit_cost(points: Iterable[int]) -> Tuple[float, float]:
    """(flops, bytes) of `bayes_fit` over tasks with these many valid
    observations each: x, y and mask read once per point, 13 words of
    posterior written per task; per point the masked moments and
    standardization (~14 flops) once and the residual (~5) per step, per
    task ~45 scalar flops per step for the 2x2 algebra."""
    flops = bytes_ = 0.0
    for n in points:
        flops += 14 * n + N_ITERS * (5 * n + 45) + 30
        bytes_ += (3 * n + 13) * F32
    return flops, bytes_


def share(flops: float, bytes_: float, seconds: float, kind: str
          ) -> Tuple[float, str]:
    """(% of the roofline, the bounding term) for work that took
    `seconds` of device time."""
    if seconds <= 0:
        raise ValueError("kernel time must be positive")
    p = peaks(kind)
    t_compute = flops / p["flops_per_s"]
    t_memory = bytes_ / p["hbm_bytes_per_s"]
    bound = "compute" if t_compute >= t_memory else "memory"
    return 100.0 * max(t_compute, t_memory) / seconds, bound
