"""Batched posterior-predictive evaluation shared by every serving path.

`PredictionService.predict_batch` and the async front-end's coalesced
dispatch must produce bit-identical numbers for the same queries, so both
call the two functions here: `predict_stacked` (one kernel/vectorized call
over gathered posterior rows) and `finalize` (factor rescaling + z-bands).
Off TPU the math is the same float64 elementwise ops as the scalar
`predict_blr_np` path, so slicing a coalesced batch apart yields exactly
what each caller would have computed alone.
"""
from __future__ import annotations

from typing import Optional, Tuple

import numpy as np

from repro import obs

# the posterior leaves the serving stack stores and gathers, with their
# per-row shapes ('n' is fit metadata, not needed by the predictive)
LEAVES = ("mu", "sigma", "beta_prec", "x_mu", "x_sd", "y_mu", "y_sd")
LEAF_SHAPES = {"mu": (2,), "sigma": (2, 2), "beta_prec": (), "x_mu": (),
               "x_sd": (), "y_mu": (), "y_sd": ()}


def predict_stacked(x: np.ndarray, post: dict, impl: str = "auto"
                    ) -> Tuple[np.ndarray, np.ndarray]:
    """(Q,) inputs + per-query gathered leaves (Q, ...) -> (mean, std) in
    float64.  TPU: fused Pallas pass, fed from one host-packed buffer
    (`kernels.ops.bayes_predict`); elsewhere the vectorized float64
    reference (bit-exact vs the scalar path at any runtime magnitude).

    jax/kernels are imported per call so `repro.store` (and the event
    vocabulary re-exporting its keys) stays import-light for consumers
    that never predict."""
    from repro.core import bayes
    from repro.kernels import ops
    with obs.span("lotaru.compute.predict"):
        if not (impl in ("pallas", "interpret")
                or (impl == "auto" and ops._on_tpu())):
            return bayes.predict_blr_np(post, np.asarray(x, np.float64))
        return ops.bayes_predict(x, post, impl=impl)


def fit_stacked(x: np.ndarray, y: np.ndarray, mask: np.ndarray,
                impl: str = "auto") -> dict:
    """(T, N) padded/masked observation buffers -> stacked posterior dict
    (float64 numpy leaves, incl. `alpha`/`n` fit metadata) from ONE batched
    MacKay evidence fixed-point dispatch.

    This is the fit-side sibling of `predict_stacked`, shared by the
    posterior maintenance plane (fleet-wide evidence refresh) and any bulk
    re-fit: TPU gets the fused Pallas kernel with ragged row padding
    (`kernels.bayes_fit.bayes_fit_ragged`), everywhere else the jit'd vmap
    of `core.bayes.fit_blr` — either way a fleet of task models re-fits in
    a single dispatch instead of one fixed-point solve per task."""
    from repro.core import bayes
    from repro.kernels import ops
    import jax.numpy as jnp
    xj = jnp.asarray(x, jnp.float32)
    yj = jnp.asarray(y, jnp.float32)
    mj = jnp.asarray(mask, jnp.float32)
    if impl in ("pallas", "interpret") or (impl == "auto" and ops._on_tpu()):
        from repro.kernels.bayes_fit import bayes_fit_ragged
        post = bayes_fit_ragged(xj, yj, mj, interpret=(impl == "interpret"))
    else:
        post = bayes.fit_blr_batch(xj, yj, mj)
    return {k: np.asarray(v, np.float64) for k, v in post.items()}


def fold_stacked(nigs, xs, ys, impl: str = "auto"):
    """Batched streaming-observation fold — the ingest-side sibling of
    `fit_stacked`: T NIG states + ragged per-task observation rows ->
    T updated states from ONE fold dispatch (`core.bayes.nig_update_batch`).

    Unlike its read-path siblings, impl='auto' NEVER routes to a device
    kernel — not even on TPU: the ingest plane's exactness contract
    (bit-identical to the scalar `nig_update` chain, which feeds state
    digests and failover replay) only holds for the float64 CPU fold.
    The float32 'pallas'/'interpret'/'scan' forms are an explicit opt-in
    for device-resident posterior banks that keep no digest."""
    from repro.core import bayes
    if impl in ("pallas", "interpret", "scan"):
        return bayes.nig_update_batch(nigs, xs, ys, impl=impl)
    return bayes.nig_update_batch(nigs, xs, ys, impl="numpy")


def scale(mean: np.ndarray, std: np.ndarray, factors: np.ndarray
          ) -> Tuple[np.ndarray, np.ndarray]:
    """Extrapolation-factor rescaling (with the mean floor) shared by the
    flat path (`finalize`) and the decision plane's matrix path — one
    definition, so the two can never drift apart (broadcasts, so factors
    may be per-query (Q,) or a (T, N) matrix against (T, 1) predictions)."""
    f = np.asarray(factors, np.float64)
    return np.maximum(mean, 1e-3) * f, std * f


def cost_matrix(mean_s: np.ndarray, std_s: np.ndarray,
                z: Optional[float]) -> np.ndarray:
    """Quantile cost view over an already-scaled (T, N) mean/std pair:
    `mean + z * std` at the requested band, or the mean itself when no
    quantile is asked for.  Matches `plane.PredictionMatrix.costs`
    term-for-term (same expressions, no reassociation) so a resident
    plane serving this view schedules bitwise like the gather path."""
    if z is None:
        return np.array(mean_s, np.float64, copy=True)
    return mean_s + z * std_s


def finalize(mean: np.ndarray, std: np.ndarray, factors: np.ndarray,
             z: float) -> np.ndarray:
    """Apply extrapolation factors and credible bands -> (Q, 3) array of
    [mean, lower, upper] seconds."""
    mean, std = scale(mean, std, factors)
    lower = np.maximum(mean - z * std, 0.0)
    upper = mean + z * std
    return np.stack([mean, lower, upper], axis=1)
