"""jit'd public wrappers for the Pallas kernels, with platform dispatch:
TPU -> compiled kernel; CPU -> interpret mode (tests) or the jnp reference
(production fallback).  The model code calls these entry points."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro import obs
from repro.kernels import ref
from repro.kernels.bayes_fit import bayes_fit as _bayes_fit_pallas
from repro.kernels.bayes_fit import bayes_predict as _bayes_predict_pallas
from repro.kernels.bayes_fit import nig_fold as _nig_fold_pallas
from repro.kernels.bayes_fit import nig_fold_scan as _nig_fold_scan
from repro.kernels.decision_plane import fused_cost as _fused_cost_pallas
from repro.kernels.decision_plane import fused_cost_ref as _fused_cost_ref
from repro.kernels.flash_attention import flash_attention as _flash_pallas
from repro.kernels.rglru_scan import rglru_scan as _rglru_pallas


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


@functools.partial(jax.jit, static_argnames=("causal", "window", "impl"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    impl: str = "auto"):
    """impl: auto | pallas | interpret | ref"""
    if impl == "pallas" or (impl == "auto" and _on_tpu()):
        return _flash_pallas(q, k, v, causal=causal, window=window)
    if impl == "interpret":
        return _flash_pallas(q, k, v, causal=causal, window=window,
                             interpret=True)
    return ref.attention_ref(q, k, v, causal=causal, window=window)


@functools.partial(jax.jit, static_argnames=("impl",))
def rglru_scan(a, gx, h0, *, impl: str = "auto"):
    if impl == "pallas" or (impl == "auto" and _on_tpu()):
        return _rglru_pallas(a, gx, h0)
    if impl == "interpret":
        return _rglru_pallas(a, gx, h0, interpret=True)
    return ref.rglru_scan_ref(a, gx, h0)


@functools.partial(jax.jit, static_argnames=("impl",))
def bayes_fit(x, y, mask, *, impl: str = "auto"):
    if impl == "pallas" or (impl == "auto" and _on_tpu()):
        return _bayes_fit_pallas(x, y, mask)
    if impl == "interpret":
        return _bayes_fit_pallas(x, y, mask, interpret=True)
    return ref.bayes_fit_ref(x, y, mask)


def nig_fold(xs, ys, mask, mu, v, prec, b, *, impl: str = "auto"):
    """Batched streaming-update fold (the ingest-plane device form):
    (T, K) standardized masked observations folded into T NIG states in
    one dispatch.  impl: auto | pallas | interpret | ref ('ref' is the
    vmapped lax.scan form).  The EXACT float64 ingest path lives in
    `core.bayes.nig_update_batch(impl='numpy')` — this float32 entry point
    is for device-resident posterior banks, not digest-bearing state."""
    if impl == "pallas" or (impl == "auto" and _on_tpu()):
        return _nig_fold_pallas(xs, ys, mask, mu, v, prec, b)
    if impl == "interpret":
        return _nig_fold_pallas(xs, ys, mask, mu, v, prec, b, interpret=True)
    return _nig_fold_scan(xs, ys, mask, mu, v, prec, b)


@functools.partial(jax.jit, static_argnames=("impl",))
def _bayes_predict_jit(x, post, impl: str):
    if impl == "pallas" or (impl == "auto" and _on_tpu()):
        return _bayes_predict_pallas(x, post)
    if impl == "interpret":
        return _bayes_predict_pallas(x, post, interpret=True)
    return ref.bayes_predict_ref(x, post)


@functools.partial(jax.jit, static_argnames=("z", "impl"))
def fused_cost(x, post, factors, *, z: float = 0.0, impl: str = "auto"):
    """Fused predict -> scale -> quantile cost matrix (T, N) for the
    decision plane: posterior rows + input sizes + factor matrix in, the
    HEFT cost matrix out, one dispatch.  impl: auto | pallas | interpret
    | ref.  The EFT sweep itself lives in `kernels.decision_plane`
    (`eft_sweep` / `eft_sweep_many` / `eft_sweep_pallas`) — it carries
    loop state, so it keeps its own jit entry points."""
    if impl == "pallas" or (impl == "auto" and _on_tpu()):
        return _fused_cost_pallas(x, post, factors, z=z)
    if impl == "interpret":
        return _fused_cost_pallas(x, post, factors, z=z, interpret=True)
    return _fused_cost_ref(x, post, factors, z)


_PREDICT_TILE = 1024            # jit shape bucket (avoids a recompile per
_SAFE_ONE = ("beta_prec", "x_sd", "y_sd")     # distinct batch size)


def bayes_predict(x, post, *, impl: str = "auto"):
    """Batched posterior predictive: x (Q,), post leaves gathered per query
    (Q, ...) -> (mean, std) each (Q,).  TPU: fused Pallas pass; CPU: the
    vmapped predict_blr reference.

    Queries are padded to _PREDICT_TILE multiples BEFORE the jit boundary:
    a serving loop whose batch shrinks by one per completion would
    otherwise trigger an XLA compile per distinct Q.  Padded rows use
    benign posteriors (unit scales, zero means) and are sliced off."""
    q = x.shape[0]
    qp = -(-max(q, 1) // _PREDICT_TILE) * _PREDICT_TILE
    if qp != q:
        pad = qp - q
        with obs.span("lotaru.compute.pad"):
            x = jnp.pad(x, (0, pad))
            post = {k: jnp.pad(jnp.asarray(v),
                               ((0, pad),) + ((0, 0),) * (jnp.ndim(v) - 1),
                               constant_values=1.0 if k in _SAFE_ONE else 0.0)
                    for k, v in post.items()}
    mean, std = _bayes_predict_jit(x, post, impl)
    with obs.span("lotaru.compute.pad"):
        return mean[:q], std[:q]
