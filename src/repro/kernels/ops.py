"""jit'd public wrappers for the Pallas kernels, with platform dispatch:
TPU -> compiled kernel; CPU -> interpret mode (tests) or the jnp reference
(production fallback).  The model code calls these entry points."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.kernels import ref
from repro.kernels.bayes_fit import bayes_fit as _bayes_fit_pallas
from repro.kernels.bayes_fit import (bayes_predict_planes,
                                     pack_predict_planes)
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
def _bayes_predict_jit(planes, impl: str):
    """Packed planes (PREDICT_PLANES, rows, LANE) -> (2, rows, LANE): mean
    and std.  The benchmark finds the kernel by this function's name."""
    if impl == "pallas" or (impl == "auto" and _on_tpu()):
        return bayes_predict_planes(planes)
    if impl == "interpret":
        return bayes_predict_planes(planes, interpret=True)
    p = planes.reshape(planes.shape[0], -1)
    post = {"mu": jnp.stack([p[1], p[2]], axis=1),
            "sigma": jnp.stack([p[3], p[4], p[4], p[5]],
                               axis=1).reshape(-1, 2, 2),
            "beta_prec": p[6], "x_mu": p[7], "x_sd": p[8],
            "y_mu": p[9], "y_sd": p[10]}
    mean, std = ref.bayes_predict_ref(p[0], post)
    return jnp.stack([mean, std]).reshape((2,) + planes.shape[1:])


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


def bayes_predict(x, post, *, impl: str = "auto"):
    """Batched posterior predictive: x (Q,), post leaves gathered per query
    (Q, ...) -> (mean, std) each (Q,) float64 NumPy.  TPU: fused Pallas
    pass; CPU: the vmapped predict_blr reference.

    Inputs are packed and padded on the host (NumPy) into one fresh float32
    buffer, bucketed to PREDICT_TILE multiples so a serving loop whose batch
    shrinks by one per completion reuses one compiled program; then one
    transfer, one jitted dispatch and one readback, with no eager device
    program around them.  Padded lanes are sliced off on the host."""
    x = np.asarray(x)
    q = x.shape[0]
    with obs.span("lotaru.compute.pad"):
        planes = pack_predict_planes(
            x, {k: np.asarray(v) for k, v in post.items()})
    out = _bayes_predict_jit(jax.device_put(planes), impl)
    with obs.span("lotaru.compute.readback"):
        host = np.asarray(out)
    if obs.enabled():
        obs.count("lotaru.compute.h2d_bytes", planes.nbytes)
        obs.count("lotaru.compute.d2h_bytes", host.nbytes)
    mean, std = host.reshape(2, -1)[:, :q].astype(np.float64)
    return mean, std
