"""Bayesian linear regression (the paper's Eq. 1-3) in pure JAX.

Model:  y_i = X beta + eps_i,  eps ~ N(0, 1/beta_prec),  beta ~ N(0, 1/alpha I)
(Gaussian prior == L2 regularization, exactly as Section 4.5 argues).

Hyper-parameters (alpha, beta_prec) are set by evidence (type-II maximum
likelihood) fixed-point iteration a la MacKay / sklearn's BayesianRidge —
appropriate for the tiny training sets local profiling yields (3-10 points).

Everything is expressed with fixed-shape jnp ops + masks so thousands of
task models fit in one `vmap`/`jit` (see kernels/bayes_fit for the fused
Pallas version of the batched fit).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

N_ITERS = 30
EPS = 1e-9


def _design(x: jnp.ndarray) -> jnp.ndarray:
    return jnp.stack([jnp.ones_like(x), x], axis=-1)          # (N, 2)


def fit_blr(x: jnp.ndarray, y: jnp.ndarray,
            mask: Optional[jnp.ndarray] = None) -> dict:
    """Fit one task model.  x, y: (N,) float32 (input size, runtime);
    mask: (N,) 1.0 for valid points (fixed-shape batching).

    Returns a dict of arrays (vmap-friendly 'posterior' pytree):
      mu (2,), sigma (2,2), alpha, beta_prec, x_mu, x_sd, y_mu, y_sd, n
    """
    x = jnp.asarray(x, jnp.float32)
    y = jnp.asarray(y, jnp.float32)
    m = jnp.ones_like(x) if mask is None else jnp.asarray(mask, jnp.float32)
    n = jnp.maximum(m.sum(), 1.0)

    # standardize over valid points (keeps the fixed-point iteration stable)
    x_mu = (x * m).sum() / n
    y_mu = (y * m).sum() / n
    x_sd = jnp.sqrt(((x - x_mu) ** 2 * m).sum() / n + EPS)
    y_sd = jnp.sqrt(((y - y_mu) ** 2 * m).sum() / n + EPS)
    xs = (x - x_mu) / x_sd * m
    ys = (y - y_mu) / y_sd * m

    phi = _design(xs) * m[:, None]                            # (N,2)
    gram = phi.T @ phi                                        # (2,2)
    phi_y = phi.T @ ys                                        # (2,)
    eye = jnp.eye(2, dtype=jnp.float32)

    def body(_, ab):
        alpha, beta = ab
        sigma = jnp.linalg.inv(alpha * eye + beta * gram)
        mu = beta * sigma @ phi_y
        # effective number of well-determined parameters
        lam = jnp.linalg.eigvalsh(beta * gram)
        gamma = jnp.sum(lam / (alpha + lam))
        resid = ((ys - phi @ mu) ** 2 * m).sum()
        alpha = gamma / jnp.maximum(mu @ mu, EPS)
        beta = jnp.maximum(n - gamma, EPS) / jnp.maximum(resid, EPS)
        return jnp.clip(alpha, 1e-6, 1e6), jnp.clip(beta, 1e-6, 1e8)

    alpha, beta = jax.lax.fori_loop(0, N_ITERS, body,
                                    (jnp.float32(1.0), jnp.float32(1.0)))
    sigma = jnp.linalg.inv(alpha * eye + beta * gram)
    mu = beta * sigma @ phi_y
    return {"mu": mu, "sigma": sigma, "alpha": alpha, "beta_prec": beta,
            "x_mu": x_mu, "x_sd": x_sd, "y_mu": y_mu, "y_sd": y_sd, "n": n}


def predict_blr(post: dict, x_new: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Predictive mean and std (in original units) at x_new (...,)."""
    xs = (jnp.asarray(x_new, jnp.float32) - post["x_mu"]) / post["x_sd"]
    phi = jnp.stack([jnp.ones_like(xs), xs], axis=-1)
    mean_s = phi @ post["mu"]
    var_s = 1.0 / post["beta_prec"] + jnp.einsum(
        "...i,ij,...j->...", phi, post["sigma"], phi)
    mean = mean_s * post["y_sd"] + post["y_mu"]
    std = jnp.sqrt(jnp.maximum(var_s, 0.0)) * post["y_sd"]
    return mean, std


def predict_blr_np(post: dict, x_new) -> Tuple[np.ndarray, np.ndarray]:
    """predict_blr in float64 numpy, vectorized over any leading dims shared
    by x_new and the posterior leaves (stacked posteriors: leaves (..., 2),
    (..., 2, 2), scalars (...)).

    The serving path uses this off-TPU: one vectorized call over thousands
    of gathered queries is the batched predict, and because the scalar and
    batched paths are the *same* float64 elementwise ops, they agree
    bit-for-bit at any runtime magnitude (fp32 ulps at hour-scale runtimes
    exceed the service's 1e-4 parity budget)."""
    mu = np.asarray(post["mu"], np.float64)
    sig = np.asarray(post["sigma"], np.float64)
    x = np.asarray(x_new, np.float64)
    xs = (x - np.asarray(post["x_mu"], np.float64)) \
        / np.asarray(post["x_sd"], np.float64)
    y_mu = np.asarray(post["y_mu"], np.float64)
    y_sd = np.asarray(post["y_sd"], np.float64)
    mean_s = mu[..., 0] + mu[..., 1] * xs
    var_s = 1.0 / np.asarray(post["beta_prec"], np.float64) \
        + sig[..., 0, 0] + 2.0 * sig[..., 0, 1] * xs + sig[..., 1, 1] * xs * xs
    mean = mean_s * y_sd + y_mu
    std = np.sqrt(np.maximum(var_s, 0.0)) * y_sd
    return mean, std


def credible_interval(post: dict, x_new: jnp.ndarray,
                      z: float = 1.96) -> Tuple[jnp.ndarray, jnp.ndarray]:
    mean, std = predict_blr(post, x_new)
    return mean - z * std, mean + z * std


# batched (many tasks at once): x,y,mask (T, N)
fit_blr_batch = jax.jit(jax.vmap(fit_blr))
predict_blr_batch = jax.jit(jax.vmap(predict_blr))


def constant_posterior(mean: float, std: float) -> dict:
    """Degenerate posterior whose predictive is exactly (mean, std) at any
    input — lets median-fallback tasks ride the same batched predict path
    as the regression tasks (predict_blr of this dict returns (mean, std)).

    float64 leaves: the scalar path returns the median at full precision,
    so the batched path must carry it at full precision too (an fp32 ulp
    at hour-scale runtimes already exceeds the 1e-4 parity budget)."""
    return {"mu": np.zeros(2), "sigma": np.zeros((2, 2)),
            "alpha": np.float64(1.0), "beta_prec": np.float64(1.0),
            "x_mu": np.float64(0.0), "x_sd": np.float64(1.0),
            "y_mu": np.float64(mean), "y_sd": np.float64(max(std, 1e-6)),
            "n": np.float64(0.0)}


# ---------------------------------------------------------------------------
# streaming conjugate updates (the online-prediction subsystem)
# ---------------------------------------------------------------------------
# The MacKay fit above is a one-shot offline procedure.  For the online
# service we lift a fitted posterior into a conjugate Normal-Inverse-Gamma
# state:  beta | s2 ~ N(mu, s2 V),  s2 ~ IG(a, b),  which admits EXACT
# rank-1 updates as task completions stream in — no refit, O(1) per event.
# The standardization stats are frozen at lift time (they only fix the
# affine coordinate system; the conjugate algebra is exact in it).
# All state is float64 numpy: thousands of sequential Sherman-Morrison
# updates stay exact to ~1e-12 where float32 would drift.

_NIG_SCALARS = ("a", "b", "x_mu", "x_sd", "y_mu", "y_sd", "n0", "n_obs",
                "s2_lift")


def nig_from_blr_stacked(post: dict) -> dict:
    """Lift fitted BLR posteriors into streaming NIG fields, over any
    leading batch shape: (T, ...) leaves from `store.compute.fit_stacked`
    give (T, ...) fields, unbatched leaves give unbatched ones.

    Moment matching: the MacKay posterior has weight covariance `sigma` and
    noise precision `beta_prec`; we take E[s2] = b/a = 1/beta_prec with
    a = max(n/2, 1) pseudo-observations of noise, and V = sigma * beta_prec
    so that E[s2] * V equals the fitted weight covariance exactly.

    Every field is elementwise float64 per task, and the stacked `inv`
    runs the same LAPACK routine per 2x2 matrix, so a task's fields are
    bit-identical whether it is lifted alone or in a stack."""
    sigma = np.asarray(post["sigma"], np.float64)
    beta = np.asarray(post["beta_prec"], np.float64)
    n0 = np.asarray(post["n"], np.float64)
    a = np.maximum(n0 / 2.0, 1.0)
    v = sigma * beta[..., None, None]
    return {"mu": np.array(post["mu"], np.float64), "v": v,
            "prec": np.linalg.inv(v), "a": a, "b": a / beta,
            "x_mu": np.asarray(post["x_mu"], np.float64),
            "x_sd": np.asarray(post["x_sd"], np.float64),
            "y_mu": np.asarray(post["y_mu"], np.float64),
            "y_sd": np.asarray(post["y_sd"], np.float64),
            "n0": n0, "n_obs": np.zeros(np.shape(n0)),
            # noise level the evidence fixed point chose at lift time; the
            # maintenance plane's drift trigger compares the streaming
            # estimate b/a against it (see online.maintenance.RefreshPolicy)
            "s2_lift": 1.0 / beta}


def nig_rows(nig: dict) -> list:
    """Split (T, ...) NIG fields into T streaming states: float64 arrays
    for mu, v and prec (views of row t: nothing updates a state in place,
    `nig_update` and the folds build new arrays) and Python floats for
    the scalars, the types `nig_from_blr` gives and checkpoints hold."""
    return [{"mu": mu, "v": v, "prec": prec, "a": a, "b": b, "x_mu": x_mu,
             "x_sd": x_sd, "y_mu": y_mu, "y_sd": y_sd, "n0": n0,
             "n_obs": n_obs, "s2_lift": s2_lift}
            for (mu, v, prec, a, b, x_mu, x_sd, y_mu, y_sd, n0, n_obs,
                 s2_lift) in zip(nig["mu"], nig["v"], nig["prec"],
                                 *(nig[k].tolist() for k in _NIG_SCALARS))]


def nig_from_blr(post: dict) -> dict:
    """Lift one fitted BLR posterior into a streaming NIG state: the
    unbatched case of `nig_from_blr_stacked`."""
    nig = nig_from_blr_stacked(post)
    return {k: v if k in ("mu", "v", "prec") else float(v)
            for k, v in nig.items()}


def nig_update(nig: dict, x_new: float, y_new: float) -> dict:
    """Exact conjugate rank-1 update with one observation (original units).

    Sherman-Morrison keeps V = prec^-1 without re-inversion:
        prec' = prec + phi phi^T
        V'    = V - (V phi)(V phi)^T / (1 + phi^T V phi)
        mu'   = V' (prec mu + phi y)
        a'    = a + 1/2
        b'    = b + (y^2 + mu^T prec mu - mu'^T prec' mu') / 2

    All 2x2 algebra is unrolled to explicit component arithmetic — the
    SAME expressions `_nig_fold_np` evaluates on (T,) vectors — so the
    scalar chain and the batched fold perform identical float64 IEEE op
    sequences per task and agree bit-for-bit (BLAS matvec/dot kernels do
    not guarantee that: their FMA contractions differ from elementwise
    numpy in the last ulp).
    """
    xs = (float(x_new) - nig["x_mu"]) / nig["x_sd"]
    ys = (float(y_new) - nig["y_mu"]) / nig["y_sd"]
    prec, v, mu = nig["prec"], nig["v"], nig["mu"]
    mu1, mu2 = mu[0], mu[1]
    v11, v12, v22 = v[0, 0], v[0, 1], v[1, 1]
    p11, p12, p22 = prec[0, 0], prec[0, 1], prec[1, 1]

    (nmu1, nmu2, nv11, nv12, nv22, np11, np12, np22, nb) = _nig_step(
        mu1, mu2, v11, v12, v22, p11, p12, p22, nig["b"], xs, ys)

    out = dict(nig)
    out.update(mu=np.array([nmu1, nmu2], np.float64),
               v=np.array([[nv11, nv12], [nv12, nv22]], np.float64),
               prec=np.array([[np11, np12], [np12, np22]], np.float64),
               a=nig["a"] + 0.5, b=nb if nb > 1e-12 else 1e-12,
               n_obs=nig["n_obs"] + 1.0)
    return out


def _nig_step(mu1, mu2, v11, v12, v22, p11, p12, p22, b, xs, ys):
    """One Sherman-Morrison rank-1 NIG update in explicit 2x2 component
    form, on standardized (xs, ys).  Polymorphic over scalars and (T,)
    float64 vectors: numpy elementwise ufuncs are IEEE-deterministic per
    element, so evaluating these expressions lane-wise over T tasks is
    bit-identical to evaluating them one task at a time — the property
    `nig_update_batch` is built on."""
    # vp = V phi with phi = (1, xs);  denom = 1 + phi^T V phi
    vp1 = v11 + v12 * xs
    vp2 = v12 + v22 * xs
    denom = 1.0 + (vp1 + xs * vp2)
    nv11 = v11 - vp1 * vp1 / denom
    nv12 = v12 - vp1 * vp2 / denom
    nv22 = v22 - vp2 * vp2 / denom
    np11 = p11 + 1.0
    np12 = p12 + xs
    np22 = p22 + xs * xs
    r1 = (p11 * mu1 + p12 * mu2) + ys            # prec mu + phi y
    r2 = (p12 * mu1 + p22 * mu2) + xs * ys
    nmu1 = nv11 * r1 + nv12 * r2
    nmu2 = nv12 * r1 + nv22 * r2
    qo = (mu1 * p11 + mu2 * p12) * mu1 + (mu1 * p12 + mu2 * p22) * mu2
    qn = (nmu1 * np11 + nmu2 * np12) * nmu1 \
        + (nmu1 * np12 + nmu2 * np22) * nmu2
    # callers floor nb at 1e-12 (np.maximum for vectors, a branch for
    # scalars — identical values, and the scalar chain stays free of
    # numpy per-op dispatch)
    nb = b + 0.5 * (ys * ys + qo - qn)
    return nmu1, nmu2, nv11, nv12, nv22, np11, np12, np22, nb


def _nig_fold_np(mu, v, prec, a, b, n_obs, xs, ys, m):
    """Vectorized masked fold: apply K standardized observations to T NIG
    states simultaneously, one scan step per observation column.

    Bit-identical to chaining `nig_update` per task: both evaluate the
    SAME `_nig_step` component expressions, and numpy float64 elementwise
    ufuncs are IEEE-deterministic per lane — vectorizing over tasks cannot
    reassociate anything (every contraction in the 2x2 algebra is written
    out; there are no BLAS dispatches whose FMA behavior could differ).
    Masked lanes keep their old state via `where` selection (denominators
    are >= 1 and b is floored, so dead lanes never produce NaNs that
    could leak through the select).
    """
    mu1, mu2 = mu[:, 0], mu[:, 1]
    v11, v12, v22 = v[:, 0, 0], v[:, 0, 1], v[:, 1, 1]
    p11, p12, p22 = prec[:, 0, 0], prec[:, 0, 1], prec[:, 1, 1]
    for k in range(xs.shape[1]):
        xk, yk, mk = xs[:, k], ys[:, k], m[:, k] > 0.0
        (nmu1, nmu2, nv11, nv12, nv22, np11, np12, np22, nb) = _nig_step(
            mu1, mu2, v11, v12, v22, p11, p12, p22, b, xk, yk)
        nb = np.maximum(nb, 1e-12)
        mu1 = np.where(mk, nmu1, mu1)
        mu2 = np.where(mk, nmu2, mu2)
        v11 = np.where(mk, nv11, v11)
        v12 = np.where(mk, nv12, v12)
        v22 = np.where(mk, nv22, v22)
        p11 = np.where(mk, np11, p11)
        p12 = np.where(mk, np12, p12)
        p22 = np.where(mk, np22, p22)
        b = np.where(mk, nb, b)
        a = np.where(mk, a + 0.5, a)
        n_obs = np.where(mk, n_obs + 1.0, n_obs)
    mu = np.stack([mu1, mu2], axis=1)
    v = np.stack([np.stack([v11, v12], 1), np.stack([v12, v22], 1)], axis=1)
    prec = np.stack([np.stack([p11, p12], 1),
                     np.stack([p12, p22], 1)], axis=1)
    return mu, v, prec, a, b, n_obs


_FOLD_VEC_MIN_TASKS = 64
"""Below this many tasks the vectorized fold's numpy per-op dispatch
overhead loses to per-task python-float chains; both are the identical
IEEE op sequence, so the size dispatch is invisible to digests."""


def _nig_chain_py(nig: dict, xrow, yrow) -> dict:
    """Per-task scalar chain on python floats: the same `_nig_step`
    component expressions `nig_update` evaluates (python float and numpy
    float64 scalar arithmetic share the hardware double ops, so results
    are bit-identical), minus numpy's per-op scalar dispatch — the fast
    form for narrow folds."""
    if not len(xrow):
        return dict(nig)
    x_mu, x_sd = float(nig["x_mu"]), float(nig["x_sd"])
    y_mu, y_sd = float(nig["y_mu"]), float(nig["y_sd"])
    mu, v, prec = nig["mu"], nig["v"], nig["prec"]
    mu1, mu2 = float(mu[0]), float(mu[1])
    v11, v12, v22 = float(v[0, 0]), float(v[0, 1]), float(v[1, 1])
    p11, p12, p22 = float(prec[0, 0]), float(prec[0, 1]), float(prec[1, 1])
    b = float(nig["b"])
    for x, y in zip(xrow, yrow):
        sx = (float(x) - x_mu) / x_sd
        sy = (float(y) - y_mu) / y_sd
        (mu1, mu2, v11, v12, v22, p11, p12, p22, b) = _nig_step(
            mu1, mu2, v11, v12, v22, p11, p12, p22, b, sx, sy)
        b = b if b > 1e-12 else 1e-12
    k = len(xrow)
    out = dict(nig)
    out.update(mu=np.array([mu1, mu2], np.float64),
               v=np.array([[v11, v12], [v12, v22]], np.float64),
               prec=np.array([[p11, p12], [p12, p22]], np.float64),
               a=nig["a"] + 0.5 * k, b=b,
               n_obs=nig["n_obs"] + float(k))
    return out


def nig_update_batch(nigs, xs, ys, impl: str = "numpy"):
    """Fold grouped observations into many streaming NIG states in ONE
    dispatch: `nigs` is a list of T states, `xs[i]`/`ys[i]` the (ragged)
    observation sequence for state i, in arrival order.  Returns T updated
    states; the inputs are not mutated.

    impl='numpy' (default) is the float64 CPU path the ingest plane uses —
    bit-identical to `[chain of nig_update]` per task (the scalar chain is
    the exactness oracle).  It size-dispatches between two forms that run
    the identical IEEE op sequence: 'chain' (per-task python-float chains;
    fastest when T is small, where numpy per-op overhead dominates) and
    'vec' (the masked (T, K) vectorized fold `_nig_fold_np`; fastest for
    wide cross-task batches).  Pass 'chain'/'vec' to force a form.
    impl='scan' runs the vmapped `lax.scan` form and 'pallas'/'interpret'
    the fused kernel (kernels.bayes_fit.nig_fold) — the device-resident
    float32 forms for TPU posterior banks, parity within kernel tolerance,
    NOT for the float64 streaming states that feed digests.
    """
    if len(xs) != len(nigs) or len(ys) != len(nigs):
        raise ValueError(f"need one observation row per state: "
                         f"{len(nigs)} states, {len(xs)}/{len(ys)} rows")
    if not nigs:
        return []
    t = len(nigs)
    kmax = 0
    for i, (xi, yi) in enumerate(zip(xs, ys)):
        if len(xi) != len(yi):
            raise ValueError(f"row {i}: len(x)={len(xi)} != len(y)={len(yi)}")
        kmax = max(kmax, len(xi))
    if kmax == 0:
        return [dict(n) for n in nigs]
    if impl == "numpy":
        impl = "chain" if t < _FOLD_VEC_MIN_TASKS else "vec"
    if impl == "chain":
        return [_nig_chain_py(n, xr, yr)
                for n, xr, yr in zip(nigs, xs, ys)]

    x = np.zeros((t, kmax), np.float64)
    y = np.zeros((t, kmax), np.float64)
    m = np.zeros((t, kmax), np.float64)
    for i, (xi, yi) in enumerate(zip(xs, ys)):
        k = len(xi)
        x[i, :k] = np.asarray(xi, np.float64)
        y[i, :k] = np.asarray(yi, np.float64)
        m[i, :k] = 1.0
    stats = np.array([[n["x_mu"], n["x_sd"], n["y_mu"], n["y_sd"]]
                      for n in nigs], np.float64)
    # standardize exactly as the scalar update does, per task
    sx = (x - stats[:, 0:1]) / stats[:, 1:2]
    sy = (y - stats[:, 2:3]) / stats[:, 3:4]
    mu = np.stack([np.asarray(n["mu"], np.float64) for n in nigs])
    v = np.stack([np.asarray(n["v"], np.float64) for n in nigs])
    prec = np.stack([np.asarray(n["prec"], np.float64) for n in nigs])
    a = np.array([n["a"] for n in nigs], np.float64)
    b = np.array([n["b"] for n in nigs], np.float64)
    n_obs = np.array([n["n_obs"] for n in nigs], np.float64)

    if impl == "vec":
        mu, v, prec, a, b, n_obs = _nig_fold_np(mu, v, prec, a, b, n_obs,
                                                sx, sy, m)
    elif impl in ("scan", "pallas", "interpret", "auto"):
        from repro.kernels import bayes_fit as _kbf
        if impl == "scan":
            fmu, fv, fprec, fb = _kbf.nig_fold_scan(
                sx, sy, m, mu, v, prec, b)
        else:
            fmu, fv, fprec, fb = _kbf.nig_fold(
                sx, sy, m, mu, v, prec, b,
                interpret=(impl == "interpret"))
        counts = m.sum(axis=1)
        mu = np.asarray(fmu, np.float64)
        v = np.asarray(fv, np.float64)
        prec = np.asarray(fprec, np.float64)
        b = np.asarray(fb, np.float64)
        a = a + 0.5 * counts
        n_obs = n_obs + counts
    else:
        raise ValueError(f"unknown impl {impl!r}")

    counts = m.sum(axis=1)
    out = []
    for i, nig in enumerate(nigs):
        o = dict(nig)
        if counts[i]:
            o.update(mu=mu[i], v=v[i], prec=prec[i],
                     a=a[i], b=b[i], n_obs=n_obs[i])
        # rows with no observations pass through VERBATIM: restacking
        # them would symmetrize v/prec ([1,0] := [0,1]) and a fitted
        # input matrix can be asymmetric in the last ulp — the scalar
        # chain (zero updates) leaves those bytes untouched
        out.append(o)
    return out


def nig_refit(nig0: dict, x: np.ndarray, y: np.ndarray) -> dict:
    """Batch posterior from the prior state `nig0` and ALL observations at
    once (closed form).  Mathematically identical to folding the points in
    one at a time with `nig_update` — the exactness oracle for tests."""
    xs = (np.asarray(x, np.float64) - nig0["x_mu"]) / nig0["x_sd"]
    ys = (np.asarray(y, np.float64) - nig0["y_mu"]) / nig0["y_sd"]
    phi = np.stack([np.ones_like(xs), xs], axis=-1)          # (N, 2)
    prec0, mu0 = nig0["prec"], nig0["mu"]
    prec_n = prec0 + phi.T @ phi
    v_n = np.linalg.inv(prec_n)
    mu_n = v_n @ (prec0 @ mu0 + phi.T @ ys)
    b_n = nig0["b"] + 0.5 * (ys @ ys + mu0 @ prec0 @ mu0
                             - mu_n @ prec_n @ mu_n)
    out = dict(nig0)
    out.update(mu=mu_n, v=v_n, prec=prec_n,
               a=nig0["a"] + 0.5 * len(xs), b=max(b_n, 1e-12),
               n_obs=nig0["n_obs"] + float(len(xs)))
    return out


# refit points are padded to a multiple of this: a full streamed ring (256)
# plus the profiling points needs three compiled programs at most
_REFIT_BUCKET = 128


def refresh_fit(fit_x, fit_y, buf_x, buf_y) -> dict:
    """Periodic evidence refresh (the maintenance plane's scalar oracle):
    re-run the MacKay fixed point over the fit-time profiling points plus
    every streamed observation retained in the buffer, in one fit.

    Streaming NIG updates are exact *given* the hyperparameters frozen at
    lift time — after hundreds of completions the (alpha, beta) evidence
    lift and the standardization no longer reflect the data.  This refit
    re-chooses both from everything observed.  Either side may be empty
    (a promoted median-fallback task has no fit-time regression data: its
    streamed-only observations are preserved and refit on their own), but
    not both.  Returns a predict_blr/nig_from_blr-compatible posterior.

    The points are padded (masked) to a multiple of `_REFIT_BUCKET` and
    fitted by the jitted `fit_blr_batch`: an eager `fit_blr` re-traces
    and compiles its fixed-point loop on every call, and a promoted task's
    buffer grows one observation at a time."""
    x = np.concatenate([np.asarray(fit_x, np.float64).ravel(),
                        np.asarray(buf_x, np.float64).ravel()])
    y = np.concatenate([np.asarray(fit_y, np.float64).ravel(),
                        np.asarray(buf_y, np.float64).ravel()])
    if x.size == 0:
        raise ValueError("refresh_fit needs at least one observation")
    n = x.size
    width = -(-n // _REFIT_BUCKET) * _REFIT_BUCKET
    xp, yp, m = (np.zeros((1, width), np.float32) for _ in range(3))
    xp[0, :n], yp[0, :n], m[0, :n] = x, y, 1.0
    return {k: np.asarray(v)[0] for k, v in
            fit_blr_batch(xp, yp, m).items()}


# the NIG fields an export reads
NIG_EXPORT_FIELDS = ("mu", "v", "a", "b", "x_mu", "x_sd", "y_mu", "y_sd",
                     "n0", "n_obs")


def nig_stack(nigs) -> dict:
    """Stack T streaming states into the (T, ...) NIG fields
    `nig_to_blr_stacked` reads."""
    return {k: np.array([n[k] for n in nigs], np.float64)
            for k in NIG_EXPORT_FIELDS}


def nig_to_blr_stacked(nig: dict) -> dict:
    """Export streaming states back to the predict_blr posterior format
    (float32 leaves), over any leading batch shape.

    The Student-t predictive scale^2 = (b/a) (1 + phi V phi) maps onto the
    Gaussian form 1/beta_prec + phi sigma phi with beta_prec = a/b and
    sigma = (b/a) V, so downstream (batched) predict code is unchanged.
    Each leaf is computed in float64 per task and rounded once to float32,
    so a stacked export equals the unbatched one bit for bit."""
    s2 = (np.asarray(nig["b"], np.float64)
          / np.asarray(nig["a"], np.float64))
    f32 = np.float32
    return {"mu": np.asarray(nig["mu"], np.float64).astype(f32),
            "sigma": (s2[..., None, None] * np.asarray(nig["v"], np.float64)
                      ).astype(f32),
            "alpha": np.ones(np.shape(s2), f32),
            "beta_prec": (1.0 / s2).astype(f32),
            "x_mu": np.asarray(nig["x_mu"], np.float64).astype(f32),
            "x_sd": np.asarray(nig["x_sd"], np.float64).astype(f32),
            "y_mu": np.asarray(nig["y_mu"], np.float64).astype(f32),
            "y_sd": np.asarray(nig["y_sd"], np.float64).astype(f32),
            "n": (np.asarray(nig["n0"], np.float64)
                  + np.asarray(nig["n_obs"], np.float64)).astype(f32)}


def nig_to_blr(nig: dict) -> dict:
    """Export one streaming state: the unbatched case of
    `nig_to_blr_stacked` (float32 arrays for mu and sigma, float32
    scalars for the rest)."""
    return {k: v[()] for k, v in nig_to_blr_stacked(nig).items()}
