"""Batched Bayesian-linear-regression fit kernel — the paper's core
computation (Section 4.5) fused for TPU: thousands of per-task models fitted
in one pass.

Each grid step processes a (block_tasks, N) tile: standardization, Gram
accumulation, and the MacKay evidence fixed-point — all with closed-form
2x2 linear algebra (eigenvalues / inverse of the symmetric Gram matrix),
so the whole fit is elementwise + tiny reductions in VMEM: one HBM read of
the (x, y, mask) tile, one write of the posterior.

Outputs (per task): mu (2,), sigma (2,2) flattened to (4,), alpha,
beta_prec, and the standardization stats — matching core.bayes.fit_blr
(the vmapped oracle in kernels/ref.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

N_ITERS = 30
EPS = 1e-9
DEFAULT_BLOCK_TASKS = 128


def _eig2(a11, a12, a22):
    """eigenvalues of [[a11,a12],[a12,a22]] (closed form, ascending)."""
    tr = a11 + a22
    det = a11 * a22 - a12 * a12
    disc = jnp.sqrt(jnp.maximum(tr * tr / 4.0 - det, 0.0))
    return tr / 2.0 - disc, tr / 2.0 + disc


def _inv2(a11, a12, a22):
    det = jnp.maximum(a11 * a22 - a12 * a12, 1e-30)
    return a22 / det, -a12 / det, a11 / det


def _bayes_kernel(x_ref, y_ref, m_ref, mu_ref, sig_ref, hyp_ref, stat_ref):
    x = x_ref[...].astype(jnp.float32)            # (bt, N)
    y = y_ref[...].astype(jnp.float32)
    m = m_ref[...].astype(jnp.float32)
    n = jnp.maximum(m.sum(axis=1), 1.0)           # (bt,)

    x_mu = (x * m).sum(1) / n
    y_mu = (y * m).sum(1) / n
    x_sd = jnp.sqrt(((x - x_mu[:, None]) ** 2 * m).sum(1) / n + EPS)
    y_sd = jnp.sqrt(((y - y_mu[:, None]) ** 2 * m).sum(1) / n + EPS)
    xs = (x - x_mu[:, None]) / x_sd[:, None] * m
    ys = (y - y_mu[:, None]) / y_sd[:, None] * m

    # Gram of the [1, x] design (masked)
    g11 = m.sum(1)                                 # sum 1*1
    g12 = xs.sum(1)
    g22 = (xs * xs).sum(1)
    p1 = ys.sum(1)                                 # phi^T y
    p2 = (xs * ys).sum(1)

    def body(_, ab):
        alpha, beta = ab
        a11 = alpha + beta * g11
        a12 = beta * g12
        a22 = alpha + beta * g22
        i11, i12, i22 = _inv2(a11, a12, a22)
        mu1 = beta * (i11 * p1 + i12 * p2)
        mu2 = beta * (i12 * p1 + i22 * p2)
        l1, l2 = _eig2(beta * g11, beta * g12, beta * g22)
        gamma = l1 / (alpha + l1) + l2 / (alpha + l2)
        # residual ||y - phi mu||^2 (masked): expand the quadratic form
        resid = ((ys - (mu1[:, None] + mu2[:, None] * xs) * m) ** 2).sum(1)
        alpha = gamma / jnp.maximum(mu1 * mu1 + mu2 * mu2, EPS)
        beta = jnp.maximum(n - gamma, EPS) / jnp.maximum(resid, EPS)
        return jnp.clip(alpha, 1e-6, 1e6), jnp.clip(beta, 1e-6, 1e8)

    ones = jnp.ones_like(n)
    alpha, beta = jax.lax.fori_loop(0, N_ITERS, body, (ones, ones))

    a11 = alpha + beta * g11
    a12 = beta * g12
    a22 = alpha + beta * g22
    i11, i12, i22 = _inv2(a11, a12, a22)
    mu1 = beta * (i11 * p1 + i12 * p2)
    mu2 = beta * (i12 * p1 + i22 * p2)

    mu_ref[...] = jnp.stack([mu1, mu2], axis=1)
    sig_ref[...] = jnp.stack([i11, i12, i12, i22], axis=1)
    hyp_ref[...] = jnp.stack([alpha, beta], axis=1)
    stat_ref[...] = jnp.stack([x_mu, x_sd, y_mu, y_sd, n], axis=1)


def bayes_fit(x: jnp.ndarray, y: jnp.ndarray, mask: jnp.ndarray, *,
              block_tasks: int = DEFAULT_BLOCK_TASKS,
              interpret: bool = False) -> dict:
    """x, y, mask: (T, N) -> posterior dict matching core.bayes.fit_blr
    (leaves stacked over T)."""
    t, n = x.shape
    block_tasks = min(block_tasks, t)
    assert t % block_tasks == 0, (t, block_tasks)
    grid = (t // block_tasks,)
    in_spec = pl.BlockSpec((block_tasks, n), lambda i: (i, 0))
    mu, sig, hyp, stat = pl.pallas_call(
        _bayes_kernel,
        grid=grid,
        in_specs=[in_spec, in_spec, in_spec],
        out_specs=[pl.BlockSpec((block_tasks, 2), lambda i: (i, 0)),
                   pl.BlockSpec((block_tasks, 4), lambda i: (i, 0)),
                   pl.BlockSpec((block_tasks, 2), lambda i: (i, 0)),
                   pl.BlockSpec((block_tasks, 5), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((t, 2), jnp.float32),
                   jax.ShapeDtypeStruct((t, 4), jnp.float32),
                   jax.ShapeDtypeStruct((t, 2), jnp.float32),
                   jax.ShapeDtypeStruct((t, 5), jnp.float32)],
        interpret=interpret,
    )(x.astype(jnp.float32), y.astype(jnp.float32), mask.astype(jnp.float32))
    return {"mu": mu, "sigma": sig.reshape(t, 2, 2),
            "alpha": hyp[:, 0], "beta_prec": hyp[:, 1],
            "x_mu": stat[:, 0], "x_sd": stat[:, 1],
            "y_mu": stat[:, 2], "y_sd": stat[:, 3], "n": stat[:, 4]}


def pad_ragged(xs, ys, min_cols: int = 2, col_bucket: int = 64):
    """Variable-length per-task observation buffers -> fixed-shape
    (T, N) float32 (x, y, mask) arrays for one batched fit dispatch.

    The maintenance plane gathers the streamed buffers of every due task
    across every tenant; their lengths are ragged (each task has seen a
    different number of completions).  Rows are right-padded to the longest
    buffer with mask=0 — the fit kernel's masked reductions make padded
    columns exact no-ops, so a (3-point, 200-point) pair costs one tile.

    N is rounded up to a `col_bucket` multiple: successive refresh passes
    see steadily-growing buffers, and without shape bucketing every pass
    would re-jit the batched fit for a new N (the same trick as the
    predict path's PREDICT_TILE)."""
    t = len(xs)
    n = max(min_cols, max((len(v) for v in xs), default=min_cols))
    if col_bucket > 1:
        n = -(-n // col_bucket) * col_bucket
    x = np.zeros((t, n), np.float32)
    y = np.zeros((t, n), np.float32)
    m = np.zeros((t, n), np.float32)
    for i, (xi, yi) in enumerate(zip(xs, ys)):
        k = len(xi)
        if k != len(yi):
            raise ValueError(f"row {i}: len(x)={k} != len(y)={len(yi)}")
        x[i, :k] = np.asarray(xi, np.float32)
        y[i, :k] = np.asarray(yi, np.float32)
        m[i, :k] = 1.0
    return x, y, m


def bayes_fit_ragged(x: jnp.ndarray, y: jnp.ndarray, mask: jnp.ndarray, *,
                     block_tasks: int = DEFAULT_BLOCK_TASKS,
                     interpret: bool = False) -> dict:
    """`bayes_fit` for any task count: rows already carry per-row masks
    (pad_ragged); the task dimension is padded to a grid-block multiple
    with fully-masked rows so a fleet refresh of, say, 130 due tasks still
    costs ONE pallas_call, then the padding rows are sliced off."""
    t = x.shape[0]
    bt = min(block_tasks, t)
    tp = -(-t // bt) * bt
    if tp != t:
        pad = ((0, tp - t), (0, 0))
        x = jnp.pad(x, pad)
        y = jnp.pad(y, pad)
        mask = jnp.pad(mask, pad)
    post = bayes_fit(x, y, mask, block_tasks=bt, interpret=interpret)
    return {k: v[:t] for k, v in post.items()}


# ---------------------------------------------------------------------------
# batched streaming-update fold (the ingest-plane hot path)
# ---------------------------------------------------------------------------
# One ingest batch = K completions spanning T tasks.  The scalar path pays
# one Sherman-Morrison rank-1 update per completion; the fold applies each
# task's observation sequence in order, but runs ALL tasks' sequences
# simultaneously — a (T, K) masked scan where step k advances every task
# that still has a k-th observation.  All 2x2 algebra is unrolled to
# elementwise component arithmetic, so one grid step is K rounds of
# vector ops over a (block_tasks,) tile: one HBM read of the tile, one
# write of the folded states.  Inputs are PRE-standardized (the caller
# owns the frozen affine coords); a and n_obs are closed-form in the mask
# counts and stay host-side.

DEFAULT_FOLD_COLS = 8


def _nig_fold_kernel(x_ref, y_ref, m_ref, mu_ref, v_ref, prec_ref, b_ref,
                     omu_ref, ov_ref, oprec_ref, ob_ref):
    xs = x_ref[...]                                # (bt, K) standardized
    ys = y_ref[...]
    m = m_ref[...]
    mu1, mu2 = mu_ref[...][:, 0], mu_ref[...][:, 1]
    v = v_ref[...]                                 # (bt, 4) [00,01,10,11]
    v11, v12, v22 = v[:, 0], v[:, 1], v[:, 3]
    p = prec_ref[...]
    p11, p12, p22 = p[:, 0], p[:, 1], p[:, 3]
    b = b_ref[...][:, 0]

    for k in range(xs.shape[1]):                   # K is static: unrolled
        xk, yk, mk = xs[:, k], ys[:, k], m[:, k]
        # vp = V phi with phi = (1, xk)
        vp1 = v11 + v12 * xk
        vp2 = v12 + v22 * xk
        denom = 1.0 + (vp1 + xk * vp2)             # 1 + phi^T V phi
        nv11 = v11 - vp1 * vp1 / denom
        nv12 = v12 - vp1 * vp2 / denom
        nv22 = v22 - vp2 * vp2 / denom
        np11 = p11 + 1.0
        np12 = p12 + xk
        np22 = p22 + xk * xk
        r1 = (p11 * mu1 + p12 * mu2) + yk          # prec mu + phi y
        r2 = (p12 * mu1 + p22 * mu2) + xk * yk
        nmu1 = nv11 * r1 + nv12 * r2
        nmu2 = nv12 * r1 + nv22 * r2
        qo = (mu1 * p11 + mu2 * p12) * mu1 + (mu1 * p12 + mu2 * p22) * mu2
        qn = (nmu1 * np11 + nmu2 * np12) * nmu1 \
            + (nmu1 * np12 + nmu2 * np22) * nmu2
        nb = jnp.maximum(b + 0.5 * (yk * yk + qo - qn), 1e-12)
        sel = mk > 0.0
        mu1 = jnp.where(sel, nmu1, mu1)
        mu2 = jnp.where(sel, nmu2, mu2)
        v11 = jnp.where(sel, nv11, v11)
        v12 = jnp.where(sel, nv12, v12)
        v22 = jnp.where(sel, nv22, v22)
        p11 = jnp.where(sel, np11, p11)
        p12 = jnp.where(sel, np12, p12)
        p22 = jnp.where(sel, np22, p22)
        b = jnp.where(sel, nb, b)

    omu_ref[...] = jnp.stack([mu1, mu2], axis=1)
    ov_ref[...] = jnp.stack([v11, v12, v12, v22], axis=1)
    oprec_ref[...] = jnp.stack([p11, p12, p12, p22], axis=1)
    ob_ref[...] = b[:, None]


def nig_fold(xs, ys, mask, mu, v, prec, b, *,
             block_tasks: int = DEFAULT_BLOCK_TASKS,
             col_bucket: int = DEFAULT_FOLD_COLS,
             interpret: bool = False):
    """Fused masked fold of (T, K) standardized observations into T NIG
    states.  mu: (T,2); v, prec: (T,2,2); b: (T,).  Returns the updated
    (mu, v, prec, b).  Columns are bucketed (the kernel unrolls K) and the
    task dim padded to a block multiple, so ragged ingest batches of any
    shape cost one pallas_call."""
    t, k = np.shape(xs)
    kp = max(1, -(-k // col_bucket) * col_bucket)
    bt = min(block_tasks, max(t, 1))
    tp = -(-t // bt) * bt

    def pad(arr, cols=None):
        arr = jnp.asarray(arr, jnp.float32).reshape(t, -1)
        want = cols if cols is not None else arr.shape[1]
        return jnp.pad(arr, ((0, tp - t), (0, want - arr.shape[1])))

    xq, yq, mq = pad(xs, kp), pad(ys, kp), pad(mask, kp)
    muq = pad(jnp.asarray(mu).reshape(t, 2))
    vq = pad(jnp.asarray(v).reshape(t, 4))
    pq = pad(jnp.asarray(prec).reshape(t, 4))
    bq = pad(jnp.asarray(b).reshape(t, 1))

    obs_spec = pl.BlockSpec((bt, kp), lambda i: (i, 0))
    two = pl.BlockSpec((bt, 2), lambda i: (i, 0))
    four = pl.BlockSpec((bt, 4), lambda i: (i, 0))
    one = pl.BlockSpec((bt, 1), lambda i: (i, 0))
    omu, ov, oprec, ob = pl.pallas_call(
        _nig_fold_kernel,
        grid=(tp // bt,),
        in_specs=[obs_spec, obs_spec, obs_spec, two, four, four, one],
        out_specs=[two, four, four, one],
        out_shape=[jax.ShapeDtypeStruct((tp, 2), jnp.float32),
                   jax.ShapeDtypeStruct((tp, 4), jnp.float32),
                   jax.ShapeDtypeStruct((tp, 4), jnp.float32),
                   jax.ShapeDtypeStruct((tp, 1), jnp.float32)],
        interpret=interpret,
    )(xq, yq, mq, muq, vq, pq, bq)
    return (omu[:t], ov[:t].reshape(t, 2, 2),
            oprec[:t].reshape(t, 2, 2), ob[:t, 0])


@jax.jit
def nig_fold_scan(xs, ys, mask, mu, v, prec, b):
    """vmapped per-task sequential `lax.scan` form of the fold — the jit
    reference for the kernel, and the dispatch-friendly shape for chaining
    the fold into larger jitted programs.  Same signature as `nig_fold`."""
    def one(xr, yr, mr, mu0, v0, p0, b0):
        def step(carry, inp):
            cmu, cv, cp, cb = carry
            xk, yk, mk = inp
            phi = jnp.stack([jnp.ones_like(xk), xk])
            vp = cv @ phi
            denom = 1.0 + phi @ vp
            v_n = cv - jnp.outer(vp, vp) / denom
            p_n = cp + jnp.outer(phi, phi)
            mu_n = v_n @ (cp @ cmu + phi * yk)
            b_n = jnp.maximum(
                cb + 0.5 * (yk * yk + cmu @ cp @ cmu - mu_n @ p_n @ mu_n),
                1e-12)
            sel = mk > 0.0
            return (jnp.where(sel, mu_n, cmu), jnp.where(sel, v_n, cv),
                    jnp.where(sel, p_n, cp), jnp.where(sel, b_n, cb)), 0.0
        (muf, vf, pf, bf), _ = jax.lax.scan(
            step, (mu0, v0, p0, b0), (xr, yr, mr))
        return muf, vf, pf, bf

    f32 = lambda z: jnp.asarray(z, jnp.float32)
    return jax.vmap(one)(f32(xs), f32(ys), f32(mask),
                         f32(mu), f32(v), f32(prec), f32(b))


# ---------------------------------------------------------------------------
# batched posterior predictive (the prediction-service hot path)
# ---------------------------------------------------------------------------
# One query = (per-query gathered posterior, input size).  Everything is
# elementwise in the query dimension — means and stds for tens of thousands
# of (task, node, input) requests come back in a single fused pass instead
# of one predict_blr dispatch per query.  Queries are laid out (rows, 128)
# to match the fp32 VPU lane width, and every input of a query rides in one
# packed (PREDICT_PLANES, rows, 128) array: one transfer, one kernel input.

LANE = 128
DEFAULT_BLOCK_ROWS = 8
PREDICT_TILE = LANE * DEFAULT_BLOCK_ROWS     # queries per grid step
# one float32 plane per scalar of a query: its input size, then its gathered
# posterior (mu, the sigma triangle, beta_prec, the x/y standardization)
PREDICT_PLANES = 11
# padded lanes: unit precision and scales keep the math finite
PLANE_PAD = (0.0, 0.0, 0.0, 0.0, 0.0, 0.0, 1.0, 0.0, 1.0, 0.0, 1.0)


def _predict_kernel(p_ref, out_ref):
    x = p_ref[0]                                     # (br, LANE)
    mu1 = p_ref[1]                                   # component planes
    mu2 = p_ref[2]
    s11, s12, s22 = p_ref[3], p_ref[4], p_ref[5]
    beta = p_ref[6]
    x_mu, x_sd = p_ref[7], p_ref[8]
    y_mu, y_sd = p_ref[9], p_ref[10]

    xs = (x - x_mu) / x_sd
    mean_s = mu1 + mu2 * xs
    var_s = 1.0 / beta + s11 + 2.0 * s12 * xs + s22 * xs * xs
    out_ref[0] = mean_s * y_sd + y_mu
    out_ref[1] = jnp.sqrt(jnp.maximum(var_s, 0.0)) * y_sd


def predict_plane_rows(x, post) -> tuple:
    """The PREDICT_PLANES rows of the packed layout, in order, each (Q,):
    views of x (Q,) and the leaves gathered per query (Q, ...)."""
    mu, sig = post["mu"], post["sigma"]
    return (x, mu[:, 0], mu[:, 1], sig[:, 0, 0], sig[:, 0, 1], sig[:, 1, 1],
            post["beta_prec"], post["x_mu"], post["x_sd"], post["y_mu"],
            post["y_sd"])


def pack_predict_planes(x: np.ndarray, post: dict) -> np.ndarray:
    """Host side of the predictive: NumPy x (Q,) and gathered leaves ->
    a fresh float32 (PREDICT_PLANES, rows, LANE) buffer, Q padded up to a
    PREDICT_TILE multiple with PLANE_PAD, ready for one transfer.  The
    bucket keeps one compiled program for every Q up to the tile."""
    q = len(x)
    qp = -(-max(q, 1) // PREDICT_TILE) * PREDICT_TILE
    buf = np.empty((PREDICT_PLANES, qp), np.float32)
    for i, row in enumerate(predict_plane_rows(x, post)):
        buf[i, :q] = row
    buf[:, q:] = np.asarray(PLANE_PAD, np.float32)[:, None]
    return buf.reshape(PREDICT_PLANES, qp // LANE, LANE)


def bayes_predict_planes(planes: jnp.ndarray, *, interpret: bool = False):
    """Packed planes (PREDICT_PLANES, rows, LANE), rows a DEFAULT_BLOCK_ROWS
    multiple -> (2, rows, LANE) float32: mean, then std."""
    n_planes, rows, lane = planes.shape
    assert n_planes == PREDICT_PLANES and lane == LANE, planes.shape
    assert rows % DEFAULT_BLOCK_ROWS == 0, rows
    spec = lambda k: pl.BlockSpec((k, DEFAULT_BLOCK_ROWS, LANE),
                                  lambda i: (0, i, 0))
    return pl.pallas_call(
        _predict_kernel,
        grid=(rows // DEFAULT_BLOCK_ROWS,),
        in_specs=[spec(PREDICT_PLANES)],
        out_specs=spec(2),
        out_shape=jax.ShapeDtypeStruct((2, rows, LANE), jnp.float32),
        interpret=interpret,
    )(planes)


def bayes_predict(x: jnp.ndarray, post: dict, *, interpret: bool = False):
    """x: (Q,) query inputs; post: posterior dict with leading dim Q
    (already gathered per query).  Returns (mean, std), each (Q,).  Stacks
    and pads the planes with jnp (traceable), then the planes form."""
    q = x.shape[0]
    qp = -(-q // PREDICT_TILE) * PREDICT_TILE
    rows = jnp.stack([jnp.asarray(r, jnp.float32)
                      for r in predict_plane_rows(x, post)])
    pad = jnp.broadcast_to(jnp.asarray(PLANE_PAD, jnp.float32)[:, None],
                           (PREDICT_PLANES, qp - q))
    planes = jnp.concatenate([rows, pad], axis=1)
    out = bayes_predict_planes(
        planes.reshape(PREDICT_PLANES, qp // LANE, LANE), interpret=interpret)
    out = out.reshape(2, qp)
    return out[0, :q], out[1, :q]
