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
              interpret: bool = False) -> tuple:
    """x, y, mask: (T, N) -> the kernel's four float32 outputs (T, 2) mu,
    (T, 4) sigma, (T, 2) (alpha, beta_prec) and (T, 5) (x_mu, x_sd, y_mu,
    y_sd, n): the columns of FIT_LAYOUT, in order."""
    t, n = x.shape
    block_tasks = min(block_tasks, t)
    assert t % block_tasks == 0, (t, block_tasks)
    grid = (t // block_tasks,)
    in_spec = pl.BlockSpec((block_tasks, n), lambda i: (i, 0))
    return tuple(pl.pallas_call(
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
    )(x.astype(jnp.float32), y.astype(jnp.float32), mask.astype(jnp.float32)))


# The fit's packed result: per task one float32 row of FIT_COLS columns,
# the posterior leaves side by side in this order (sigma row-major).
FIT_LAYOUT = (("mu", 2), ("sigma", 4), ("alpha", 1), ("beta_prec", 1),
              ("x_mu", 1), ("x_sd", 1), ("y_mu", 1), ("y_sd", 1), ("n", 1))
FIT_COLS = sum(k for _, k in FIT_LAYOUT)


def fit_columns(post: dict) -> jnp.ndarray:
    """Posterior leaves stacked over T -> (T, FIT_COLS) float32, the
    layout of the kernel's concatenated outputs (traceable)."""
    t = post["mu"].shape[0]
    return jnp.concatenate([jnp.reshape(post[k], (t, w)).astype(jnp.float32)
                            for k, w in FIT_LAYOUT], axis=1)


def pack_fit_planes(x: np.ndarray, y: np.ndarray, mask: np.ndarray,
                    block_tasks: int = DEFAULT_BLOCK_TASKS) -> np.ndarray:
    """Host side of the fleet fit: padded (T, N) x, y, mask (`pad_ragged`)
    -> one fresh float32 (3, Tp, N) buffer, ready for one transfer.  T is
    padded up to a `block_tasks` multiple with fully masked rows, which the
    kernel's masked reductions leave exact, so a pass of any task count up
    to the bucket reuses one compiled program; N is already bucketed."""
    t, n = np.shape(x)
    tp = -(-max(t, 1) // block_tasks) * block_tasks
    buf = np.empty((3, tp, n), np.float32)
    buf[0, :t], buf[1, :t], buf[2, :t] = x, y, mask
    buf[:, t:] = 0.0
    return buf


def unpack_fit(out: np.ndarray, t: int) -> dict:
    """The read-back (Tp, FIT_COLS) result -> the posterior dict of its
    first `t` rows, float64 NumPy leaves (sigma (t, 2, 2))."""
    rows = np.asarray(out[:t], np.float64)
    post, c = {}, 0
    for k, w in FIT_LAYOUT:
        post[k] = rows[:, c] if w == 1 else rows[:, c:c + w]
        c += w
    post["sigma"] = post["sigma"].reshape(t, 2, 2)
    return post


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
