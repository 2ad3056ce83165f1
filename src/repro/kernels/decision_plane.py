"""Device-resident fused decision plane kernels: predict -> quantile cost ->
upward rank -> candidate-EFT sweep in single dispatches.

The PR-4 decision plane batches the *prediction* into one kernel call but
runs HEFT itself (ranks + the per-task insertion sweep) through Python
loops on the host.  This module moves the whole pipeline into compiled
dispatches:

  * `fused_cost` — Pallas kernel (jnp reference: `fused_cost_ref`): from
    the stacked posterior leaves straight to the (T, N) quantile cost
    matrix W = max(mean, 1e-3)*f + z*(std*f), fusing the posterior
    predictive, extrapolation-factor scaling and the mean + z*std quantile
    shift into one pass over the task rows.

  * `upward_rank` — the HEFT reverse-topo rank recurrence as one
    `fori_loop` dispatch (w_avg and the cached avg-comm terms come in as
    arrays; only max/add ops, so float64 results are bitwise what the
    host recurrence computes).

  * `eft_sweep` — the insertion-based candidate-EFT sweep as ONE jitted
    `fori_loop` dispatch: per-node busy intervals live in (N, S) begin/end
    arrays, the per-task gap search is a fused select + min-reduce, and
    placements are in-place row scatters.  `eft_sweep_many` vmaps it over
    a megabatch of workflows sharing one cluster (padded/masked task
    rows), so B tenant replans cost one dispatch.  `eft_sweep_pallas` is
    the Pallas kernel form (VMEM-resident interval stacks, min+iota
    argmin), interpret-testable off-TPU.

Bit-parity (the property tests assert bitwise-equal schedules vs
`sched.heft.heft_schedule_matrix` when run in float64):

  * every arithmetic term (`finish + comm`, `cand + dur`, `est + dur`,
    `gb8 / gbps`) is a single IEEE add/div — no multi-term sums anywhere,
    so there is nothing for XLA to reassociate or FMA-contract;
  * the sorted interval invariant makes the gap search exact: ends are
    non-decreasing, so the candidate start at slot k is
    max(ready, end[k-1]) and candidates are non-decreasing in k — the
    first fitting slot is the *minimum* candidate among fits, computed as
    one select + min-reduce (no argmax/gather needed);
  * first-minimum tie-breaking of `jnp.argmin` matches `np.argmin`;
  * the insertion point is counting-searchsorted
    (#begins < est, advancing past equal-begin/earlier-end zero-length
    slots), exactly the reference's `list.sort()` tuple order.

Padding conventions: interval begins pad +inf (a fit past the last
interval always exists while cnt <= S-2), ends pad +inf (keeps candidates
non-decreasing across the pad boundary).  Masked task rows
(`order_arr == -1`, from megabatch padding) insert the (inf, inf)
interval — bitwise a no-op on the pad columns — and scatter their outputs
to a dummy row, so padded and unpadded sweeps agree exactly.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANE = 128
DEFAULT_SLOTS = 48          # busy-interval columns per node (auto-doubled
                            # by the host wrapper on overflow)


# ---------------------------------------------------------------------------
# fused predict -> quantile cost
# ---------------------------------------------------------------------------

def fused_cost_ref(x, post, factors, z):
    """jnp reference: (T,) inputs + stacked posterior leaves (T, ...) +
    (T, N) factors -> (T, N) quantile cost matrix in one expression.

    Mirrors `kernels.bayes_fit._predict_kernel` followed by
    `store.compute.scale` and the mean + z*std quantile shift of
    `PredictionMatrix.costs` — fused so the scaled mean/std matrices are
    never materialized."""
    xs = (x - post["x_mu"]) / post["x_sd"]
    mean_s = post["mu"][:, 0] + post["mu"][:, 1] * xs
    var_s = (1.0 / post["beta_prec"] + post["sigma"][:, 0, 0]
             + 2.0 * post["sigma"][:, 0, 1] * xs
             + post["sigma"][:, 1, 1] * xs * xs)
    mean = mean_s * post["y_sd"] + post["y_mu"]
    std = jnp.sqrt(jnp.maximum(var_s, 0.0)) * post["y_sd"]
    w = jnp.maximum(mean, 1e-3)[:, None] * factors
    if z != 0.0:
        w = w + z * (std[:, None] * factors)
    return w


def _cost_kernel(x_ref, post_ref, f_ref, w_ref, *, z):
    x = x_ref[:, 0]                                  # (bt,)
    mu1, mu2 = post_ref[0, :, 0], post_ref[1, :, 0]
    s11, s12, s22 = post_ref[2, :, 0], post_ref[3, :, 0], post_ref[4, :, 0]
    beta = post_ref[5, :, 0]
    x_mu, x_sd = post_ref[6, :, 0], post_ref[7, :, 0]
    y_mu, y_sd = post_ref[8, :, 0], post_ref[9, :, 0]

    xs = (x - x_mu) / x_sd
    mean_s = mu1 + mu2 * xs
    var_s = 1.0 / beta + s11 + 2.0 * s12 * xs + s22 * xs * xs
    mean = mean_s * y_sd + y_mu
    std = jnp.sqrt(jnp.maximum(var_s, 0.0)) * y_sd

    f = f_ref[...]                                   # (bt, Np)
    w = jnp.maximum(mean, 1e-3)[:, None] * f
    if z != 0.0:
        w = w + z * (std[:, None] * f)
    w_ref[...] = w


def fused_cost(x, post, factors, z: float = 0.0, *,
               block_tasks: int = 8, interpret: bool = False):
    """Pallas fused cost: x (T,), posterior leaves with leading dim T,
    factors (T, N) -> W (T, N) float32.  Tasks tile the sublane axis,
    nodes the lane axis; the per-task posterior scalars ride along as a
    (10, T, 1) plane stack.  N should be a LANE multiple on real TPUs
    (interpret mode takes any shape)."""
    t, n = factors.shape
    bt = min(block_tasks, t)
    tp = -(-t // bt) * bt

    def col(v):
        v = jnp.asarray(v, jnp.float32).reshape(t)
        return jnp.pad(v, (0, tp - t))[:, None]          # (tp, 1)

    planes = jnp.stack([
        col(post["mu"][:, 0]), col(post["mu"][:, 1]),
        col(post["sigma"][:, 0, 0]), col(post["sigma"][:, 0, 1]),
        col(post["sigma"][:, 1, 1]),
        col(post["beta_prec"]) + (1.0 - col(jnp.ones(t))),   # pad-safe
        col(post["x_mu"]), col(post["x_sd"]) + (1.0 - col(jnp.ones(t))),
        col(post["y_mu"]), col(post["y_sd"]) + (1.0 - col(jnp.ones(t))),
    ])
    xq = col(x)
    f = jnp.pad(jnp.asarray(factors, jnp.float32), ((0, tp - t), (0, 0)))

    w = pl.pallas_call(
        functools.partial(_cost_kernel, z=float(z)),
        grid=(tp // bt,),
        in_specs=[pl.BlockSpec((bt, 1), lambda i: (i, 0)),
                  pl.BlockSpec((10, bt, 1), lambda i: (0, i, 0)),
                  pl.BlockSpec((bt, n), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((bt, n), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((tp, n), jnp.float32),
        interpret=interpret,
    )(xq, planes, f)
    return w[:t]


# ---------------------------------------------------------------------------
# upward-rank recurrence
# ---------------------------------------------------------------------------

@jax.jit
def upward_rank(w_avg, avg_comm, succ_pad):
    """HEFT reverse-topo rank recurrence as one dispatch.

    w_avg (T,): per-task mean cost (row cumsum / N, computed upstream);
    avg_comm (T,): the W-independent average pairwise comm term;
    succ_pad (T, M): successor rows in topo order, -1 padded.  Only
    max/add ops, so float64 in -> bitwise the host recurrence out."""
    t = w_avg.shape[0]

    def body(k, rank):
        i = t - 1 - k
        s = succ_pad[i]
        sv = jnp.maximum(s, 0)
        cand = jnp.where(s >= 0, avg_comm[i] + rank[sv], 0.0)
        best = jnp.maximum(jnp.max(cand, initial=0.0), 0.0)
        return rank.at[i].set(w_avg[i] + best)

    return jax.lax.fori_loop(0, t, body, jnp.zeros_like(w_avg))


# ---------------------------------------------------------------------------
# candidate-EFT sweep (jit reference / production host path)
# ---------------------------------------------------------------------------

def _sweep(W, order_arr, dep_rows, gb8, ready0, avail, same, gbps_min,
           S: int):
    """One workflow's insertion sweep.  All arrays row-indexed by topo
    position; `order_arr` lists rows in rank order (-1 = padded/masked).
    Returns (assign, est, eft, cnt): assignments as node columns, start /
    finish times, and final interval counts (cnt.max() > S - 1 means the
    interval stacks overflowed and the caller must retry with larger S).
    """
    T, N = W.shape
    f = W.dtype
    inf = jnp.asarray(jnp.inf, f)
    ninf = -inf
    has = avail > 0.0
    # interval begins pad +inf (a fit past the last interval always
    # exists), ends pad +inf (candidates stay non-decreasing across the
    # pad); node_available seeds a [0, avail) prefix like the reference
    b0 = jnp.full((N, S), inf, f).at[:, 0].set(jnp.where(has, 0.0, inf))
    b1 = jnp.full((N, S), inf, f).at[:, 0].set(jnp.where(has, avail, inf))
    # outputs scatter by topo row; row T is the dump slot for masked tasks
    assignA = jnp.zeros(T + 1, jnp.int32)
    finishA = jnp.zeros(T + 1, f)
    estA = jnp.zeros(T + 1, f)
    eftA = jnp.zeros(T + 1, f)
    # commRow[d] = comm seconds from d's placed node to every node,
    # computed once at placement (deps then pay one gather, not a row of
    # pairwise-minimum lookups per successor)
    commRow = jnp.zeros((T + 1, N), f)
    ar = jnp.arange(S)

    def body(t, carry):
        b0, b1, assignA, finishA, estA, eftA, commRow = carry
        o = order_arr[t]
        valid = o >= 0
        i = jnp.maximum(o, 0)
        drows = dep_rows[i]
        ds = jnp.minimum(jnp.maximum(drows, 0), T)
        dcand = finishA[ds][:, None] + commRow[ds]           # (D, N)
        dcand = jnp.where((drows >= 0)[:, None], dcand, ninf)
        ready = jnp.maximum(ready0[i], dcand.max(axis=0))
        dur = W[i]
        # gap search: cand[k] = max(ready, end[k-1]) is non-decreasing in
        # k, so the first fitting slot is the MINIMUM candidate among fits
        # — one fused select + min-reduce, no argmax/gather
        prev = jnp.concatenate(
            [jnp.full((N, 1), ninf, f), b1[:, :-1]], axis=1)
        cand = jnp.maximum(ready[:, None], prev)
        fits = cand + dur[:, None] <= b0
        est = jnp.min(jnp.where(fits, cand, inf), axis=1)
        eft = est + dur
        j = jnp.argmin(eft).astype(jnp.int32)
        estj = est[j]
        eftj = eft[j]
        # masked rows insert (inf, inf): bitwise a no-op on the pad
        # columns, so padded megabatch lanes never perturb real nodes
        est_ins = jnp.where(valid, estj, inf)
        eft_ins = jnp.where(valid, eftj, inf)
        b0j = b0[j]
        b1j = b1[j]
        # counting searchsorted + zero-length-slot tie advance (the
        # reference's (begin, end) tuple sort order)
        pos = (jnp.sum(b0j < est_ins)
               + jnp.sum((b0j == est_ins) & (b1j < eft_ins)))
        nb0 = jnp.where(ar < pos, b0j,
                        jnp.where(ar == pos, est_ins, jnp.roll(b0j, 1)))
        nb1 = jnp.where(ar < pos, b1j,
                        jnp.where(ar == pos, eft_ins, jnp.roll(b1j, 1)))
        b0 = b0.at[j].set(nb0)
        b1 = b1.at[j].set(nb1)
        iw = jnp.where(valid, i, T).astype(jnp.int32)
        assignA = assignA.at[iw].set(j)
        finishA = finishA.at[iw].set(eftj)
        estA = estA.at[iw].set(estj)
        eftA = eftA.at[iw].set(eftj)
        commRow = commRow.at[iw].set(
            jnp.where(same[j], 0.0, gb8[i] / gbps_min[j]))
        return b0, b1, assignA, finishA, estA, eftA, commRow

    carry = (b0, b1, assignA, finishA, estA, eftA, commRow)
    carry = jax.lax.fori_loop(0, W.shape[0], body, carry)
    b0, _, assignA, _, estA, eftA, _ = carry
    cnt = jnp.sum(b0 < inf, axis=1).astype(jnp.int32)
    return assignA[:-1], estA[:-1], eftA[:-1], cnt


eft_sweep = jax.jit(_sweep, static_argnames=("S",))

# megabatch: vmap over workflows sharing one cluster (same/gbps shared);
# per-workflow arrays are padded to common (T, D) with order_arr -1 rows
@functools.partial(jax.jit, static_argnames=("S",))
def eft_sweep_many(W, order_arr, dep_rows, gb8, ready0, avail,
                   same, gbps_min, *, S):
    fn = jax.vmap(
        lambda w, o, d, g, r, a: _sweep(w, o, d, g, r, a,
                                        same, gbps_min, S))
    return fn(W, order_arr, dep_rows, gb8, ready0, avail)


# ---------------------------------------------------------------------------
# candidate-EFT sweep (Pallas kernel form)
# ---------------------------------------------------------------------------

def _sweep_kernel(w_ref, order_ref, dep_ref, gb8_ref, ready0_ref, avail_ref,
                  same_ref, gbps_ref, assign_ref, est_ref, eft_ref, cnt_ref,
                  b0_ref, b1_ref, comm_ref, fin_ref):
    T = w_ref.shape[0]
    Np = w_ref.shape[1]
    S = b0_ref.shape[1]
    D = dep_ref.shape[1]
    inf = jnp.float32(jnp.inf)
    ninf = -inf

    avail = avail_ref[0, :]                                   # (Np,)
    has = avail > 0.0
    col = jax.lax.broadcasted_iota(jnp.int32, (Np, S), 1)
    first = col == 0
    b0_ref[...] = jnp.where(first & has[:, None], 0.0, inf)
    b1_ref[...] = jnp.where(first & has[:, None],
                            avail[:, None] + jnp.zeros((Np, S), jnp.float32),
                            inf)
    comm_ref[...] = jnp.zeros((T + 1, Np), jnp.float32)
    fin_ref[...] = jnp.zeros((T + 1, 1), jnp.float32)
    iota_n = jax.lax.broadcasted_iota(jnp.int32, (1, Np), 1)[0]
    ar = jax.lax.broadcasted_iota(jnp.int32, (1, S), 1)[0]

    def body(t, _):
        o = order_ref[t, 0]
        valid = o >= 0
        i = jnp.maximum(o, 0)
        ready = ready0_ref[pl.ds(i, 1), :][0]
        for k in range(D):                                    # unrolled
            d = dep_ref[pl.ds(i, 1), pl.ds(k, 1)][0, 0]
            dv = d >= 0
            dsafe = jnp.maximum(d, 0)
            dfin = fin_ref[pl.ds(dsafe, 1), pl.ds(0, 1)][0, 0]
            crow = comm_ref[pl.ds(dsafe, 1), :][0]
            ready = jnp.where(dv, jnp.maximum(ready, dfin + crow), ready)
        dur = w_ref[pl.ds(i, 1), :][0]
        b0 = b0_ref[...]
        b1 = b1_ref[...]
        prev = jnp.where(first, ninf, jnp.roll(b1, 1, axis=1))
        cand = jnp.maximum(ready[:, None], prev)
        fits = cand + dur[:, None] <= b0
        est = jnp.min(jnp.where(fits, cand, inf), axis=1)
        eft = est + dur
        # first-minimum argmin via min + iota (no 1D argmin on TPU)
        m = jnp.min(eft)
        j = jnp.min(jnp.where(eft == m, iota_n, Np))
        estj = jnp.min(jnp.where(iota_n == j, est, inf))
        eftj = estj + jnp.min(jnp.where(iota_n == j, dur, inf))
        est_ins = jnp.where(valid, estj, inf)
        eft_ins = jnp.where(valid, eftj, inf)
        b0j = b0_ref[pl.ds(j, 1), :][0]
        b1j = b1_ref[pl.ds(j, 1), :][0]
        pos = (jnp.sum((b0j < est_ins).astype(jnp.int32))
               + jnp.sum(((b0j == est_ins) & (b1j < eft_ins))
                         .astype(jnp.int32)))
        sb0 = jnp.where(first[0], ninf, jnp.roll(b0j, 1))
        sb1 = jnp.where(first[0], ninf, jnp.roll(b1j, 1))
        nb0 = jnp.where(ar < pos, b0j, jnp.where(ar == pos, est_ins, sb0))
        nb1 = jnp.where(ar < pos, b1j, jnp.where(ar == pos, eft_ins, sb1))
        b0_ref[pl.ds(j, 1), :] = nb0[None, :]
        b1_ref[pl.ds(j, 1), :] = nb1[None, :]
        iw = jnp.where(valid, i, T)
        fin_ref[pl.ds(iw, 1), pl.ds(0, 1)] = eftj.reshape(1, 1)
        crow_new = jnp.where(same_ref[j] != 0.0, 0.0,
                             gb8_ref[i, 0] / gbps_ref[j])
        comm_ref[pl.ds(iw, 1), :] = crow_new[None, :]
        assign_ref[pl.ds(iw, 1), pl.ds(0, 1)] = j.reshape(1, 1)
        est_ref[pl.ds(iw, 1), pl.ds(0, 1)] = estj.reshape(1, 1)
        eft_ref[pl.ds(iw, 1), pl.ds(0, 1)] = eftj.reshape(1, 1)
        return 0

    jax.lax.fori_loop(0, T, body, 0)
    cnt_ref[...] = jnp.sum((b0_ref[...] < inf).astype(jnp.int32), axis=1,
                           keepdims=True).reshape(1, Np)


def eft_sweep_pallas(W, order_arr, dep_rows, gb8, ready0, avail, same,
                     gbps_min, *, S: int = DEFAULT_SLOTS,
                     interpret: bool = False):
    """Pallas kernel form of `eft_sweep` (float32): the interval stacks,
    comm rows and finish times stay VMEM-resident across the whole sweep —
    one kernel launch schedules the workflow.  Returns (assign, est, eft,
    cnt) like `eft_sweep`.  Run with interpret=True off-TPU; on real TPUs
    pad N to a LANE multiple."""
    T, N = W.shape
    f32 = jnp.float32
    i32 = jnp.int32
    outs = pl.pallas_call(
        _sweep_kernel,
        out_shape=[jax.ShapeDtypeStruct((T + 1, 1), i32),     # assign
                   jax.ShapeDtypeStruct((T + 1, 1), f32),     # est
                   jax.ShapeDtypeStruct((T + 1, 1), f32),     # eft
                   jax.ShapeDtypeStruct((1, N), i32),         # cnt
                   jax.ShapeDtypeStruct((N, S), f32),         # b0 (work)
                   jax.ShapeDtypeStruct((N, S), f32),         # b1 (work)
                   jax.ShapeDtypeStruct((T + 1, N), f32),     # comm (work)
                   jax.ShapeDtypeStruct((T + 1, 1), f32)],    # fin (work)
        interpret=interpret,
    )(jnp.asarray(W, f32), jnp.asarray(order_arr, i32).reshape(T, 1),
      jnp.asarray(dep_rows, i32), jnp.asarray(gb8, f32).reshape(T, 1),
      jnp.asarray(ready0, f32), jnp.asarray(avail, f32).reshape(1, N),
      jnp.asarray(same, f32), jnp.asarray(gbps_min, f32))
    assign, est, eft, cnt = outs[0], outs[1], outs[2], outs[3]
    return (assign[:T, 0], est[:T, 0], eft[:T, 0], cnt[0])
