"""The HEFT candidate-EFT insertion sweep as one jitted dispatch.

  * `eft_sweep` — one workflow's sweep as ONE `fori_loop` dispatch:
    per-node busy intervals live in (N, S) begin/end arrays, the per-task
    gap search is a fused select + min-reduce, and placements are
    in-place row scatters.  `sched.fused` runs it in float64 (jax's x64
    mode) on the host CPU device for problems of `_JIT_MIN_CELLS` cells
    and more;
  * `eft_sweep_many` — `eft_sweep` vmapped over a megabatch of workflows
    sharing one cluster (padded/masked task rows), so B tenant replans
    cost one dispatch (`sched.fused.replan_many`).

Bit-parity (the property tests assert bitwise-equal schedules vs
`sched.heft.heft_schedule_reference` when run in float64):

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
(`order_arr == -1`, from bucket or megabatch padding) insert the
(inf, inf) interval — bitwise a no-op on the pad columns — and scatter
their outputs to a dummy row, so padded and unpadded sweeps agree exactly.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

DEFAULT_SLOTS = 48          # busy-interval columns per node (auto-doubled
                            # by the host wrapper on overflow)


# ---------------------------------------------------------------------------
# candidate-EFT sweep
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
