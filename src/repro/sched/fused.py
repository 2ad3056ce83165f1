"""Device-resident fused decision plane: predict -> quantile -> upward-rank
-> candidate-EFT sweep with persistent posterior rows, updated in place.

The PR-4 decision plane already batches the prediction matrix into one
dispatch per planning round, but every round still *re-materializes* it —
a full store gather + predictive call + factor matrix — and then runs
HEFT's ranking and placement through per-task Python/NumPy loops.  At
fleet scale (thousands of tenant workflows replanning continuously) the
decision plane itself is the hot path.  This module keeps it resident:

  * `FusedPlane` — holds one workflow's raw predictive rows (mean/std per
    task), the static factor matrix, and the streaming node corrections
    *across* planning rounds.  On each round it asks the store snapshot
    which backing blocks moved since its last gather
    (`StoreSnapshot.rows_changed_since`, generation-tagged against the
    COW store) and re-gathers/re-predicts ONLY those rows, scattering
    them in place.  Because the predictive is elementwise per row, a
    dirty-subset update is bit-identical to a full re-gather.

  * `fused_heft_schedule` — the fused scheduling engine.  Bit-identical
    to `heft.heft_schedule_matrix` (the parity suite asserts equality on
    random DAGs/clusters), but the candidate-EFT sweep runs on flat
    (N, S) busy-interval arrays instead of per-node Python lists and slot
    loops: per task, ONE vectorized gap search over every node replaces N
    `_earliest_slot` calls.  The W-independent half of the upward rank
    (the avg pairwise comm term, O(T * N^2)) is cached per (dag, cluster)
    on the plane — it never changes between rounds, so a warm replan pays
    only the O(T * N) w_avg cumsum, the reverse-topo recurrence, and the
    sweep.

  * `replan_many` — megabatched replans across planes (tenants /
    workflows): the dirty rows of ALL planes are coalesced into ONE
    padded predictive dispatch (`store.compute.predict_stacked`), the way
    `fit_stacked` batches the fleet refresh, then each request is
    scheduled off its resident rows.

  * The candidate-EFT sweep itself has two engines: a float64 NumPy
    engine (flat interval arrays, the portable fallback and parity
    oracle) and the `kernels.decision_plane.eft_sweep` jitted engine —
    the whole per-task insertion loop compiled into ONE dispatch (run in
    float64 via jax's x64 mode on `sweep_device()`).  The jit
    engine is an order of magnitude faster at fleet scale and remains
    bit-identical: the sweep contains no multi-term sums, so there is
    nothing for the compiler to reassociate.  `engine="auto"` picks by
    problem size (the dispatch overhead dominates tiny DAGs).

Bit-parity notes (why the vectorized gap search is exact): the insertion
policy keeps each node's busy intervals non-overlapping and sorted, so
interval ends are non-decreasing; the candidate start before interval i
is therefore `max(ready, end[i-1])` independent of earlier fit checks,
and the FIRST i with `cand + dur <= begin[i]` is exactly the slot
`_earliest_slot`'s sequential walk returns.  max/min/compare are exact in
IEEE floats and every arithmetic term (`cand + dur`, `est + dur`, comm
charges) uses the same expressions as the reference, so schedules match
bitwise, not just approximately.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.core.microbench import NodeSpec
from repro.sched.heft import Schedule, comm_structure
from repro.sched.plane import PredictionMatrix, quantile_z
from repro.store import compute
from repro.workflow.dag import WorkflowDAG

__all__ = ["FusedPlane", "PlaneStats", "ReplanRequest",
           "fused_heft_schedule", "replan_many", "sweep_device"]


# ---------------------------------------------------------------------------
# fused HEFT engine (host float64 path)
# ---------------------------------------------------------------------------

_NEG_INF = float("-inf")

# auto engine policy: the jitted sweep is one compiled dispatch but pays
# jit/dispatch overhead; below this many (task x node) cells the NumPy
# engine wins and avoids compiles for throwaway shapes
_JIT_MIN_CELLS = 5000
# task/dep dims are padded to bucket multiples so shrinking replan
# frontiers (the rescheduler re-plans ever-smaller sub-DAGs) reuse one
# compiled sweep instead of re-jitting per shape
_TASK_BUCKET = 64
_DEP_BUCKET = 4


class _PlanContext:
    """Per-(dag, cluster) invariants cached across planning rounds: the
    topo order and row maps, the pairwise comm structure, successor
    lists, the W-independent avg-comm rank terms, and the sweep engine's
    static arrays (dep rows, output bits, a shared zero ready matrix).
    All of it is derived data — cached values are bitwise what a cold
    round recomputes, so warm and cold rounds schedule identically."""

    __slots__ = ("dag", "order", "row_of", "names", "same", "gbps_min",
                 "succ", "avg_comm", "dep_rows", "gb8", "zeros", "slot_cap")

    def __init__(self, dag: WorkflowDAG, nodes: List[NodeSpec]):
        self.dag = dag      # strong ref: the cache key includes id(dag),
        # which stays unique only while the dag is alive
        self.order = dag.topo_order()
        self.row_of = {u: i for i, u in enumerate(self.order)}
        self.names = [n.name for n in nodes]
        self.same, self.gbps_min = comm_structure(nodes)
        self.succ = dag.successors()
        n_nodes = len(nodes)
        self.avg_comm: Dict[str, float] = {}
        for u in self.order:
            gb = dag.tasks[u].output_gb
            terms = np.where(self.same, 0.0, (gb * 8.0) / self.gbps_min)
            self.avg_comm[u] = (float(terms.ravel().cumsum()[-1])
                                / (n_nodes ** 2))
        n_tasks = len(self.order)
        depth = max((len(dag.tasks[u].deps) for u in self.order), default=0)
        depth = max(-(-max(depth, 1) // _DEP_BUCKET) * _DEP_BUCKET, 1)
        self.dep_rows = np.full((n_tasks, depth), -1, np.int32)
        for i, u in enumerate(self.order):
            for k, d in enumerate(dag.tasks[u].deps):
                self.dep_rows[i, k] = self.row_of[d]
        self.gb8 = np.asarray([dag.tasks[u].output_gb * 8.0
                               for u in self.order], np.float64)
        self.zeros = np.zeros((n_tasks, n_nodes))
        self.slot_cap = 48        # doubled on interval-stack overflow

    def ranks(self, dag: WorkflowDAG, W: np.ndarray) -> Dict[str, float]:
        """Upward ranks off this round's W: the per-round halves only
        (w_avg cumsum + reverse-topo recurrence); avg_comm is cached."""
        n_nodes = len(self.names)
        w_avg_arr = (W.cumsum(axis=1)[:, -1] / n_nodes if n_nodes
                     else W.sum(1))
        rank: Dict[str, float] = {}
        avg_comm, succ, row_of = self.avg_comm, self.succ, self.row_of
        for u in reversed(self.order):
            best = 0.0
            for v in succ[u]:
                best = max(best, avg_comm[u] + rank[v])
            rank[u] = float(w_avg_arr[row_of[u]]) + best
        return rank


_CTX_CACHE_MAX = 32


def _context(dag: WorkflowDAG, nodes: List[NodeSpec],
             rank_cache: Optional[dict]) -> _PlanContext:
    if rank_cache is None:
        return _PlanContext(dag, nodes)
    key = (id(dag), len(dag.tasks), tuple(n.name for n in nodes))
    ctx = rank_cache.get(key)
    if ctx is None or ctx.dag is not dag:
        ctx = rank_cache[key] = _PlanContext(dag, nodes)
        while len(rank_cache) > _CTX_CACHE_MAX:    # bound replan-frontier
            rank_cache.pop(next(iter(rank_cache)))  # churn (FIFO evict)
    return ctx


class _SlotArrays:
    """Per-node busy intervals as flat (N, S) arrays: `b0`/`b1` are the
    interval begins/ends sorted by begin, `cnt` the live count per node.
    Padding is +inf / -inf so the vectorized gap search needs no masking:
    the +inf begin past the last interval always fits, and the -inf ends
    make the shifted `prev` ends a no-op under max."""

    __slots__ = ("b0", "b1", "cnt", "cap", "_prev", "_cand", "_tmp")

    def __init__(self, n_nodes: int, cap: int = 8):
        self.cap = cap
        self.b0 = np.full((n_nodes, cap), np.inf)
        self.b1 = np.full((n_nodes, cap), _NEG_INF)
        self.cnt = np.zeros(n_nodes, np.int64)
        self._prev = np.empty((n_nodes, cap))
        self._cand = np.empty((n_nodes, cap))
        self._tmp = np.empty((n_nodes, cap))

    def seed_available(self, avail: np.ndarray) -> None:
        """node_available entries > 0 enter as a [0, avail) busy prefix —
        same convention as the reference's slot lists."""
        busy = avail > 0.0
        self.b0[busy, 0] = 0.0
        self.b1[busy, 0] = avail[busy]
        self.cnt[busy] = 1

    def _grow(self) -> None:
        n, cap = self.b0.shape
        new_cap = cap * 2
        for name, fill in (("b0", np.inf), ("b1", _NEG_INF)):
            a = np.full((n, new_cap), fill)
            a[:, :cap] = getattr(self, name)
            setattr(self, name, a)
        self.cap = new_cap
        self._prev = np.empty((n, new_cap))
        self._cand = np.empty((n, new_cap))
        self._tmp = np.empty((n, new_cap))

    def earliest(self, ready: np.ndarray, dur: np.ndarray
                 ) -> Tuple[np.ndarray, np.ndarray]:
        """(est, cand-start matrix row picks) for every node at once —
        the vectorized `_earliest_slot`.  Returns est (N,) and the
        first-fit column indices (N,)."""
        b0, b1 = self.b0, self.b1
        prev = self._prev
        prev[:, 0] = _NEG_INF
        prev[:, 1:] = b1[:, :-1]
        cand = np.maximum(ready[:, None], prev, out=self._cand)
        np.add(cand, dur[:, None], out=self._tmp)
        fits = self._tmp <= b0                     # +inf pad: always a fit
        ff = fits.argmax(axis=1)
        est = cand[np.arange(cand.shape[0]), ff]
        return est, ff

    def insert(self, j: int, est: float, eft: float) -> None:
        """Insert [est, eft) into node j's sorted intervals (the tuple
        (b0, b1) lexicographic order the reference's list.sort() keeps)."""
        c = int(self.cnt[j])
        if c + 1 >= self.cap:
            self._grow()      # keep >= 1 spare +inf column: the gap search
            # relies on the pad past the last interval always fitting
        b0r, b1r = self.b0[j], self.b1[j]
        pos = int(np.searchsorted(b0r[:c], est))
        while pos < c and b0r[pos] == est and b1r[pos] < eft:
            pos += 1                               # zero-length-interval ties
        if pos < c:
            b0r[pos + 1:c + 1] = b0r[pos:c].copy()
            b1r[pos + 1:c + 1] = b1r[pos:c].copy()
        b0r[pos] = est
        b1r[pos] = eft
        self.cnt[j] = c + 1


def _ready_rows(ctx: _PlanContext, dag: WorkflowDAG, nodes: List[NodeSpec],
                ready_at) -> Optional[np.ndarray]:
    """Materialize external ready-time constraints as a (T, N) array in
    topo-row order (None when unconstrained: the caller uses a shared
    zero matrix).  Callable form pays the same T x N calls the reference
    engine would have made."""
    if ready_at is None:
        return None
    if isinstance(ready_at, np.ndarray):
        rows = np.asarray(ready_at, np.float64)
        want = (len(ctx.order), len(nodes))
        if rows.shape != want:
            raise ValueError(f"ready_at array must be {want}, got "
                             f"{rows.shape}")
        return rows
    if callable(ready_at):
        return np.asarray([[ready_at(u, n) for n in nodes]
                           for u in ctx.order], np.float64)
    col = np.asarray([ready_at.get(u, 0.0) for u in ctx.order], np.float64)
    return np.repeat(col[:, None], len(nodes), axis=1)


def fused_heft_schedule(dag: WorkflowDAG, nodes: List[NodeSpec],
                        matrix: PredictionMatrix,
                        ready_at=None,
                        node_available: Optional[Dict[str, float]] = None,
                        quantile: Optional[float] = None,
                        rank_cache: Optional[dict] = None,
                        engine: str = "auto",
                        W: Optional[np.ndarray] = None) -> Schedule:
    """Fused-engine HEFT: bit-identical to `heft.heft_schedule_matrix`.

    `ready_at` additionally accepts a precomputed (T, N) array (rows in
    `dag.topo_order()` order) so replans can charge external dependency
    comm without T x N Python callbacks.  `rank_cache` is an optional
    dict the caller keeps across rounds; per-(dag, cluster) invariants
    (comm structure, successor lists, the W-independent avg-comm rank
    terms, the sweep's static arrays) are memoized in it.  `engine`:
    'numpy' = flat-array host sweep; 'jit' = one compiled dispatch
    (`kernels.decision_plane.eft_sweep` in float64); 'auto' picks by
    problem size.  `W` overrides the cost matrix (topo-row order) — the
    resident plane passes its fused cost view so the matrix is never
    re-derived here."""
    ctx = _context(dag, nodes, rank_cache)
    if W is None:
        W = matrix.costs(ctx.order, ctx.names, quantile=quantile)  # (T, N)
    rank = ctx.ranks(dag, W)
    if engine == "auto":
        engine = "jit" if W.size >= _JIT_MIN_CELLS else "numpy"
    if engine == "jit":
        return _schedule_jit(ctx, dag, nodes, W, rank, ready_at,
                             node_available)
    return _schedule_numpy(ctx, dag, nodes, W, rank, ready_at,
                           node_available)


def _schedule_numpy(ctx: _PlanContext, dag: WorkflowDAG,
                    nodes: List[NodeSpec], W: np.ndarray,
                    rank: Dict[str, float], ready_at,
                    node_available: Optional[Dict[str, float]]) -> Schedule:
    order, names = ctx.order, ctx.names
    same, gbps_min = ctx.same, ctx.gbps_min
    n_nodes = len(nodes)
    sched = Schedule(order={name: [] for name in names})
    row_of = ctx.row_of
    slots = _SlotArrays(n_nodes)
    if node_available:
        slots.seed_available(np.asarray(
            [node_available.get(name, 0.0) for name in names], np.float64))

    ready_rows = _ready_rows(ctx, dag, nodes, ready_at)
    finish: Dict[str, float] = {}
    assign_idx: Dict[str, int] = {}
    zeros = np.zeros(n_nodes)

    for u in sorted(order, key=lambda u: -rank[u]):
        t = dag.tasks[u]
        i = row_of[u]
        ready = zeros.copy() if ready_rows is None else ready_rows[i].copy()
        for d in t.deps:
            dn = assign_idx[d]
            comm = np.where(same[dn], 0.0,
                            (dag.tasks[d].output_gb * 8.0) / gbps_min[dn])
            np.maximum(ready, finish[d] + comm, out=ready)
        dur = W[i]
        est, _ = slots.earliest(ready, dur)
        eft = est + dur
        j = int(np.argmin(eft))
        est_j, eft_j = float(est[j]), float(eft[j])
        slots.insert(j, est_j, eft_j)
        name = names[j]
        sched.assignment[u] = name
        sched.order[name].append(u)
        sched.est[u] = (est_j, eft_j)
        finish[u] = eft_j
        assign_idx[u] = j
    for name in sched.order:
        sched.order[name].sort(key=lambda u: sched.est[u][0])
    return sched


def _sweep_inputs(ctx: _PlanContext, dag: WorkflowDAG,
                  nodes: List[NodeSpec], W: np.ndarray,
                  rank: Dict[str, float], ready_at,
                  node_available: Optional[Dict[str, float]]):
    """Pack one replan into the jitted sweep's padded array form.

    The task dimension is padded to a _TASK_BUCKET multiple with masked
    (order == -1) rows so shrinking rescheduler frontiers hit the same
    compiled sweep; masked rows are bitwise no-ops inside the kernel."""
    order = ctx.order
    n_tasks, n_nodes = len(order), len(nodes)
    rank_arr = np.asarray([rank[u] for u in order], np.float64)
    # stable argsort == sorted(order, key=-rank): ties keep topo order
    order_arr = np.argsort(-rank_arr, kind="stable").astype(np.int32)
    ready0 = _ready_rows(ctx, dag, nodes, ready_at)
    if ready0 is None:
        ready0 = ctx.zeros
    if node_available:
        avail = np.asarray([node_available.get(name, 0.0)
                            for name in ctx.names], np.float64)
    else:
        avail = np.zeros(n_nodes)
    tp = -(-n_tasks // _TASK_BUCKET) * _TASK_BUCKET
    if tp != n_tasks:
        pad = tp - n_tasks
        order_arr = np.concatenate(
            [order_arr, np.full(pad, -1, np.int32)])
        W = np.concatenate([W, np.ones((pad, n_nodes))])
        ready0 = np.concatenate([ready0, np.zeros((pad, n_nodes))])
        dep_rows = np.concatenate(
            [ctx.dep_rows, np.full((pad, ctx.dep_rows.shape[1]), -1,
                                   np.int32)])
        gb8 = np.concatenate([ctx.gb8, np.zeros(pad)])
    else:
        dep_rows, gb8 = ctx.dep_rows, ctx.gb8
    return W, order_arr, dep_rows, gb8, ready0, avail


def _build_schedule(ctx: _PlanContext, order_arr: np.ndarray,
                    assign: np.ndarray, est: np.ndarray,
                    eft: np.ndarray) -> Schedule:
    """Rehydrate a `Schedule` from the sweep's flat outputs, visiting
    tasks in rank order (the order the reference appends in) so per-node
    lists tie-break identically before the final est sort."""
    n_tasks = len(ctx.order)
    sched = Schedule(order={name: [] for name in ctx.names})
    order, names = ctx.order, ctx.names
    for t in range(len(order_arr)):
        i = int(order_arr[t])
        if i < 0 or i >= n_tasks:
            continue
        u = order[i]
        name = names[int(assign[i])]
        sched.assignment[u] = name
        sched.order[name].append(u)
        sched.est[u] = (float(est[i]), float(eft[i]))
    for name in sched.order:
        sched.order[name].sort(key=lambda u: sched.est[u][0])
    return sched


def sweep_device():
    """The device the float64 jit sweep runs on: the process's CPU device,
    on every platform.  The sweep's contract is schedules bitwise equal to
    `heft.heft_schedule_matrix`; a TPU emulates float64 and does not keep
    it, so a process that holds a TPU places the sweep on its host CPU."""
    import jax
    return jax.devices("cpu")[0]


def _schedule_jit(ctx: _PlanContext, dag: WorkflowDAG,
                  nodes: List[NodeSpec], W: np.ndarray,
                  rank: Dict[str, float], ready_at,
                  node_available: Optional[Dict[str, float]]) -> Schedule:
    import jax

    from repro.kernels import decision_plane as dp
    packed = _sweep_inputs(ctx, dag, nodes, W, rank, ready_at,
                           node_available)
    while True:
        S = ctx.slot_cap
        with jax.enable_x64(True), jax.default_device(sweep_device()):
            assign, est, eft, cnt = dp.eft_sweep(
                *packed, ctx.same, ctx.gbps_min, S=S)
            assign = np.asarray(assign)
            est = np.asarray(est)
            eft = np.asarray(eft)
            cnt = np.asarray(cnt)
        if cnt.max() <= S - 1:
            break
        ctx.slot_cap = S * 2      # interval stacks overflowed: the gap
        # search needs >= 1 spare pad column per node — recompile larger
    return _build_schedule(ctx, packed[1], assign, est, eft)


# ---------------------------------------------------------------------------
# resident prediction plane
# ---------------------------------------------------------------------------

@dataclass
class PlaneStats:
    """Residency telemetry: how much work each round actually did."""
    rounds: int = 0
    full_gathers: int = 0          # complete (re)builds of the row stack
    rows_refreshed: int = 0        # dirty rows re-gathered + re-predicted
    predict_dispatches: int = 0    # predictive kernel calls issued
    matrix_rebuilds: int = 0       # scaled-view recomputations
    cost_rebuilds: int = 0         # (T, N) quantile cost-view recomputations
    sweep_dispatches: int = 0      # jitted EFT sweep calls (megabatch = 1)


class FusedPlane:
    """One workflow's device-resident slice of the decision plane.

    Holds the raw (factor-free) predictive mean/std per task plus the
    static factor matrix across planning rounds; `sync()` pulls only the
    rows whose store blocks moved since the last round (generation-tagged
    dirty detection) and `matrix()` serves the scaled `PredictionMatrix`
    view — elementwise-identical to `PredictionService.predict_matrix`,
    asserted by the parity suite.  On TPU the row stack lives as device
    arrays and the in-place row updates are device scatters
    (`kernels.decision_plane`); on CPU it is float64 NumPy either way.
    """

    def __init__(self, service, nodes: Sequence[NodeSpec],
                 entries: Optional[Sequence[Tuple[str, str, float]]] = None,
                 dag: Optional[WorkflowDAG] = None, impl: str = "auto"):
        if entries is None:
            if dag is None:
                raise ValueError("FusedPlane needs `entries` or a `dag`")
            entries = [(u, dag.tasks[u].task_name, dag.tasks[u].input_gb)
                       for u in dag.tasks]
        self.service = service
        self.nodes = list(nodes)
        self.node_names = [n.name for n in self.nodes]
        self.impl = impl
        self.entries = [(u, t, float(gb)) for u, t, gb in entries]
        self.uids: Tuple[str, ...] = tuple(u for u, _, _ in self.entries)
        self._tasks = [t for _, t, _ in self.entries]
        self._x = np.asarray([gb for _, _, gb in self.entries], np.float64)
        self._keys = [service._binding.key_str(t) for t in self._tasks]
        self.stats = PlaneStats()
        self.rank_cache: dict = {}
        # resident state
        self._mean_raw: Optional[np.ndarray] = None   # (T,) factor-free
        self._std_raw: Optional[np.ndarray] = None
        self._generation = -1          # store generation the rows reflect
        self._base_f: Optional[np.ndarray] = None     # (T, N) static factors
        self._base_f_version: Optional[int] = None
        self._matrix: Optional[PredictionMatrix] = None
        self._matrix_key = None
        # derived (T, N) quantile cost views, resident across rounds:
        # _view is the matrix reindexed to one dag's topo order,
        # _cost_cache the per-quantile `mean + z*std` off it
        self._view: Optional[Tuple[np.ndarray, np.ndarray]] = None
        self._view_key = None
        self._cost_cache: Dict[Optional[float], np.ndarray] = {}

    @property
    def binding(self):
        return self.service._binding

    # ---- dirty-row sync ----------------------------------------------------
    def collect_dirty(self):
        """Sync the binding, snapshot the store, and return
        (snapshot, dirty_index_array) — the rows whose backing blocks
        moved since this plane's last gather (all rows on first use).
        Split from `apply_rows` so `replan_many` can coalesce the
        predictive dispatch across planes."""
        binding = self.binding
        binding.sync()
        snap = self.service.store.snapshot()
        if self._mean_raw is None:
            idx = np.arange(len(self._keys))
            self.stats.full_gathers += 1
        elif snap.generation == self._generation:
            idx = np.empty(0, np.int64)
        else:
            dirty = snap.rows_changed_since(self._keys, self._generation)
            idx = np.nonzero(dirty)[0]
        return snap, idx

    def apply_rows(self, snap, idx: np.ndarray, mean: np.ndarray,
                   std: np.ndarray) -> None:
        """Scatter re-predicted rows in place and adopt the snapshot
        generation.  The predictive is elementwise per row, so the
        scattered values are bitwise what a full re-gather would put
        there."""
        if self._mean_raw is None:
            self._mean_raw = np.empty(len(self._keys))
            self._std_raw = np.empty(len(self._keys))
        if len(idx):
            self._mean_raw[idx] = mean
            self._std_raw[idx] = std
            self.stats.rows_refreshed += len(idx)
        self._generation = snap.generation

    def sync(self) -> int:
        """One round's resident-row maintenance: dirty-row gather +
        predict + in-place scatter.  Returns the number of rows
        refreshed."""
        snap, idx = self.collect_dirty()
        if len(idx):
            post = snap.gather([self._keys[i] for i in idx])
            mean, std = compute.predict_stacked(self._x[idx], post,
                                                impl=self.impl)
            self.stats.predict_dispatches += 1
            self.apply_rows(snap, idx, mean, std)
        else:
            self.apply_rows(snap, idx, np.empty(0), np.empty(0))
        return len(idx)

    # ---- scaled matrix view ------------------------------------------------
    def matrix(self) -> PredictionMatrix:
        """The scaled (T, N) `PredictionMatrix` for the current round:
        resident raw rows x (static factor matrix x streaming node
        corrections) — the exact `compute.scale` arithmetic
        `predict_matrix` applies, so consumers see identical numbers.
        Cached until rows, factors, or corrections move."""
        self.stats.rounds += 1
        self.sync()
        binding = self.binding
        if self._base_f is None \
                or binding.factor_version != self._base_f_version:
            self._base_f = binding.base_factor_matrix(self._tasks,
                                                      self.node_names)
            self._base_f_version = binding.factor_version
        corr_map = binding.node_corrections(self.node_names)
        corr = tuple(corr_map.get(n, 1.0) for n in self.node_names)
        key = (self._generation, self._base_f_version, corr)
        if self._matrix is None or key != self._matrix_key:
            f = self._base_f * np.asarray(corr, np.float64)[None, :]
            mean, std = compute.scale(self._mean_raw[:, None],
                                      self._std_raw[:, None], f)
            self._matrix = PredictionMatrix(self.uids, self.node_names,
                                            mean, std)
            self._matrix_key = key
            self.stats.matrix_rebuilds += 1
        return self._matrix

    # ---- resident cost view ------------------------------------------------
    def cost_view(self, dag: WorkflowDAG, quantile: Optional[float]
                  ) -> Tuple[PredictionMatrix, np.ndarray]:
        """(matrix, W): the (T, N) quantile cost matrix in `dag`'s topo
        order, resident across rounds.  The reindexed mean/std pair and
        the per-quantile `mean + z*std` are cached until the underlying
        matrix moves (rows, factors, or corrections), so a steady-state
        replan re-derives nothing — same expressions as
        `PredictionMatrix.costs`, hence bitwise-equal schedules."""
        mat = self.matrix()
        ctx = _context(dag, self.nodes, self.rank_cache)
        # the ctx object in the key pins the dag: id-recycling after a
        # frontier dag dies can never alias a stale view
        vkey = (self._matrix_key, ctx)
        if self._view is None or self._view_key != vkey:
            rows = np.asarray([mat.uid_index[u] for u in ctx.order],
                              np.int64)
            cols = np.asarray([mat.node_index[n] for n in ctx.names],
                              np.int64)
            self._view = (mat.means[np.ix_(rows, cols)],
                          mat.stds[np.ix_(rows, cols)])
            self._view_key = vkey
            self._cost_cache.clear()
        W = self._cost_cache.get(quantile)
        if W is None:
            mean_g, std_g = self._view
            z = None if quantile is None else quantile_z(quantile)
            W = compute.cost_matrix(mean_g, std_g, z)
            self._cost_cache[quantile] = W
            self.stats.cost_rebuilds += 1
        return mat, W

    # ---- scheduling --------------------------------------------------------
    def schedule(self, dag: WorkflowDAG, ready_at=None,
                 node_available: Optional[Dict[str, float]] = None,
                 quantile: Optional[float] = None,
                 engine: str = "auto") -> Schedule:
        """One fused replan round off the resident rows + cost view."""
        mat, W = self.cost_view(dag, quantile)
        return fused_heft_schedule(dag, self.nodes, mat,
                                   ready_at=ready_at,
                                   node_available=node_available,
                                   quantile=quantile,
                                   rank_cache=self.rank_cache,
                                   engine=engine, W=W)


# ---------------------------------------------------------------------------
# megabatched replans
# ---------------------------------------------------------------------------

@dataclass
class ReplanRequest:
    """One tenant/workflow's replan in a megabatch."""
    plane: FusedPlane
    dag: WorkflowDAG
    ready_at: object = None
    node_available: Optional[Dict[str, float]] = None
    quantile: Optional[float] = None


def replan_many(requests: Sequence[ReplanRequest],
                impl: str = "auto", fuse_sweeps: bool = True
                ) -> List[Schedule]:
    """Megabatched replans across tenants/workflows: the dirty rows of
    every plane are coalesced into ONE padded predictive dispatch (the
    way `fit_stacked` batches the fleet refresh), scattered back into
    each plane's resident stack, then the EFT sweeps of requests sharing
    one cluster and padded shape run as ONE vmapped dispatch
    (`kernels.decision_plane.eft_sweep_many`; `fuse_sweeps=False` falls
    back to per-request scheduling).  Bit-identical to calling
    `plane.schedule(...)` per request — the predictive is elementwise and
    the vmapped sweep runs each lane's exact scalar program, so batching
    changes nothing but the dispatch count."""
    # every binding syncs BEFORE any snapshot is taken: planes sharing one
    # store then collect against the same generation, so the scatter below
    # leaves them all clean and the per-request schedule() pass re-gathers
    # nothing (block-granular dirtiness would otherwise let tenant B's
    # sync, landing after tenant A's snapshot, re-dirty a shared block)
    for req in requests:
        req.plane.binding.sync()
    collected = []
    xs, posts = [], []
    for req in requests:
        snap, idx = req.plane.collect_dirty()
        collected.append((req, snap, idx))
        if len(idx):
            xs.append(req.plane._x[idx])
            posts.append(snap.gather([req.plane._keys[i] for i in idx]))
    if xs:
        x_all = np.concatenate(xs)
        post_all = {leaf: np.concatenate([p[leaf] for p in posts])
                    for leaf in compute.LEAVES}
        mean_all, std_all = compute.predict_stacked(x_all, post_all,
                                                    impl=impl)
        off = 0
        for req, snap, idx in collected:
            if len(idx):
                req.plane.apply_rows(snap, idx,
                                     mean_all[off:off + len(idx)],
                                     std_all[off:off + len(idx)])
                req.plane.stats.predict_dispatches += 1
                off += len(idx)
            else:
                req.plane.apply_rows(snap, idx, np.empty(0), np.empty(0))
    else:
        for req, snap, idx in collected:
            req.plane.apply_rows(snap, idx, np.empty(0), np.empty(0))
    return _schedule_requests(requests, fuse_sweeps)


def _schedule_requests(requests: Sequence[ReplanRequest],
                       fuse_sweeps: bool) -> List[Schedule]:
    """Schedule every (synced) request, vmapping the EFT sweeps of
    same-cluster, same-padded-shape groups into one dispatch each."""
    results: List[Optional[Schedule]] = [None] * len(requests)
    groups: Dict[tuple, list] = {}
    for pos, req in enumerate(requests):
        plane = req.plane
        mat, W = plane.cost_view(req.dag, req.quantile)
        ctx = _context(req.dag, plane.nodes, plane.rank_cache)
        rank = ctx.ranks(req.dag, W)
        if not (fuse_sweeps and W.size >= _JIT_MIN_CELLS):
            results[pos] = fused_heft_schedule(
                req.dag, plane.nodes, mat, ready_at=req.ready_at,
                node_available=req.node_available, quantile=req.quantile,
                rank_cache=plane.rank_cache, W=W)
            continue
        packed = _sweep_inputs(ctx, req.dag, plane.nodes, W, rank,
                               req.ready_at, req.node_available)
        # one group = one cluster comm structure + one padded shape: the
        # vmapped sweep shares (same, gbps_min) and stacks the rest
        key = (tuple(ctx.names), ctx.same.tobytes(),
               ctx.gbps_min.tobytes(), packed[0].shape,
               packed[2].shape[1])
        groups.setdefault(key, []).append((pos, req, ctx, packed))
    for members in groups.values():
        _dispatch_group(members, results)
    return results


def _dispatch_group(members: list, results: List[Optional[Schedule]]
                    ) -> None:
    import jax

    from repro.kernels import decision_plane as dp
    ctx0 = members[0][2]
    stacked = [np.stack([m[3][k] for m in members])
               for k in range(6)]
    while True:
        S = max(m[2].slot_cap for m in members)
        with jax.enable_x64(True), jax.default_device(sweep_device()):
            if len(members) == 1:
                assign, est, eft, cnt = dp.eft_sweep(
                    *members[0][3], ctx0.same, ctx0.gbps_min, S=S)
                assign, est, eft = assign[None], est[None], eft[None]
                cnt = np.asarray(cnt)[None]
            else:
                assign, est, eft, cnt = dp.eft_sweep_many(
                    *stacked, ctx0.same, ctx0.gbps_min, S=S)
            assign = np.asarray(assign)
            est = np.asarray(est)
            eft = np.asarray(eft)
            cnt = np.asarray(cnt)
        if cnt.max() <= S - 1:
            break
        for _, _, ctx, _ in members:
            ctx.slot_cap = max(ctx.slot_cap, S * 2)
    for b, (pos, req, ctx, packed) in enumerate(members):
        req.plane.stats.sweep_dispatches += 1
        results[pos] = _build_schedule(ctx, packed[1], assign[b],
                                       est[b], eft[b])
