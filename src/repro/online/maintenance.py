"""Posterior maintenance plane: fleet-wide periodic evidence refresh.

Streaming NIG updates (online.predictor) are *exact conjugate* updates —
given the (alpha, beta) hyperparameters the MacKay evidence fixed point
chose at fit time.  After hundreds of online completions that lift no
longer reflects the data: the standardization is frozen at profile scale
and the prior precision was tuned for 3-10 downsampled points, which
degrades exactly the uncertainty estimates the scheduler consumes.  The
standard remedy (Hilman et al. 2018) is periodic re-fitting from the
accumulated observations.

This module closes that loop across the whole store:

  * `RefreshPolicy` decides *when* a task is due — every N posterior-moving
    completions, and/or when the streaming noise estimate b/a drifts beyond
    `drift_ratio` x the lift-time level;
  * `FleetRefresher` gathers the ragged observation buffers of every due
    task across every tenant bound to one `PosteriorStore`, re-runs the
    evidence fixed point for all of them in ONE padded/masked batched fit
    dispatch (`store.compute.fit_stacked`: Pallas kernel on TPU, jit'd vmap
    elsewhere), moment-matches the refreshed posteriors back into the
    streaming NIG states in one stacked lift (each task taken up by
    `OnlinePredictor.apply_refresh`), and publishes every rewritten row,
    exported from the predictors' states in one stacked export, through
    the store in a single copy-on-write generation bump (`put_stacked`).

The refresh is out-of-band by construction: the expensive fit runs with no
locks held (a fit that races a concurrent observe() is rejected per task by
its change seq and the task simply stays due), and readers keep serving
from immutable snapshots until the one-generation publish lands — in-flight
predict batches are never blocked.  `start()` runs the loop on a daemon
thread; `repro.store.frontend.AsyncPredictionFrontend` can own the same
loop next to its batch-window worker.
"""
from __future__ import annotations

import contextlib
import itertools
import threading
import time
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

from repro import obs
from repro.store.compute import LEAVES
from repro.store.posterior import PosteriorStore, TenantBinding


@dataclass
class RefreshPolicy:
    """When is a task's streaming posterior due for an evidence refresh?

    every_n: posterior-moving completions since the last refresh (the
        Hilman-style periodic trigger).
    drift_ratio: optional evidence-drift trigger — refresh as soon as the
        streaming noise estimate b/a leaves
        (s2_lift / drift_ratio, s2_lift * drift_ratio), i.e. the data
        contradicts the lift-time noise level before the periodic counter
        fires.
    min_points: never refit on fewer total (fit + streamed) points.

    The last two are multi-tenant fairness budgets, enforced by
    `FleetRefresher.due()` (so every entry point — refresh, maybe_refresh,
    the daemon loop — sees the same throttled view):

    max_tasks_per_tenant_per_cycle: cap on how many of one tenant's due
        tasks enter a single refresh pass.  A noisy tenant streaming
        completions into hundreds of tasks fills its quota and the rest
        stay due for the next cycle — they are deferred, never dropped —
        while other tenants' tasks still make the dispatch.
    min_interval_s: per-task refresh rate limit — a task refreshed less
        than this many seconds ago is not due yet, no matter how many
        completions landed (protects the fit dispatch from a tenant whose
        every_n fires continuously).
    """
    every_n: int = 32
    drift_ratio: Optional[float] = None
    min_points: int = 4
    max_tasks_per_tenant_per_cycle: Optional[int] = None
    min_interval_s: Optional[float] = None


@dataclass
class RefreshReport:
    """What one `FleetRefresher.refresh()` pass did."""
    n_tasks: int = 0          # posteriors refreshed and published
    n_tenants: int = 0        # distinct tenants those rows belong to
    n_dispatches: int = 0     # batched fit dispatches issued (0 or 1)
    n_stale: int = 0          # fits rejected by a racing observe()
    generation: int = -1      # store generation after the publish
    duration_s: float = 0.0


class FleetRefresher:
    """Batched evidence refresh for every namespace bound to one store.

    One instance owns the refresh schedule of a whole (multi-tenant)
    `PosteriorStore`; `refresh()` is safe to call from any thread, and
    `start(interval_s)` runs `maybe_refresh()` on a daemon thread.
    """

    def __init__(self, store: PosteriorStore,
                 policy: Optional[RefreshPolicy] = None, impl: str = "auto"):
        self.store = store
        self.policy = policy or RefreshPolicy()
        self.impl = impl
        self.dispatch_count = 0          # lifetime batched-fit dispatches
        self.reports: List[RefreshReport] = []
        self.failure_count = 0           # background passes that raised
        self.last_error: Optional[BaseException] = None   # most recent one
        self._last_refresh: Dict[Tuple[int, str], float] = {}   # applied-at
        self._pass_ids = itertools.count()       # trace ids of passes
        self._stop = threading.Event()                          # monotonic
        self._thread: Optional[threading.Thread] = None

    # ---- due detection ------------------------------------------------------
    def due(self) -> List[Tuple[TenantBinding, str]]:
        """(binding, task) pairs due under the policy, across all tenants.
        Predictors without the refresh protocol (plain LotaruPredictor) are
        skipped — their posteriors are not streaming.

        The policy's fairness budgets apply here: tasks refreshed within
        `min_interval_s` are not yet due, and each tenant contributes at
        most `max_tasks_per_tenant_per_cycle` tasks per sweep (the rest
        remain due and surface on later sweeps — deferred, not dropped)."""
        with obs.span("lotaru.refresh.due"):
            out = []
            pol = self.policy
            now = time.monotonic()
            per_tenant: Dict[str, int] = {}
            for b in self.store.bindings():
                fn = getattr(b.predictor, "refresh_due", None)
                if fn is None:
                    continue
                for t in fn(pol):
                    if pol.min_interval_s is not None:
                        last = self._last_refresh.get((id(b.predictor), t))
                        if (last is not None
                                and now - last < pol.min_interval_s):
                            continue
                    if pol.max_tasks_per_tenant_per_cycle is not None:
                        n = per_tenant.get(b.tenant, 0)
                        if n >= pol.max_tasks_per_tenant_per_cycle:
                            continue
                        per_tenant[b.tenant] = n + 1
                    out.append((b, t))
        return out

    # ---- the batched refresh pass -------------------------------------------
    def refresh(self, due: Optional[List[Tuple[TenantBinding, str]]] = None
                ) -> RefreshReport:
        """Refresh every due task in ONE batched fit dispatch and publish
        all rewritten rows in ONE store generation.  See module docstring
        for the race/locking story."""
        from repro.store.compute import fit_stacked
        t0 = time.perf_counter()
        with obs.span("lotaru.refresh.pass", pass_id=next(self._pass_ids)):
            if due is None:
                due = self.due()
            with obs.span("lotaru.refresh.prepare"):
                rows, keys, padded = self._prepare(due)
            n_rows = n_tenants = n_stale = n_dispatches = 0
            if rows:
                # ONE padded/masked evidence fixed-point dispatch for the
                # fleet
                with obs.span("lotaru.refresh.fit"):
                    post = fit_stacked(*padded, impl=self.impl)
                self.dispatch_count += 1
                n_dispatches = 1
                with obs.span("lotaru.refresh.apply"):
                    n_rows, n_tenants, n_stale = self._apply(rows, keys, post)
        report = RefreshReport(n_tasks=n_rows, n_tenants=n_tenants,
                               n_dispatches=n_dispatches, n_stale=n_stale,
                               generation=self.store.generation,
                               duration_s=time.perf_counter() - t0)
        self._record(report)
        return report

    def _prepare(self, due):
        """The due tasks' fit rows: their observation buffers, snapshotted,
        and the padded (x, y, mask) arrays of the one fit (None when
        nothing is due)."""
        from repro.kernels.bayes_fit import pad_ragged
        # one fit row per distinct (predictor, task): two bindings may feed
        # the same predictor into two namespaces — fit once, publish to both.
        # Buffers are snapshotted in ONE refresh_snapshot call per predictor
        # (one state-lock acquisition, one consistent instant), not per task.
        rows: Dict[Tuple[int, str], dict] = {}
        by_predictor: Dict[int, Tuple[object, List[str]]] = {}
        for b, task in due:
            p = b.predictor
            key = (id(p), task)
            if key not in rows:
                rows[key] = {"p": p, "task": task, "bindings": []}
                by_predictor.setdefault(id(p), (p, []))[1].append(task)
            if b not in rows[key]["bindings"]:
                rows[key]["bindings"].append(b)
        for p, tasks in by_predictor.values():
            for task, (seq, x, y) in p.refresh_snapshot(tasks).items():
                rows[(id(p), task)].update(seq=seq, x=x, y=y)
        if not rows:
            return rows, [], None
        keys = list(rows)
        x, y, m = pad_ragged([rows[k]["x"] for k in keys],
                             [rows[k]["y"] for k in keys])
        if obs.enabled():
            obs.count("lotaru.refresh.fit_cells", m.size)
            obs.count("lotaru.refresh.fit_points", int(m.sum()))
        return rows, keys, (x, y, m)

    def _apply(self, rows, keys, post) -> Tuple[int, int, int]:
        """Moment-match the fitted posteriors into the streaming states and
        publish them -> (rows published, tenants, stale fits)."""
        from repro.core import bayes
        # one stacked lift for the fleet, then a per-task take-up: a task
        # whose change seq moved while the fit ran keeps its (newer) state
        # and stays due
        lifted = bayes.nig_rows(bayes.nig_from_blr_stacked(post))
        applied: List[dict] = []
        for k, nig in zip(keys, lifted):
            r = rows[k]
            if r["p"].apply_refresh(r["task"], nig, seq=r["seq"]):
                applied.append(r)
                # applied-at stamp, the policy's min_interval_s rate limit
                self._last_refresh[k] = time.monotonic()
        n_stale = len(keys) - len(applied)
        obs.count("lotaru.refresh.stale_rows", n_stale)

        # publish: one stacked export of the states the predictors now hold
        # and one put_stacked -> one COW generation across all tenants, then
        # advance each binding's cursor past the rows just written.
        # Binding locks are taken in namespace order (always before the
        # store lock inside put_stacked — the same order sync() uses), so a
        # concurrent sync/flush serializes cleanly instead of deadlocking.
        bindings = sorted({id(b): b for r in applied for b in r["bindings"]
                           }.values(), key=lambda b: b.namespace)
        tenants = set()
        with contextlib.ExitStack() as stack:
            for b in bindings:
                stack.enter_context(b._sync_lock)
            held: List[dict] = []          # the states read back, in order
            key_strs: List[str] = []
            src: List[int] = []            # held state of each published key
            per_binding: Dict[int, Dict[str, int]] = {}
            for r in applied:
                # seq captured BEFORE the state is read: if an observe lands
                # in between, the exported row is fresher than the seq and
                # the cursor advance below refuses — the row just stays due
                p, task = r["p"], r["task"]
                seq = p.change_seq(task)
                nig = p.tasks[task].nig
                if nig is None:          # replaced by a load_state(): its
                    continue             # cursor refuses, the sync writes it
                n_keys = len(key_strs)
                for b in r["bindings"]:
                    if b._detached:      # evicted/displaced mid-refresh:
                        continue         # never write its rows back
                    key_strs.append(b.key_str(task))
                    src.append(len(held))
                    per_binding.setdefault(id(b), {})[task] = seq
                    tenants.add(b.tenant)
                if len(key_strs) > n_keys:
                    held.append(nig)
            if key_strs:
                out = bayes.nig_to_blr_stacked(bayes.nig_stack(held))
                self.store.put_stacked(
                    key_strs, {leaf: out[leaf][src] for leaf in LEAVES})
            obs.count("lotaru.refresh.published_rows", len(key_strs))
            for b in bindings:
                if not b._detached:
                    b._advance_cursor(per_binding.get(id(b), {}))
        return len(key_strs), len(tenants), n_stale

    def _record(self, report: RefreshReport) -> None:
        if len(self.reports) >= 4096:    # telemetry, not a log: a daemon
            del self.reports[:2048]      # loop must not grow without bound
        self.reports.append(report)

    def maybe_refresh(self) -> Optional[RefreshReport]:
        """refresh() only if anything is due (the polling entry point —
        a no-op pass costs one due() sweep and no dispatch)."""
        due = self.due()
        return self.refresh(due) if due else None

    # ---- background loop ----------------------------------------------------
    def start(self, interval_s: float = 1.0) -> "FleetRefresher":
        """Run maybe_refresh() every `interval_s` on a daemon thread."""
        if self._thread is not None:
            raise RuntimeError("refresher already running")
        self._stop.clear()
        self._thread = threading.Thread(target=self._loop, args=(interval_s,),
                                        daemon=True,
                                        name="posterior-refresher")
        self._thread.start()
        return self

    def _loop(self, interval_s: float) -> None:
        while not self._stop.wait(interval_s):
            try:
                self.maybe_refresh()
            except Exception as e:       # noqa: BLE001  (a refresh bug must
                # not kill the maintenance loop — but it must not die
                # silently either: operators watch failure_count/last_error
                # (a plane whose reports stop moving while these climb is
                # persistently failing, not idle)
                self.failure_count += 1
                self.last_error = e

    def stop(self) -> None:
        if self._thread is None:
            return
        self._stop.set()
        self._thread.join(timeout=10.0)
        self._thread = None

    def __enter__(self) -> "FleetRefresher":
        return self

    def __exit__(self, *exc) -> None:
        self.stop()
