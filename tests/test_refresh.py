"""Posterior maintenance plane: refresh policy triggers, fleet-wide
single-dispatch/single-generation refresh, out-of-band serving isolation,
generation-aware service refresh, and incremental (generation-delta)
checkpoints."""
import os
import time

import numpy as np
import pytest

from repro.core import bayes
from repro.core.microbench import simulate_microbench
from repro.core.predictor import LotaruPredictor
from repro.core.traces import TraceRow
from repro.online import (FleetRefresher, OnlinePredictor, PredictionService,
                          RefreshPolicy, TaskCompletion)
from repro.online.events import PredictionQuery
from repro.sched.cluster import LOCAL, TARGET_MACHINES
from repro.store import AsyncPredictionFrontend, PosteriorStore, TaskKey


def _traces(task="bwa", n=6, slope=30.0, base=4.0):
    return [TraceRow("wf", task, "local", s, base + slope * s)
            for s in np.linspace(0.05, 0.4, n)]


def _fit(tasks=("bwa", "idx")):
    lot = LotaruPredictor("G", local_bench=simulate_microbench(LOCAL, 1))
    traces = []
    for j, t in enumerate(tasks):
        traces += _traces(t, slope=20.0 + 7 * j, base=2.0 + j)
    return lot.fit(traces)


def _benches():
    return {n.name: simulate_microbench(n, 1) for n in TARGET_MACHINES}


def _observe_local(online, task, n, rng, slope=35.0, base=4.0, noise=0.5):
    for i in range(n):
        x = float(rng.uniform(0.5, 6.0))
        online.observe(TaskCompletion(
            "wf", f"{task}-{i}", task, "local", x,
            float(base + slope * x + rng.normal(0, noise))))


# --- refresh policy triggers ----------------------------------------------------
def test_refresh_due_every_n_completions(rng):
    online = OnlinePredictor(_fit(("bwa", "idx")))
    policy = RefreshPolicy(every_n=5)
    _observe_local(online, "bwa", 4, rng)
    assert online.refresh_due(policy) == []
    _observe_local(online, "bwa", 1, rng)
    assert online.refresh_due(policy) == ["bwa"]      # idx has no stream
    # a refresh resets the counter
    snap = online.refresh_snapshot(["bwa"])["bwa"]
    post = bayes.refresh_fit([], [], snap[1], snap[2])
    assert online.apply_refresh("bwa", bayes.nig_from_blr(post),
                                seq=snap[0])
    assert online.refresh_due(policy) == []


def test_refresh_due_evidence_drift_trigger(rng):
    """streamed noise far above the lift-time level trips the drift
    trigger long before the periodic counter would."""
    online = OnlinePredictor(_fit(("bwa",)))
    policy = RefreshPolicy(every_n=10 ** 6, drift_ratio=3.0)
    assert online.refresh_due(policy) == []
    # fit noise is ~0 (exact line); stream wildly noisy observations
    _observe_local(online, "bwa", 6, rng, noise=80.0)
    assert online.refresh_due(policy) == ["bwa"]
    st = online.tasks["bwa"]
    ratio = (st.nig["b"] / st.nig["a"]) / st.nig["s2_lift"]
    assert ratio > 3.0


def test_apply_refresh_rejects_stale_fit(rng):
    """an observation landing between snapshot and apply must win: the
    stale fit is rejected and the task stays due."""
    online = OnlinePredictor(_fit(("bwa",)))
    _observe_local(online, "bwa", 5, rng)
    seq, x, y = online.refresh_snapshot(["bwa"])["bwa"]
    post = bayes.refresh_fit([], [], x, y)
    _observe_local(online, "bwa", 1, rng)           # race: new observation
    before = online.predict("bwa", 3.0)
    assert not online.apply_refresh("bwa", bayes.nig_from_blr(post),
                                    seq=seq)
    assert online.predict("bwa", 3.0) == before
    assert online.refresh_due(RefreshPolicy(every_n=5)) == ["bwa"]


# --- fleet-wide batched refresh -------------------------------------------------
def test_fleet_refresh_one_generation_across_tenants(rng):
    """two tenants' due tasks are refreshed by ONE dispatch and published
    in ONE copy-on-write generation; the refreshed predictive matches the
    scalar one-shot refresh_fit reference."""
    store = PosteriorStore()
    onlines, svcs = {}, {}
    for tenant in ("acme", "globex"):
        online = OnlinePredictor(_fit(("bwa", "idx")))
        onlines[tenant] = online
        svcs[tenant] = PredictionService(online, store=store, tenant=tenant,
                                         workflow="w")
        _observe_local(online, "bwa", 6, rng)
        _observe_local(online, "idx", 6, rng, slope=12.0)
        svcs[tenant].predict_batch([PredictionQuery("bwa", None, 1.0)])

    refresher = FleetRefresher(store, RefreshPolicy(every_n=4))
    due = refresher.due()
    assert {(b.tenant, t) for b, t in due} == {
        ("acme", "bwa"), ("acme", "idx"),
        ("globex", "bwa"), ("globex", "idx")}
    gen0 = store.generation
    report = refresher.refresh()
    assert report.n_dispatches == 1
    assert report.n_tasks == 4
    assert report.n_tenants == 2
    assert store.generation == gen0 + 1            # ONE generation for all

    for tenant, online in onlines.items():
        for task in ("bwa", "idx"):
            st = online.tasks[task]
            ref = bayes.nig_to_blr(bayes.nig_from_blr(
                bayes.refresh_fit(st.fit_xs, st.fit_ys, st.xs, st.ys)))
            got = svcs[tenant].predict_batch(
                [PredictionQuery(task, None, 3.0)])[0][0]
            want, _ = bayes.predict_blr_np(ref, 3.0)
            assert got == pytest.approx(max(float(want), 1e-3), rel=2e-3)
    # the publish advanced the cursors: the next predict re-syncs nothing
    gen1 = store.generation
    svcs["acme"].predict_batch([PredictionQuery("bwa", None, 1.0)])
    assert store.generation == gen1


def test_refresh_preserves_streamed_only_observations(rng):
    """a promoted median-fallback task has NO fit-time regression data:
    its refresh refits on the streamed buffer alone (streamed-only
    observations preserved, downsampled medians never resurrected)."""
    rows = [TraceRow("wf", "multiqc", "local", s, r)
            for s, r in zip([0.1, 0.2, 0.3, 0.4], [30, 29, 31, 30])]
    lot = LotaruPredictor("G", local_bench=simulate_microbench(LOCAL, 1))
    lot.fit(rows)
    online = OnlinePredictor(lot)
    assert online.tasks["multiqc"].fit_xs == []     # median task: no fit data
    xs, ys = [], []
    for i in range(8):                              # strong correlation at
        x = 2.0 + 3.0 * i                           # production scale ->
        y = 10.0 + 12.0 * x + float(rng.normal(0, 0.1))   # promotion
        online.observe(TaskCompletion("wf", f"m{i}", "multiqc", "local",
                                      x, y))
        xs.append(x)
        ys.append(y)
    assert online.tasks["multiqc"].nig is not None  # promoted
    _observe_local(online, "multiqc", 4, rng, slope=12.0, base=10.0,
                   noise=0.1)
    store = PosteriorStore()
    svc = PredictionService(online, store=store)
    refresher = FleetRefresher(store, RefreshPolicy(every_n=1))
    report = refresher.refresh()
    assert report.n_tasks == 1
    st = online.tasks["multiqc"]
    ref = bayes.nig_to_blr(bayes.nig_from_blr(
        bayes.refresh_fit([], [], st.xs, st.ys)))
    got = svc.predict_batch([PredictionQuery("multiqc", None, 20.0)])[0][0]
    want, _ = bayes.predict_blr_np(ref, 20.0)
    assert got == pytest.approx(float(want), rel=2e-3)


def test_refresh_fit_compiles_once_for_a_growing_buffer(rng):
    """A promoted task refits a ring that grows one point at a time: every
    length up to the padding bucket runs the one compiled fit."""
    import jax
    compiles = []

    def listen(event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(secs)
    x = rng.uniform(0.5, 8.0, 40)
    y = 3.0 + 2.0 * x + rng.normal(0.0, 0.1, 40)
    bayes.refresh_fit([], [], x[:4], y[:4])
    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        posts = [bayes.refresh_fit([], [], x[:n], y[:n])
                 for n in range(5, 40)]
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    assert compiles == []
    assert all(float(p["n"]) == n for p, n in zip(posts, range(5, 40)))


def test_refresh_out_of_band_snapshot_isolation(rng):
    """readers holding a pre-refresh snapshot keep serving it; the refresh
    lands as one atomic generation — in-flight predict batches are never
    blocked on (or torn by) a refresh."""
    store = PosteriorStore()
    online = OnlinePredictor(_fit(("bwa", "idx")))
    svc = PredictionService(online, store=store)
    _observe_local(online, "bwa", 6, rng)
    svc.predict_batch([PredictionQuery("bwa", None, 1.0)])
    old_snap = store.snapshot()
    key = TaskKey("default", "default", "bwa")
    before = old_snap.get(key)
    report = FleetRefresher(store, RefreshPolicy(every_n=4)).refresh()
    assert report.generation == old_snap.generation + 1
    for leaf, v in old_snap.get(key).items():       # old view untouched
        np.testing.assert_array_equal(v, before[leaf])
    assert not np.array_equal(store.snapshot().get(key)["sigma"],
                              before["sigma"])


def test_refresher_noop_when_nothing_due(rng):
    store = PosteriorStore()
    online = OnlinePredictor(_fit(("bwa",)))
    PredictionService(online, store=store)
    refresher = FleetRefresher(store, RefreshPolicy(every_n=4))
    assert refresher.maybe_refresh() is None
    assert refresher.dispatch_count == 0
    report = refresher.refresh()                    # explicit call: no rows
    assert report.n_tasks == 0 and report.n_dispatches == 0


def test_frontend_runs_refresh_out_of_band(rng):
    """the front-end's maintenance thread refreshes due posteriors while
    the batch window keeps answering predict callers."""
    store = PosteriorStore()
    online = OnlinePredictor(_fit(("bwa", "idx")))
    svc = PredictionService(online, store=store)
    svc.predict_batch([PredictionQuery("bwa", None, 1.0)])
    _observe_local(online, "bwa", 8, rng)
    refresher = FleetRefresher(store, RefreshPolicy(every_n=4))
    with AsyncPredictionFrontend(store, window_s=0.005, refresher=refresher,
                                 refresh_interval_s=0.005) as fe:
        deadline = time.time() + 30.0
        while refresher.dispatch_count == 0 and time.time() < deadline:
            out = fe.predict([PredictionQuery("bwa", None, 2.0)])
            assert out.shape == (1, 3)
        assert refresher.dispatch_count >= 1
        # post-refresh serving matches the service path bit-for-bit
        np.testing.assert_array_equal(
            fe.predict([PredictionQuery("bwa", None, 2.0)]),
            svc.predict_batch([PredictionQuery("bwa", None, 2.0)]))
    assert online.tasks["bwa"].since_refresh < 8    # refresh really landed


# --- generation-aware service refresh (docstring/behavior fix) ------------------
def test_service_refresh_is_generation_aware(rng):
    """refresh() no-ops when the binding cursor is current — no row
    rewrites, no generation bump; it restacks only when actually behind."""
    store = PosteriorStore()
    online = OnlinePredictor(_fit(("bwa", "idx")))
    svc = PredictionService(online, store=store)
    svc.predict_batch([PredictionQuery("bwa", None, 1.0)])   # fully synced
    gen = store.generation
    assert svc.refresh() == 0
    assert store.generation == gen                  # no-op: nothing moved
    online.observe(TaskCompletion("wf", "u0", "bwa", "local", 2.0, 90.0))
    assert svc.refresh() == 2                       # full restack when stale
    assert store.generation == gen + 1
    assert svc.refresh() == 0                       # current again


def test_service_refresh_noop_for_static_predictor():
    svc = PredictionService(_fit(("bwa",)))
    svc.predict_batch([PredictionQuery("bwa", None, 1.0)])
    gen = svc.store.generation
    assert svc.refresh() == 0
    assert svc.store.generation == gen
    svc.predictor.fit(_traces("bwa", slope=50.0))   # out-of-band model edit
    assert svc.refresh() == 1                       # restacked
    m = svc.predict_batch([PredictionQuery("bwa", None, 2.0)])[0][0]
    assert m == pytest.approx(svc.predictor.predict("bwa", 2.0)[0], rel=1e-6)


# --- ragged batched fit kernel --------------------------------------------------
def test_bayes_fit_ragged_pads_rows_and_tasks():
    """per-row masks + task-dimension padding: a task count that is not a
    block multiple still fits in one pallas_call, exactly."""
    import jax.numpy as jnp
    from repro.kernels.bayes_fit import bayes_fit_ragged, pad_ragged
    rng = np.random.default_rng(7)
    xs_list, ys_list = [], []
    for i in range(6):                               # ragged lengths 3..14
        n = 3 + 2 * i
        x = rng.uniform(0.1, 5.0, n)
        xs_list.append(x)
        ys_list.append(2 + (4 + i) * x + rng.normal(0, 0.05, n))
    x, y, m = pad_ragged(xs_list, ys_list, col_bucket=1)
    assert x.shape == (6, 13)                        # unbucketed: exact max
    assert pad_ragged(xs_list, ys_list)[0].shape == (6, 64)   # jit bucket
    post = bayes_fit_ragged(jnp.asarray(x), jnp.asarray(y), jnp.asarray(m),
                            block_tasks=4, interpret=True)   # 6 -> pad to 8
    assert post["mu"].shape == (6, 2)
    for i in range(6):
        ref = bayes.fit_blr(xs_list[i].astype(np.float32),
                            np.asarray(ys_list[i], np.float32))
        np.testing.assert_allclose(np.asarray(post["mu"][i]),
                                   np.asarray(ref["mu"]),
                                   rtol=5e-3, atol=5e-4)
        np.testing.assert_allclose(float(post["n"][i]), len(xs_list[i]))


def test_pad_ragged_rejects_mismatched_rows():
    from repro.kernels.bayes_fit import pad_ragged
    with pytest.raises(ValueError, match="row 1"):
        pad_ragged([[1.0], [1.0, 2.0]], [[1.0], [1.0]])


# --- incremental (generation-delta) checkpoints ---------------------------------
def _warm_service(store, tenant, tasks, rng):
    online = OnlinePredictor(_fit(tasks), benches=_benches())
    svc = PredictionService(online, _benches(), store=store, tenant=tenant,
                            workflow="w")
    for t in tasks:
        _observe_local(online, t, 5, rng)
    svc.predict_batch([PredictionQuery(tasks[0], None, 1.0)])
    return online, svc


def test_incremental_save_writes_only_rewritten_blocks(tmp_path, rng):
    store = PosteriorStore(block_size=2)
    online, svc = _warm_service(store, "t", ("a0", "a1", "a2", "a3"), rng)
    path = str(tmp_path / "ckpt")
    store.save(path)
    assert sorted(store.last_checkpoint_blocks) == [0, 1]    # full: all
    # touch exactly one task -> one block dirty
    online.observe(TaskCompletion("wf", "u", "a0", "local", 2.0, 77.0))
    svc.predict_batch([PredictionQuery("a0", None, 1.0)])
    row = store.snapshot().row_of(TaskKey("t", "w", "a0"))
    store.save(path, incremental=True)
    assert store.last_checkpoint_blocks == [row // 2]        # delta: one
    restored = PosteriorStore.restore(path)
    online2 = OnlinePredictor(_fit(("a0", "a1", "a2", "a3")),
                              benches=_benches())
    restored.resume("t", "w", online2, _benches())
    svc2 = PredictionService(online2, _benches(), store=restored, tenant="t",
                             workflow="w")
    qs = [PredictionQuery(t, None, 1.5) for t in ("a0", "a1", "a2", "a3")]
    np.testing.assert_array_equal(svc2.predict_batch(qs),
                                  svc.predict_batch(qs))


def test_incremental_save_requires_existing_checkpoint(tmp_path):
    store = PosteriorStore()
    PredictionService(_fit(("bwa",)), store=store)
    with pytest.raises(FileNotFoundError, match="full save first"):
        store.save(str(tmp_path / "nope"), incremental=True)


def test_incremental_save_refuses_foreign_checkpoint(tmp_path, rng):
    """generation counters are not comparable across divergent histories:
    only the store that wrote (or restored) a checkpoint may extend it —
    any other store must do a full save.  A restored store MAY extend the
    checkpoint it came from."""
    store_a = PosteriorStore()
    _warm_service(store_a, "t", ("bwa",), rng)
    path = str(tmp_path / "c")
    store_a.save(path)
    # a different store (same shape, same generation numbers) must refuse
    store_b = PosteriorStore()
    _warm_service(store_b, "t", ("bwa",), rng)
    with pytest.raises(ValueError, match="diverged"):
        store_b.save(path, incremental=True)
    # restore -> incremental extend of the same lineage is allowed
    restored = PosteriorStore.restore(path)
    online = OnlinePredictor(_fit(("bwa",)), benches=_benches())
    restored.resume("t", "w", online, _benches())
    online.observe(TaskCompletion("wf", "u", "bwa", "local", 2.0, 50.0))
    restored.save(path, incremental=True)
    assert PosteriorStore.restore(path).generation == restored.generation


def test_checkpoint_lifecycle_evict_refresh_incremental_restore(tmp_path,
                                                                rng):
    """the satellite lifecycle: save -> evict a namespace -> refresh ->
    incremental save -> restore resumes warm with bit-identical
    predictions, and the restored store never serves a pre-refresh
    generation (or the evicted rows)."""
    store = PosteriorStore(block_size=2)
    online_a, svc_a = _warm_service(store, "a", ("a0", "a1", "a2"), rng)
    online_b, svc_b = _warm_service(store, "b", ("b0", "b1"), rng)
    path = str(tmp_path / "ckpt")
    store.save(path)

    assert store.evict("a", "w") == 3
    refresher = FleetRefresher(store, RefreshPolicy(every_n=4))
    report = refresher.refresh()
    assert report.n_tasks == 2 and report.n_tenants == 1     # tenant b only
    store.save(path, incremental=True)
    # the delta rewrote only tenant b's block(s); tenant a's block files
    # are gone from the checkpoint directory
    qs = [PredictionQuery(t, None, 2.5) for t in ("b0", "b1")]
    expected = svc_b.predict_batch(qs)

    restored = PosteriorStore.restore(path)
    assert restored.generation == store.generation
    assert restored.snapshot().generation >= report.generation
    with pytest.raises(KeyError):
        restored.snapshot().row_of(TaskKey("a", "w", "a0"))
    online_b2 = OnlinePredictor(_fit(("b0", "b1")), benches=_benches())
    restored.resume("b", "w", online_b2, _benches())
    svc_b2 = PredictionService(online_b2, _benches(), store=restored,
                               tenant="b", workflow="w")
    np.testing.assert_array_equal(svc_b2.predict_batch(qs), expected)
    # resumed state is warm: counters and buffers came back, so the next
    # refresh behaves identically on both sides
    assert online_b2.export_state() == online_b.export_state()


def test_evicted_block_file_removed_on_incremental_save(tmp_path, rng):
    store = PosteriorStore(block_size=2)
    _warm_service(store, "a", ("a0", "a1"), rng)     # rows 0-1 -> block 0
    _warm_service(store, "b", ("b0", "b1"), rng)     # rows 2-3 -> block 1
    path = str(tmp_path / "c")
    store.save(path)
    assert os.path.exists(os.path.join(path, "block_0.npz"))
    store.evict("a", "w")
    store.save(path, incremental=True)
    assert not os.path.exists(os.path.join(path, "block_0.npz"))
    assert os.path.exists(os.path.join(path, "block_1.npz"))
    restored = PosteriorStore.restore(path)
    assert restored.num_free_blocks == 1             # released block stays
    assert restored.get(TaskKey("b", "w", "b0"))["mu"].shape == (2,)


# --- per-tenant refresh budgets --------------------------------------------------
def test_refresh_budget_caps_tasks_per_tenant_per_cycle(rng):
    """max_tasks_per_tenant_per_cycle defers (never drops) excess due
    tasks: each cycle refreshes at most N per tenant and the remainder
    surfaces in the next cycle."""
    store = PosteriorStore()
    online, svc = _warm_service(store, "acme", ("bwa", "idx", "sort"), rng)
    for t in ("idx", "sort"):                        # all three due
        _observe_local(online, t, 5, rng)
        svc.predict_batch([PredictionQuery(t, None, 1.0)])
    refresher = FleetRefresher(store, RefreshPolicy(
        every_n=4, max_tasks_per_tenant_per_cycle=1))
    seen = []
    for _ in range(3):
        due = refresher.due()
        assert len(due) == 1                         # capped per cycle
        seen.append(due[0][1])
        assert refresher.refresh().n_tasks == 1
    assert sorted(seen) == ["bwa", "idx", "sort"]    # deferred, not dropped
    assert refresher.due() == []


def test_refresh_budget_uncapped_tenant_unaffected(rng):
    """the cap is per tenant: a second tenant's backlog is not throttled
    by the first tenant's budget consumption."""
    store = PosteriorStore()
    _warm_service(store, "acme", ("a0", "a1"), rng)
    online_b, svc_b = _warm_service(store, "globex", ("b0", "b1"), rng)
    _observe_local(online_b, "b1", 5, rng)
    svc_b.predict_batch([PredictionQuery("b1", None, 1.0)])
    refresher = FleetRefresher(store, RefreshPolicy(
        every_n=4, max_tasks_per_tenant_per_cycle=2))
    due = refresher.due()
    by_tenant = {}
    for b, t in due:
        by_tenant.setdefault(b.tenant, []).append(t)
    assert len(by_tenant["acme"]) == 2               # hit the cap
    assert len(by_tenant["globex"]) == 2             # own budget
    assert refresher.refresh().n_tasks == 4


def test_refresh_min_interval_defers_recently_refreshed(rng):
    """min_interval_s suppresses re-refreshing a task that was just
    refreshed, even if its completion counter is due again."""
    store = PosteriorStore()
    online, svc = _warm_service(store, "acme", ("bwa",), rng)
    refresher = FleetRefresher(store, RefreshPolicy(
        every_n=4, min_interval_s=3600.0))
    assert len(refresher.due()) == 1
    assert refresher.refresh().n_tasks == 1
    _observe_local(online, "bwa", 5, rng)            # due by counter again
    svc.predict_batch([PredictionQuery("bwa", None, 1.0)])
    assert refresher.due() == []                     # ...but too soon
    # age the last-refresh stamp past the interval: due again
    for k in refresher._last_refresh:
        refresher._last_refresh[k] -= 7200.0
    assert len(refresher.due()) == 1
    assert refresher.refresh().n_tasks == 1


# --- checkpoint retention / GC ---------------------------------------------------
def test_save_keep_last_retains_and_restores_old_generations(tmp_path, rng):
    """keep_last preserves superseded block/manifest generations as
    hard-linked history files; restore(generation=...) serves the old
    state bit-identically until retention prunes it."""
    store = PosteriorStore(block_size=2)
    online, svc = _warm_service(store, "t", ("a0", "a1", "a2", "a3"), rng)
    path = str(tmp_path / "ckpt")
    store.save(path, keep_last=2)
    g1 = store.generation
    mu_old = store.get(TaskKey("t", "w", "a0"))["mu"].copy()

    online.observe(TaskCompletion("wf", "u", "a0", "local", 2.0, 99.0))
    svc.predict_batch([PredictionQuery("a0", None, 1.0)])
    store.save(path, incremental=True, keep_last=2)
    g2 = store.generation
    assert g2 > g1
    # the superseded manifest + rewritten block were preserved
    assert os.path.exists(os.path.join(path, f"manifest.g{g1}.json"))
    old = PosteriorStore.restore(path, generation=g1)
    np.testing.assert_array_equal(old.get(TaskKey("t", "w", "a0"))["mu"],
                                  mu_old)
    # the live restore serves the NEW state
    new = PosteriorStore.restore(path)
    assert not np.array_equal(new.get(TaskKey("t", "w", "a0"))["mu"],
                              mu_old)


def test_save_keep_last_prunes_history_and_orphans(tmp_path, rng):
    """retention: only the newest keep_last-1 superseded generations stay
    restorable; older history files, stray block files, and staging temps
    are garbage-collected."""
    store = PosteriorStore(block_size=2)
    online, svc = _warm_service(store, "t", ("a0", "a1"), rng)
    path = str(tmp_path / "ckpt")
    store.save(path, keep_last=2)
    gens = [store.generation]
    for i in range(2):
        online.observe(TaskCompletion("wf", f"u{i}", "a0", "local",
                                      2.0 + i, 70.0 + i))
        svc.predict_batch([PredictionQuery("a0", None, 1.0)])
        # plant an orphan + a staging temp: GC must remove both
        orphan = os.path.join(path, "block_9.npz")
        temp = os.path.join(path, "block_0.npz.tmp")
        open(orphan, "wb").close()
        open(temp, "wb").close()
        store.save(path, incremental=True, keep_last=2)
        gens.append(store.generation)
        assert not os.path.exists(orphan)
        assert not os.path.exists(temp)
    # keep_last=2 -> exactly one superseded generation stays restorable
    hist = sorted(f for f in os.listdir(path)
                  if f.startswith("manifest.g"))
    assert hist == [f"manifest.g{gens[-2]}.json"]
    with pytest.raises(FileNotFoundError):
        PosteriorStore.restore(path, generation=gens[0])
    assert PosteriorStore.restore(
        path, generation=gens[-2]).generation == gens[-2]


def test_save_keep_last_one_keeps_live_only(tmp_path, rng):
    store = PosteriorStore(block_size=2)
    online, svc = _warm_service(store, "t", ("a0", "a1"), rng)
    path = str(tmp_path / "ckpt")
    store.save(path, keep_last=1)
    online.observe(TaskCompletion("wf", "u", "a0", "local", 2.0, 80.0))
    svc.predict_batch([PredictionQuery("a0", None, 1.0)])
    store.save(path, incremental=True, keep_last=1)
    assert not [f for f in os.listdir(path) if ".g" in f]    # no history
    assert PosteriorStore.restore(path).generation == store.generation


def test_save_keep_last_validation(tmp_path, rng):
    store = PosteriorStore()
    _warm_service(store, "t", ("a0",), rng)
    with pytest.raises(ValueError, match="keep_last"):
        store.save(str(tmp_path / "c"), keep_last=0)


# --- the stacked apply phase against the per-task reference loop ---------------
_FLEET_TASKS = tuple(f"t{i}" for i in range(6))


@pytest.fixture(scope="module")
def fleet_base():
    return _fit(_FLEET_TASKS)


def _bits(v):
    return type(v), np.asarray(v).dtype, np.asarray(v).shape, \
        np.asarray(v).tobytes()


@pytest.mark.parametrize("n_due", [1, 2, 300])
def test_stacked_apply_equals_per_task_reference(monkeypatch, rng, n_due,
                                                 fleet_base):
    """One pass over `n_due` due tasks of 50 tenants, tenant 0's predictor
    bound into a second namespace too.  With two or more tasks due, the
    second one's seq moves while the fit runs; with three or more, the
    third one's namespace is evicted then.  Every published row and
    every task's state is bit-identical to the per-task loop
    (`nig_from_blr`, `nig_to_blr`, `put_many`); the stale task keeps its
    newer state and stays due; the pass bumps one generation."""
    import repro.store.compute as compute
    store = PosteriorStore()
    onlines = [OnlinePredictor(fleet_base) for _ in range(50)]
    for j, online in enumerate(onlines):
        store.bind(f"ten{j}", "w", online)
    store.bind("ten0", "w2", onlines[0])              # shared predictor
    due_rows = [(j, t) for t in _FLEET_TASKS for j in range(50)][:n_due]
    for j, t in due_rows:
        _observe_local(onlines[j], t, 2, rng)
    policy = RefreshPolicy(every_n=2)
    refresher = FleetRefresher(store, policy)
    due = refresher.due()
    assert len(due) == n_due + sum(j == 0 for j, _ in due_rows)   # ten0 x2
    before = store.snapshot()

    real = compute.fit_stacked
    seen = {}

    def fit_and_race(x, y, m, impl="auto"):
        seen["post"] = real(x, y, m, impl)
        if n_due >= 2:                                # an observe lands
            j, t = due_rows[1]
            _observe_local(onlines[j], t, 1, rng)
            seen["stale"] = onlines[j].tasks[t].nig
        if n_due >= 3:                                # a namespace goes
            store.evict(f"ten{due_rows[2][0]}", "w")
        seen["generation"] = store.generation
        return seen["post"]
    monkeypatch.setattr(compute, "fit_stacked", fit_and_race)
    report = refresher.refresh(due)

    assert store.generation == seen["generation"] + 1
    assert report.generation == store.generation
    assert report.n_stale == (1 if n_due >= 2 else 0)
    ref_store = PosteriorStore()
    ref_items, published = [], set()
    fit_rows = list(dict.fromkeys((id(b.predictor), t) for b, t in due))
    evicted = (due_rows[2][0], "w") if n_due >= 3 else None
    for i, (j, t) in enumerate(due_rows):
        state = onlines[j].tasks[t].nig
        if i == 1:                                    # stale: kept, due
            assert state is seen["stale"]
            assert t in onlines[j].refresh_due(policy)
            continue
        r = fit_rows.index((id(onlines[j]), t))
        want = bayes.nig_from_blr({k: v[r] for k, v in seen["post"].items()})
        assert list(state) == list(want)
        for k in want:
            assert _bits(state[k]) == _bits(want[k]), (i, k)
        row = bayes.nig_to_blr(want)
        for ns in (("w", "w2") if j == 0 else ("w",)):
            if evicted == (j, ns):                    # never written back
                continue
            key = str(TaskKey(f"ten{j}", ns, t))
            ref_items.append((key, row))
            published.add(key)
    ref_store.put_many(ref_items)
    assert report.n_tasks == len(published)
    snap = store.snapshot()
    for key in store.task_keys():
        want = (ref_store.get(key) if key in published else before.get(key))
        for leaf, v in snap.get(key).items():
            assert v.tobytes() == want[leaf].tobytes(), (key, leaf)
    if evicted:
        assert not any(TaskKey(f"ten{evicted[0]}", "w", t) in snap
                       for t in _FLEET_TASKS)


def test_publish_reads_the_state_held_at_publish(rng):
    """An observe that lands after the take-up and before the publish
    reads its seq: the row published is the state the predictor then
    holds (the observed one), not the refreshed row, and the cursor
    still advances past it."""
    store = PosteriorStore()
    online = OnlinePredictor(_fit(("bwa", "idx")))
    binding = store.bind("acme", "w", online)
    _observe_local(online, "bwa", 4, rng)
    _observe_local(online, "idx", 4, rng, slope=12.0)
    real = online.change_seq

    def observe_first(task):
        if task == "bwa" and "raced" not in held:
            _observe_local(online, "bwa", 1, rng)
            held["raced"] = online.tasks["bwa"].nig
        return real(task)
    held = {}
    online.change_seq = observe_first
    report = FleetRefresher(store, RefreshPolicy(every_n=4)).refresh()
    assert report.n_tasks == 2 and report.n_stale == 0
    assert online.tasks["bwa"].nig is held["raced"]
    snap = store.snapshot()
    for task in ("bwa", "idx"):
        want = bayes.nig_to_blr(online.tasks[task].nig)
        got = snap.get(TaskKey("acme", "w", task))
        for leaf, v in got.items():
            assert v.tobytes() == np.asarray(want[leaf],
                                             np.float64).tobytes(), leaf
    assert binding.is_current()
