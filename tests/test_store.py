"""PosteriorStore subsystem: namespaced keys, copy-on-write snapshots,
block sharding, multi-tenant isolation, checkpoint round-trips, async
coalescing, and factor-cache version scoping."""
import threading

import numpy as np
import pytest

from repro.core.microbench import simulate_microbench
from repro.core.predictor import LotaruPredictor
from repro.core.traces import TraceRow
from repro.online import (OnlinePredictor, PredictionService, TaskCompletion)
from repro.online.events import PredictionQuery
from repro.sched.cluster import LOCAL, TARGET_MACHINES
from repro.store import (AsyncPredictionFrontend, PosteriorStore, TaskKey)


def _traces(task="bwa", n=6, slope=30.0, base=4.0, cpu=0.5):
    return [TraceRow("wf", task, "local", s, base + slope * s,
                     cpu_fraction=cpu)
            for s in np.linspace(0.05, 0.4, n)]


def _fit(tasks=("bwa", "idx"), variant="G", cpu=0.5):
    lot = LotaruPredictor(variant, local_bench=simulate_microbench(LOCAL, 1))
    traces = []
    for j, t in enumerate(tasks):
        traces += _traces(t, slope=20.0 + 7 * j, base=2.0 + j, cpu=cpu)
    return lot.fit(traces)


def _benches():
    return {n.name: simulate_microbench(n, 1) for n in TARGET_MACHINES}


def _queries(tasks, nodes, xs=(0.2, 1.0, 4.0)):
    return [PredictionQuery(t, n, x) for t in tasks for n in nodes for x in xs]


# --- keys -----------------------------------------------------------------------
def test_task_key_roundtrip_and_validation():
    k = TaskKey("acme", "rnaseq", "bwa_mem")
    assert str(k) == "acme/rnaseq/bwa_mem"
    assert TaskKey.parse(str(k)) == k
    assert k.namespace == "acme/rnaseq"
    with pytest.raises(ValueError):
        TaskKey("a/b", "wf", "t")
    with pytest.raises(ValueError):
        TaskKey.parse("only/two")


# --- block layout + snapshots ---------------------------------------------------
def test_block_sharding_gather_matches_get():
    """a stack larger than one block splits into fixed-size blocks and
    gather resolves rows across them exactly."""
    tasks = [f"t{i}" for i in range(7)]
    lot = _fit(tasks)
    store = PosteriorStore(block_size=3)
    svc = PredictionService(lot, store=store, tenant="a", workflow="w")
    assert len(store) == 7
    assert store.num_blocks == 3          # ceil(7 / 3)
    keys = [TaskKey("a", "w", t) for t in tasks]
    g = store.gather(keys)
    for i, k in enumerate(keys):
        row = store.get(k)
        for leaf, v in row.items():
            np.testing.assert_array_equal(g[leaf][i], v)
        np.testing.assert_array_equal(
            row["mu"], np.asarray(lot.export_posterior(tasks[i])["mu"],
                                  np.float64))
    assert svc.predict_batch([PredictionQuery("t6", None, 1.0)]).shape == (1, 3)


def test_snapshot_copy_on_write_isolation():
    """a snapshot taken before an update keeps serving the old rows; new
    snapshots see the new ones (readers never block on writers)."""
    lot = _fit(("bwa", "idx"))
    store = PosteriorStore(block_size=2)
    store.bind("a", "w", lot)
    old = store.snapshot()
    k = TaskKey("a", "w", "bwa")
    before = old.get(k)
    new_post = dict(lot.export_posterior("bwa"))
    new_post = {kk: np.asarray(vv, np.float64) * (2.0 if kk == "y_mu" else 1.0)
                for kk, vv in new_post.items()}
    store.put(k, new_post)
    np.testing.assert_array_equal(old.get(k)["y_mu"], before["y_mu"])
    assert float(store.snapshot().get(k)["y_mu"]) == pytest.approx(
        2.0 * float(before["y_mu"]))
    # unknown-at-snapshot keys are refused by the old view
    store.put(TaskKey("a", "w", "later"), new_post)
    with pytest.raises(KeyError):
        old.get(TaskKey("a", "w", "later"))
    assert TaskKey("a", "w", "later") in store.snapshot()


def test_incremental_sync_rewrites_only_dirty_rows():
    """an online observation moves exactly one row (generation bumps, the
    other tenant rows' arrays are untouched) — no wholesale restack."""
    lot = _fit(("bwa", "idx"))
    online = OnlinePredictor(lot)
    store = PosteriorStore()
    svc = PredictionService(online, store=store, tenant="a", workflow="w")
    svc.predict_batch([PredictionQuery("bwa", None, 1.0)])
    idx_before = store.get(TaskKey("a", "w", "idx"))
    gen = store.generation
    online.observe(TaskCompletion("wf", "u0", "bwa", "local", 2.0, 80.0))
    svc.predict_batch([PredictionQuery("bwa", None, 1.0)])
    assert store.generation == gen + 1
    for leaf, v in store.get(TaskKey("a", "w", "idx")).items():
        np.testing.assert_array_equal(v, idx_before[leaf])


# --- multi-tenant isolation -----------------------------------------------------
def test_multi_tenant_isolation():
    """two workflows served by ONE store: streaming updates in tenant A
    never move tenant B's posteriors or predictions (bit-exact)."""
    benches = _benches()
    lot_a = _fit(("bwa", "idx"))
    lot_b = _fit(("bwa", "merge"))       # same task name, different tenant
    online_a = OnlinePredictor(lot_a, benches=benches)
    store = PosteriorStore()
    svc_a = PredictionService(online_a, benches, store=store,
                              tenant="acme", workflow="wf_a")
    svc_b = PredictionService(lot_b, benches, store=store,
                              tenant="globex", workflow="wf_b")
    assert set(store.namespaces()) == {"acme/wf_a", "globex/wf_b"}
    qs = _queries(["bwa"], [None, "N1", "C2"])
    b_before = svc_b.predict_batch(qs)
    a_before = svc_a.predict_batch(qs)
    for i in range(8):
        online_a.observe(TaskCompletion("wf_a", f"u{i}", "bwa", "local",
                                        2.0 + i, 500.0 + 10 * i))
    a_after = svc_a.predict_batch(qs)
    b_after = svc_b.predict_batch(qs)
    assert not np.allclose(a_before, a_after)      # tenant A learned
    np.testing.assert_array_equal(b_before, b_after)  # tenant B untouched


# --- checkpoint / restore -------------------------------------------------------
def _warm_online(benches):
    lot = _fit(("bwa", "idx", "merge"))
    online = OnlinePredictor(lot, benches=benches)
    rng = np.random.default_rng(3)
    for i in range(20):
        task = ("bwa", "idx", "merge")[i % 3]
        node = ("local", "N1", "C2", "N2")[i % 4]
        x = float(rng.uniform(0.5, 6.0))
        online.observe(TaskCompletion("wf", f"u{i}", task, node, x,
                                      float(5 + 25 * x + rng.normal(0, 1))))
    return lot, online


def test_checkpoint_roundtrip_bit_identical(tmp_path):
    """save -> restart (fresh predictor objects) -> restore: predict_batch
    output is reproduced bit-exactly, including NIG streaming state and
    node-correction logs."""
    benches = _benches()
    _, online = _warm_online(benches)
    store = PosteriorStore()
    svc = PredictionService(online, benches, store=store,
                            tenant="acme", workflow="rnaseq")
    qs = _queries(["bwa", "idx", "merge"], [None, "N1", "N2", "C2"])
    before = svc.predict_batch(qs)     # also syncs all dirty rows
    store.save(str(tmp_path / "ckpt"))

    # --- "restart": rebuild everything from scratch + the checkpoint ------
    lot2 = _fit(("bwa", "idx", "merge"))
    online2 = OnlinePredictor(lot2, benches=benches)
    restored = PosteriorStore.restore(str(tmp_path / "ckpt"))
    restored.resume("acme", "rnaseq", online2, benches)
    svc2 = PredictionService(online2, benches, store=restored,
                             tenant="acme", workflow="rnaseq")
    after = svc2.predict_batch(qs)
    np.testing.assert_array_equal(before, after)

    # the resumed service keeps LEARNING identically to the original
    comp = TaskCompletion("wf", "u99", "bwa", "local", 3.0, 123.0)
    online.observe(comp)
    online2.observe(comp)
    np.testing.assert_array_equal(svc.predict_batch(qs),
                                  svc2.predict_batch(qs))


def test_checkpoint_restores_node_corrections(tmp_path):
    benches = _benches()
    _, online = _warm_online(benches)
    store = PosteriorStore()
    PredictionService(online, benches, store=store, tenant="t", workflow="w")
    store.save(str(tmp_path / "c"))
    online2 = OnlinePredictor(_fit(("bwa", "idx", "merge")), benches=benches)
    PosteriorStore.restore(str(tmp_path / "c")).resume("t", "w", online2,
                                                       benches)
    assert set(online2.node_stats) == set(online.node_stats)
    for node, stats in online.node_stats.items():
        assert online2.node_stats[node].correction == stats.correction
        assert online2.node_stats[node].logs_by_task == stats.logs_by_task


# --- async front-end ------------------------------------------------------------
def test_async_coalesces_concurrent_callers_into_one_dispatch():
    """>= 8 concurrent callers across two tenants are answered by a single
    kernel dispatch, with results identical to each tenant's sequential
    predict_batch."""
    benches = _benches()
    store = PosteriorStore()
    svc_a = PredictionService(_fit(("bwa", "idx")), benches, store=store,
                              tenant="acme", workflow="wf_a")
    svc_b = PredictionService(_fit(("bwa", "merge")), benches, store=store,
                              tenant="globex", workflow="wf_b")
    fe = AsyncPredictionFrontend(store, auto_flush=False)
    callers = []
    for i in range(10):
        tenant, wf, svc = (("acme", "wf_a", svc_a) if i % 2 == 0 else
                           ("globex", "wf_b", svc_b))
        task = "idx" if tenant == "acme" else "merge"
        callers.append((svc, _queries(["bwa", task], [None, "N1", "A2"],
                                      xs=(0.5 + 0.1 * i, 2.0)),
                        tenant, wf))
    futs = [None] * len(callers)
    barrier = threading.Barrier(len(callers))

    def submit(i):
        barrier.wait()
        svc, qs, tenant, wf = callers[i]
        futs[i] = fe.predict_async(qs, tenant=tenant, workflow=wf)

    threads = [threading.Thread(target=submit, args=(i,))
               for i in range(len(callers))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert all(not f.done() for f in futs)     # parked in the window
    assert fe.flush() == len(callers)
    assert fe.dispatch_count == 1              # ONE dispatch for 10 callers
    assert fe.coalesced == [len(callers)]
    for (svc, qs, _, _), fut in zip(callers, futs):
        np.testing.assert_array_equal(fut.result(timeout=5),
                                      svc.predict_batch(qs))


def test_async_auto_flush_window_resolves_futures():
    benches = _benches()
    store = PosteriorStore()
    svc = PredictionService(_fit(("bwa", "idx")), benches, store=store,
                            tenant="a", workflow="w")
    with AsyncPredictionFrontend(store, window_s=0.01) as fe:
        qs = _queries(["bwa", "idx"], [None, "N1"])
        futs = [fe.predict_async(qs, tenant="a", workflow="w")
                for _ in range(4)]
        for f in futs:
            np.testing.assert_array_equal(f.result(timeout=10),
                                          svc.predict_batch(qs))
    assert fe.dispatch_count >= 1


def test_async_unknown_namespace_raises():
    fe = AsyncPredictionFrontend(PosteriorStore(), auto_flush=False)
    with pytest.raises(KeyError):
        fe.predict_async([PredictionQuery("bwa", None, 1.0)], tenant="ghost")


# --- failure isolation + durability edge cases ----------------------------------
def test_put_many_atomic_on_malformed_posterior():
    """a bad posterior must not leave phantom rows, swapped blocks, or a
    stale cached snapshot behind."""
    lot = _fit(("bwa",))
    store = PosteriorStore()
    store.bind("a", "w", lot)
    gen = store.generation
    snap = store.snapshot()
    good = lot.export_posterior("bwa")
    bad = {k: v for k, v in good.items() if k != "sigma"}
    with pytest.raises(KeyError):
        store.put_many([(TaskKey("a", "w", "ok"), good),
                        (TaskKey("a", "w", "broken"), bad)])
    wrong_shape = dict(good)
    wrong_shape["mu"] = np.zeros(3)
    with pytest.raises(ValueError):
        store.put(TaskKey("a", "w", "misshapen"), wrong_shape)
    assert len(store) == 1 and store.generation == gen
    assert store.snapshot() is snap            # nothing was invalidated
    for t in ("ok", "broken", "misshapen"):
        assert TaskKey("a", "w", t) not in store.snapshot()


def test_displaced_binding_raises_instead_of_alternating():
    """when a different predictor takes a namespace over, services holding
    the old binding fail loudly instead of silently ping-ponging rows."""
    store = PosteriorStore()
    svc1 = PredictionService(_fit(("bwa",)), store=store, tenant="a",
                             workflow="w")
    svc2 = PredictionService(_fit(("bwa",), cpu=0.9), store=store,
                             tenant="a", workflow="w")
    q = [PredictionQuery("bwa", None, 1.0)]
    assert svc2.predict_batch(q).shape == (1, 3)
    with pytest.raises(RuntimeError, match="displaced"):
        svc1.predict_batch(q)


def test_frontend_failure_isolated_to_offending_caller():
    """an unknown task from one caller rejects only that caller's future;
    the shared dispatch still answers everyone else."""
    benches = _benches()
    store = PosteriorStore()
    svc = PredictionService(_fit(("bwa", "idx")), benches, store=store,
                            tenant="a", workflow="w")
    fe = AsyncPredictionFrontend(store, auto_flush=False)
    good_qs = _queries(["bwa"], [None, "N1"])
    f_good = fe.predict_async(good_qs, tenant="a", workflow="w")
    f_bad = fe.predict_async([PredictionQuery("no_such_task", None, 1.0)],
                             tenant="a", workflow="w")
    f_good2 = fe.predict_async(good_qs, tenant="a", workflow="w")
    assert fe.flush() == 3
    assert fe.dispatch_count == 1
    with pytest.raises(KeyError):
        f_bad.result(timeout=5)
    np.testing.assert_array_equal(f_good.result(timeout=5),
                                  svc.predict_batch(good_qs))
    np.testing.assert_array_equal(f_good2.result(timeout=5),
                                  svc.predict_batch(good_qs))


def test_frontend_failure_outside_caller_guards_fails_the_batch(
        monkeypatch):
    """an error no per-caller guard catches (here the store's snapshot)
    fails every caller of the taken batch instead of leaving their futures
    unresolved, and is counted; the auto-flush worker lives on."""
    benches = _benches()
    store = PosteriorStore()
    svc = PredictionService(_fit(("bwa", "idx")), benches, store=store,
                            tenant="a", workflow="w")
    qs = _queries(["bwa"], [None, "N1"])
    boom = RuntimeError("snapshot failed")

    def failing_snapshot():
        raise boom

    real = store.snapshot
    monkeypatch.setattr(store, "snapshot", failing_snapshot)
    fe = AsyncPredictionFrontend(store, auto_flush=False)
    futs = [fe.predict_async(qs, tenant="a", workflow="w") for _ in range(2)]
    with pytest.raises(RuntimeError, match="snapshot failed"):
        fe.flush()
    for f in futs:
        assert f.exception(timeout=5) is boom
    assert fe.failure_count == 1 and fe.last_error is boom

    with AsyncPredictionFrontend(store, window_s=0.001) as auto:
        f = auto.predict_async(qs, tenant="a", workflow="w")
        assert f.exception(timeout=10) is boom
        assert auto.failure_count == 1 and auto.last_error is boom
        monkeypatch.setattr(store, "snapshot", real)
        np.testing.assert_array_equal(
            auto.predict_async(qs, tenant="a", workflow="w").result(
                timeout=10), svc.predict_batch(qs))


def test_save_preserves_unresumed_namespace_state(tmp_path):
    """restore two tenants, resume only one, save again: the unresumed
    tenant's checkpointed streaming state must survive the second save."""
    benches = _benches()
    _, online_a = _warm_online(benches)
    _, online_b = _warm_online(benches)
    store = PosteriorStore()
    PredictionService(online_a, benches, store=store, tenant="a",
                      workflow="w")
    PredictionService(online_b, benches, store=store, tenant="b",
                      workflow="w")
    store.save(str(tmp_path / "c1"))

    r1 = PosteriorStore.restore(str(tmp_path / "c1"))
    online_a2 = OnlinePredictor(_fit(("bwa", "idx", "merge")),
                                benches=benches)
    r1.resume("a", "w", online_a2, benches)    # tenant b never resumed
    r1.save(str(tmp_path / "c2"))

    r2 = PosteriorStore.restore(str(tmp_path / "c2"))
    online_b2 = OnlinePredictor(_fit(("bwa", "idx", "merge")),
                                benches=benches)
    r2.resume("b", "w", online_b2, benches)
    assert online_b2.export_state() == online_b.export_state()


def test_remote_observation_does_not_rewrite_rows():
    """a remote completion for a regression task only moves node stats —
    no dirty row, no COW block write (the store generation stays put)."""
    benches = _benches()
    online = OnlinePredictor(_fit(("bwa", "idx")), benches=benches)
    store = PosteriorStore()
    svc = PredictionService(online, benches, store=store, tenant="a",
                            workflow="w")
    q = [PredictionQuery("bwa", "N1", 1.0)]
    svc.predict_batch(q)
    gen = store.generation
    online.observe(TaskCompletion("wf", "u0", "bwa", "N1", 2.0, 50.0))
    svc.predict_batch(q)
    assert online.version > 0
    assert store.generation == gen


def test_save_with_pending_dirty_rows_checkpoints_consistently(tmp_path):
    """observe() -> save() with NO intervening predict (a periodic
    checkpointer's natural order): the checkpoint must hold the
    post-observe rows, and resume must serve them."""
    benches = _benches()
    online = OnlinePredictor(_fit(("bwa", "idx")), benches=benches)
    store = PosteriorStore()
    svc = PredictionService(online, benches, store=store, tenant="t",
                            workflow="w")
    q = _queries(["bwa"], [None, "N1"])
    svc.predict_batch(q)
    online.observe(TaskCompletion("wf", "u0", "bwa", "local", 2.0, 500.0))
    store.save(str(tmp_path / "c"))             # dirty row still unsynced
    expected = svc.predict_batch(q)             # post-observe predictions

    online2 = OnlinePredictor(_fit(("bwa", "idx")), benches=benches)
    restored = PosteriorStore.restore(str(tmp_path / "c"))
    restored.resume("t", "w", online2, benches)
    svc2 = PredictionService(online2, benches, store=restored, tenant="t",
                             workflow="w")
    np.testing.assert_array_equal(svc2.predict_batch(q), expected)
    # batch path agrees with the restored predictor's own scalar path
    m, _, _ = svc2.predict_batch([PredictionQuery("bwa", None, 2.0)])[0]
    assert m == pytest.approx(online2.predict("bwa", 2.0)[0], rel=1e-12)


def test_one_predictor_feeds_two_stores_without_starvation():
    """the change feed is non-destructive: two services over two stores
    bound to the SAME predictor both see every update (a destructive dirty
    set would let the first sync starve the second binding forever)."""
    online = OnlinePredictor(_fit(("bwa", "idx")))
    svc1 = PredictionService(online, store=PosteriorStore())
    svc2 = PredictionService(online, store=PosteriorStore())
    q = [PredictionQuery("bwa", None, 2.0)]
    for i in range(6):
        online.observe(TaskCompletion("wf", f"u{i}", "bwa", "local",
                                      2.0, 200.0))
        np.testing.assert_array_equal(svc1.predict_batch(q),
                                      svc2.predict_batch(q))
    assert svc1.predict_batch(q)[0][0] == pytest.approx(200.0, rel=0.25)


def test_restore_sparse_external_manifest_no_row_aliasing(tmp_path):
    """a hand-written manifest with row gaps must restore without aliasing:
    new keys get rows BEYOND the max restored index, and duplicate row ids
    are rejected."""
    import json
    import os
    lot = _fit(("bwa",))
    store = PosteriorStore(block_size=4)
    store.bind("t", "w", lot)
    store.save(str(tmp_path / "c"))
    man_path = os.path.join(str(tmp_path / "c"), "manifest.json")
    with open(man_path) as f:
        manifest = json.load(f)
    manifest["rows"] = {"t/w/bwa": 0, "t/w/far": 6}   # gap + 2nd block
    with open(man_path, "w") as f:
        json.dump(manifest, f)
    restored = PosteriorStore.restore(str(tmp_path / "c"))
    assert restored.get("t/w/bwa")["mu"].shape == (2,)   # readable
    restored.put(TaskKey("t", "w", "new1"), lot.export_posterior("bwa"))
    rows = {k: restored.snapshot().row_of(k) for k in restored.task_keys()}
    assert len(set(rows.values())) == len(rows)          # no aliasing
    assert rows["t/w/new1"] > 6
    manifest["rows"] = {"t/w/a": 1, "t/w/b": 1}          # duplicate row
    with open(man_path, "w") as f:
        json.dump(manifest, f)
    with pytest.raises(ValueError, match="unique"):
        PosteriorStore.restore(str(tmp_path / "c"))


def test_rebind_with_new_bench_reading_drops_cached_factors():
    """re-binding the same predictor with a re-benchmarked node must not
    keep serving factors computed from the old reading."""
    from repro.core.extrapolation import MachineBench
    benches = _benches()
    lot = _fit(("bwa",))
    store = PosteriorStore()
    svc = PredictionService(lot, benches, store=store, tenant="t",
                            workflow="w")
    q = [PredictionQuery("bwa", "C2", 2.0)]
    m_old = svc.predict_batch(q)[0][0]
    old = benches["C2"]
    reread = MachineBench(old.name, old.cpu * 2.0, old.mem,
                          old.io_read, old.io_write)
    svc2 = PredictionService(lot, {"C2": reread}, store=store, tenant="t",
                             workflow="w")
    m_new = svc2.predict_batch(q)[0][0]
    assert m_new != pytest.approx(m_old, rel=1e-6)
    assert m_new == pytest.approx(lot.predict("bwa", 2.0, reread)[0],
                                  rel=1e-6)


def test_frontend_survives_cancelled_future():
    """a caller that cancels its parked future must not poison the
    dispatch for everyone else (or kill the flush path)."""
    store = PosteriorStore()
    svc = PredictionService(_fit(("bwa",)), store=store, tenant="a",
                            workflow="w")
    fe = AsyncPredictionFrontend(store, auto_flush=False)
    qs = [PredictionQuery("bwa", None, 1.0)]
    f1 = fe.predict_async(qs, tenant="a", workflow="w")
    f2 = fe.predict_async(qs, tenant="a", workflow="w")
    assert f1.cancel()
    assert fe.flush() == 2
    assert f1.cancelled()
    np.testing.assert_array_equal(f2.result(timeout=5),
                                  svc.predict_batch(qs))


def test_load_state_at_same_version_resyncs_rows():
    """rolling a live predictor back via load_state must reach bound
    services even when the restored version number equals the synced one."""
    lot = _fit(("bwa",))
    online = OnlinePredictor(lot)
    online.observe(TaskCompletion("wf", "u0", "bwa", "local", 2.0, 300.0))
    checkpoint = online.export_state()          # version 1, pulled to 300s
    svc = PredictionService(online, store=PosteriorStore())
    q = [PredictionQuery("bwa", None, 2.0)]
    at_ckpt = svc.predict_batch(q)
    for i in range(5):
        online.observe(TaskCompletion("wf", f"u{i+1}", "bwa", "local",
                                      2.0, 30.0))
    moved = svc.predict_batch(q)
    assert not np.array_equal(at_ckpt, moved)
    online.load_state(checkpoint)
    online.version = 1                          # same number the binding saw
    svc._binding._synced_version = 1
    np.testing.assert_array_equal(svc.predict_batch(q), at_ckpt)


# --- stale-factor bug fix -------------------------------------------------------
def test_factor_cache_scoped_to_fit_version():
    """a refit that changes cpu_fraction (variant W) must invalidate cached
    extrapolation factors — the service tracks the scalar path after refit
    instead of serving factors from the previous model."""
    benches = _benches()
    lot = LotaruPredictor("W", local_bench=simulate_microbench(LOCAL, 1))
    lot.fit(_traces("bwa", cpu=0.95))
    svc = PredictionService(lot, benches)
    q = [PredictionQuery("bwa", "C2", 2.0)]
    svc.predict_batch(q)                       # warm the factor cache
    lot.fit(_traces("bwa", slope=35.0, cpu=0.05))   # refit: new cpu_fraction
    m, lo, hi = svc.predict_batch(q)[0]
    m2, lo2, hi2 = lot.predict("bwa", 2.0, benches["C2"])
    assert m == pytest.approx(m2, rel=1e-6)
    assert hi == pytest.approx(hi2, rel=1e-6)


# --- eviction + backpressure (decision-plane PR) --------------------------------
def test_evict_frees_blocks_and_recycles_rows():
    """retiring a workflow's namespace releases whole blocks, later writes
    recycle the freed row slots, and everything else keeps serving."""
    lot_a = _fit(("t0", "t1", "t2", "t3"))
    lot_b = _fit(("bwa", "idx"))
    store = PosteriorStore(block_size=2)
    svc_a = PredictionService(lot_a, store=store, tenant="a", workflow="w1")
    svc_b = PredictionService(lot_b, store=store, tenant="b", workflow="w2")
    assert len(store) == 6 and store.num_blocks == 3
    pre_evict = store.snapshot()

    assert store.evict("a", "w1") == 4
    assert len(store) == 2
    # rows 0-3 lived in blocks 0-1; with no live row left those blocks drop
    # their backing arrays
    assert store.num_free_blocks == 2
    # snapshots taken before the evict keep serving the old rows ...
    assert TaskKey("a", "w1", "t0") in pre_evict
    # ... new ones refuse them, and the other namespace is untouched
    with pytest.raises(KeyError):
        store.snapshot().row_of(TaskKey("a", "w1", "t0"))
    assert svc_b.predict_batch([PredictionQuery("bwa", None, 1.0)]).shape \
        == (1, 3)

    # the evicted namespace's service fails loudly, not with stale data
    with pytest.raises(RuntimeError, match="evicted"):
        svc_a.predict_batch([PredictionQuery("t0", None, 1.0)])

    # a new workflow recycles the freed row slots instead of growing
    lot_c = _fit(("x0", "x1", "x2"))
    PredictionService(lot_c, store=store, tenant="c", workflow="w3")
    assert len(store) == 5
    assert store.num_blocks == 3          # no new blocks allocated
    assert store.num_free_blocks == 0     # recycled slots rematerialized them
    evicted_rows = {0, 1, 2, 3}
    reused = {store.snapshot().row_of(TaskKey("c", "w3", t))
              for t in ("x0", "x1", "x2")}
    assert reused < evicted_rows


def test_evict_unknown_namespace_raises():
    store = PosteriorStore()
    store.bind("a", "w", _fit(("bwa",)))
    with pytest.raises(KeyError, match="no rows"):
        store.evict("a", "nope")


def test_frontend_backpressure_cap():
    """predict_async fails fast with QueueFullError once
    max_pending_batches caller batches are parked; a flush drains the
    window and the front-end accepts again."""
    from repro.store import QueueFullError
    store = PosteriorStore()
    store.bind("a", "w", _fit(("bwa", "idx")))
    fe = AsyncPredictionFrontend(store, auto_flush=False,
                                 max_pending_batches=2)
    qs = _queries(("bwa",), (None,))
    futs = [fe.predict_async(qs, "a", "w") for _ in range(2)]
    with pytest.raises(QueueFullError, match="max_pending_batches=2"):
        fe.predict_async(qs, "a", "w")
    assert fe.flush() == 2
    for f in futs:
        assert f.result(timeout=5).shape == (len(qs), 3)
    # drained -> accepting again
    f3 = fe.predict_async(qs, "a", "w")
    fe.flush()
    assert f3.result(timeout=5).shape == (len(qs), 3)
    with pytest.raises(ValueError):
        AsyncPredictionFrontend(store, auto_flush=False,
                                max_pending_batches=0)


def test_snapshot_between_evict_and_recycle_refuses_new_keys():
    """a snapshot taken after evict() but before a recycling put_many must
    refuse the recycled keys (KeyError) — never silently serve the evicted
    tenant's old rows for them."""
    lot_a = _fit(("t0", "t1"))
    store = PosteriorStore(block_size=2)
    store.bind("a", "w1", lot_a)
    store.evict("a", "w1")
    stale = store.snapshot()              # index copied at this point
    store.bind("c", "w3", _fit(("x0",)))  # recycles freed row 0
    fresh = store.snapshot()
    assert fresh.row_of(TaskKey("c", "w3", "x0")) == 0
    with pytest.raises(KeyError):
        stale.row_of(TaskKey("c", "w3", "x0"))


# --- stacked writes (the fleet refresh's publish) --------------------------------
def _stacked_rows(rng, n):
    from repro.store.compute import LEAF_SHAPES
    return {leaf: rng.normal(size=(n,) + shape).astype(np.float32)
            for leaf, shape in LEAF_SHAPES.items()}


def _seeded_store(rng, n=10, block_size=4):
    store = PosteriorStore(block_size=block_size)
    rows = _stacked_rows(rng, n)
    store.put_many([(f"a/w/t{i}", {k: v[i] for k, v in rows.items()})
                    for i in range(n)])
    return store


def test_put_stacked_rows_equal_put_many():
    """Existing and new keys, across blocks: the same rows, row slots,
    generation and block generations as put_many writes."""
    stores = [_seeded_store(np.random.default_rng(3)) for _ in range(2)]
    keys = ["a/w/t7", "a/w/t1", "b/w/new", "a/w/t2"]
    rows = _stacked_rows(np.random.default_rng(4), len(keys))
    stores[0].put_many([(k, {leaf: v[i] for leaf, v in rows.items()})
                        for i, k in enumerate(keys)])
    stores[1].put_stacked(keys, rows)
    a, b = stores
    assert a.generation == b.generation
    assert a._block_gen == b._block_gen and a._rows == b._rows
    for key in a.task_keys():
        for leaf, v in a.get(key).items():
            assert v.tobytes() == b.get(key)[leaf].tobytes(), (key, leaf)


@pytest.mark.parametrize("fault", ["shape", "duplicate", "bad_key"])
def test_put_stacked_atomic_on_bad_input(fault):
    store = _seeded_store(np.random.default_rng(5))
    gen, rows, blocks = store.generation, dict(store._rows), \
        list(store._blocks)
    snap = store.snapshot()
    keys = ["a/w/t0", "a/w/t5", "a/w/fresh"]
    leaves = _stacked_rows(np.random.default_rng(6), 3)
    if fault == "shape":
        leaves["sigma"] = leaves["sigma"][:, :1]
    elif fault == "duplicate":
        keys[2] = "a/w/t0"
    else:
        keys[2] = "not-a-key"
    with pytest.raises(ValueError):
        store.put_stacked(keys, leaves)
    assert store.generation == gen and store._rows == rows
    assert all(x is y for x, y in zip(store._blocks, blocks))
    assert len(store._blocks) == len(blocks)
    assert store.snapshot() is snap


def test_put_stacked_keeps_earlier_snapshots():
    store = _seeded_store(np.random.default_rng(7))
    old = store.snapshot()
    before = {k: old.get(k) for k in store.task_keys()}
    keys = ["a/w/t0", "a/w/t9"]
    store.put_stacked(keys, _stacked_rows(np.random.default_rng(8), 2))
    for k, row in before.items():
        for leaf, v in old.get(k).items():
            assert v.tobytes() == row[leaf].tobytes()
    assert store.get("a/w/t0")["mu"].tobytes() != before["a/w/t0"][
        "mu"].tobytes()


def test_put_stacked_bumps_only_touched_blocks():
    store = _seeded_store(np.random.default_rng(9), n=12, block_size=4)
    gen0 = dict(store._block_gen)
    store.put_stacked(["a/w/t9", "a/w/t1"],
                      _stacked_rows(np.random.default_rng(10), 2))
    assert store._block_gen == {0: store.generation, 1: gen0[1],
                                2: store.generation}
