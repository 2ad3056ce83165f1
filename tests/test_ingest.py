"""Megabatched observation ingest: the batched write path must be an
exact replay of the scalar one.

Covers the whole stack: `bayes.nig_update_batch` bit-parity vs the
chained scalar `nig_update` (float64 oracle), with no form beside the
float64 ones; `OnlinePredictor.observe_many` digest/prediction
equivalence with the scalar observe chain under adversarial streams
(unknown tasks, remote + unknown nodes, interleaved predicts);
`OpLog.append_many` group commit (one frame + one flush, dense acks,
torn-group truncation keeps the acked watermark); one COW generation per
`PosteriorStore.sync_bindings` batch; the `observe_many` RPC +
client-side coalescing window + `IngestStats` in shard health; ingest
backpressure and wrong_shard all-or-nothing re-routing; and batch-dirty
rows feeding the fused decision plane in one dirty-row pass.

Runs under the real `hypothesis` when installed, else under the
deterministic `tests/_hypothesis_fallback.py` shim (same @given surface).
"""
import asyncio
import os

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import bayes
from repro.online import IngestStats, PredictionService, TaskCompletion
from repro.serve import (OpLog, PartialObserveError, RemoteError,
                         RetryPolicy, ServingClient, ShardInfo, ShardMap,
                         boot_shard, state_digest)
from repro.store import PosteriorStore
from repro.store.frontend import QueueFullError
from serve_helpers import TENANTS, bootstrap, make_benches, make_predictor

ADV_TASKS = ("bwa", "idx", "sort", "nope")           # "nope" is unknown
ADV_NODES = (None, "local", "A1", "N2", "ghost")     # "ghost" is unknown


def _run(coro):
    return asyncio.run(coro)


def _stream(rng, n):
    """Adversarial completion stream: unknown tasks, local + remote +
    unknown nodes, all interleaved."""
    return [TaskCompletion("wf", f"u{i}",
                           ADV_TASKS[int(rng.integers(len(ADV_TASKS)))],
                           ADV_NODES[int(rng.integers(len(ADV_NODES)))],
                           float(rng.uniform(0.05, 4.0)),
                           float(rng.uniform(5.0, 300.0)))
            for i in range(n)]


def _fresh_nigs(rng, t):
    nigs = []
    for _ in range(t):
        k = int(rng.integers(4, 9))
        x = rng.uniform(0.05, 2.0, k)
        y = 2.0 + 20.0 * x + rng.normal(0, 0.3, k)
        nigs.append(bayes.nig_from_blr(bayes.fit_blr(x, y)))
    return nigs


# --- core: the batched fold vs the scalar chain --------------------------------

@settings(max_examples=8, deadline=None)
@given(seed=st.integers(0, 10 ** 6), t=st.integers(1, 12),
       kmax=st.integers(0, 7))
def test_nig_update_batch_bitwise_matches_scalar_chain(seed, t, kmax):
    rng = np.random.default_rng(seed)
    nigs = _fresh_nigs(rng, t)
    xs = [list(rng.uniform(0.05, 3.0, int(rng.integers(0, kmax + 1))))
          for _ in range(t)]
    ys = [[float(rng.uniform(4.0, 120.0)) for _ in row] for row in xs]
    # both float64 forms must match the scalar chain bitwise: 'chain'
    # (python-float per-task chains) and 'vec' (the masked (T, K) fold);
    # 'numpy' size-dispatches between them
    by_impl = {impl: bayes.nig_update_batch(nigs, xs, ys, impl=impl)
               for impl in ("numpy", "chain", "vec")}
    for impl, got in by_impl.items():
        for nig, xrow, yrow, g in zip(nigs, xs, ys, got):
            want = dict(nig)
            for x, y in zip(xrow, yrow):
                want = bayes.nig_update(want, x, y)
            for key in ("mu", "v", "prec", "a", "b", "n_obs"):
                np.testing.assert_array_equal(
                    np.asarray(g[key]), np.asarray(want[key]),
                    err_msg=f"impl {impl!r}: leaf {key!r} is not "
                            f"bit-identical")
    got = by_impl["numpy"]
    # inputs must be untouched (predictors hand over live state)
    for nig, xrow, g in zip(nigs, xs, got):
        assert g is not nig
        assert nig["n_obs"] == g["n_obs"] - len(xrow)


def test_nig_update_batch_validates_ragged_rows():
    nigs = _fresh_nigs(np.random.default_rng(0), 2)
    with pytest.raises(ValueError):
        bayes.nig_update_batch(nigs, [[1.0]], [[2.0]])
    with pytest.raises(ValueError):
        bayes.nig_update_batch(nigs, [[1.0], []], [[2.0, 3.0], []])
    # empty batch is the identity (fresh dict copies, same values)
    out = bayes.nig_update_batch(nigs, [[], []], [[], []])
    for o, n in zip(out, nigs):
        assert o is not n and o["n_obs"] == n["n_obs"]


# --- predictor: observe_many == scalar observe chain ---------------------------

@settings(max_examples=6, deadline=None)
@given(seed=st.integers(0, 10 ** 6), n=st.integers(1, 60),
       chunk=st.integers(1, 13))
def test_observe_many_digest_identical_to_scalar_chain(seed, n, chunk):
    rng = np.random.default_rng(seed)
    comps = _stream(rng, n)
    a = make_predictor(salt=3)          # scalar oracle
    b = make_predictor(salt=3)          # batched ingest
    for c in comps:
        a.observe(c)
    applied = 0
    for i in range(0, n, chunk):
        applied += b.observe_many(comps[i:i + chunk])
        # interleaved reads must not disturb the write path
        assert b.predict("bwa", 1.7) == b.predict("bwa", 1.7)
    assert state_digest(a) == state_digest(b)
    assert a.version == b.version
    for task in ("bwa", "idx", "sort"):
        assert a.predict(task, 2.3) == b.predict(task, 2.3)
    for node in ("A1", "N2", "ghost"):
        assert a.node_correction(node) == b.node_correction(node)
    # telemetry: every record was counted exactly once, one lock
    # acquisition per batch
    assert b.ingest.records == n
    # unknown-task records are dropped (exactly as the scalar chain
    # drops them); every known-task record is folded or scalar, once
    assert b.ingest.folded + b.ingest.scalar == \
        sum(1 for c in comps if c.task in b.tasks)
    assert b.ingest.lock_acquisitions == b.ingest.batches == \
        (n + chunk - 1) // chunk


def test_observe_many_version_delta_matches_scalar_chain():
    rng = np.random.default_rng(11)
    comps = _stream(rng, 40)
    a, b = make_predictor(salt=1), make_predictor(salt=1)
    v0 = a.version
    for c in comps:
        a.observe(c)
    applied = b.observe_many(comps)
    assert applied == a.version - v0 == b.version - v0
    assert b.observe_many([]) == 0


def test_observe_many_all_local_is_one_fold_dispatch():
    p = make_predictor(salt=0)
    comps = [TaskCompletion("wf", f"u{i}", ADV_TASKS[i % 3], "local",
                            0.5 + 0.1 * i, 20.0 + i) for i in range(12)]
    p.observe_many(comps)
    assert p.ingest.fold_dispatches == 1
    assert p.ingest.folded == 12 and p.ingest.scalar == 0
    # one shared change-feed publication for the whole fold group
    seqs = {p.change_seq(t) for t in ("bwa", "idx", "sort")}
    assert len(seqs) == 1


def test_ingest_stats_merge_and_dict_roundtrip():
    a = IngestStats(batches=1, records=3, folded=2, scalar=1,
                    fold_dispatches=1, lock_acquisitions=1)
    b = IngestStats(batches=2, records=5, flushes=2,
                    generations_published=1)
    m = a.merge(b)
    assert m.batches == 3 and m.records == 8 and m.folded == 2
    assert m.as_dict()["flushes"] == 2
    assert set(m.as_dict()) == set(IngestStats().as_dict())


# --- oplog group commit --------------------------------------------------------

def test_oplog_group_commit_one_flush_dense_acks(tmp_path):
    path = os.path.join(str(tmp_path), "g.oplog")
    log = OpLog(path)
    assert log.append({"t": "a", "w": "w", "c": {"i": 0}}) == 1
    seqs = log.append_many([{"t": "a", "w": "w", "c": {"i": k}}
                            for k in range(1, 6)])
    assert seqs == [2, 3, 4, 5, 6]          # dense, in order
    assert log.flush_count == 2             # one commit per append call
    assert log.append_many([]) == []
    assert log.append({"t": "a", "w": "w", "c": {"i": 9}}) == 7
    log.close()
    # replay expands group frames: consumers never see the framing
    recs = list(OpLog.replay(path))
    assert [r["q"] for r in recs] == list(range(1, 8))
    assert [r["c"]["i"] for r in recs] == [0, 1, 2, 3, 4, 5, 9]
    assert list(OpLog.replay(path, after_seq=4)) == recs[4:]
    # reopening recovers the watermark from inside group frames
    log2 = OpLog(path)
    assert log2.last_seq == 7
    log2.close()


def test_oplog_torn_group_tail_keeps_acked_watermark(tmp_path):
    path = os.path.join(str(tmp_path), "torn.oplog")
    log = OpLog(path)
    log.append({"t": "a", "w": "w", "c": {"i": 0}})
    log.append_many([{"t": "a", "w": "w", "c": {"i": k}}
                     for k in range(1, 4)])
    log.close()
    whole = open(path, "rb").read()
    # find the start of the group frame and tear mid-group: a crash hit
    # while the commit was in flight, so NO record of it was ever acked
    solo = OpLog(os.path.join(str(tmp_path), "solo.oplog"))
    solo.append({"t": "a", "w": "w", "c": {"i": 0}})
    solo.close()
    cut = os.path.getsize(os.path.join(str(tmp_path), "solo.oplog"))
    with open(path, "wb") as f:
        f.write(whole[:cut + max(1, (len(whole) - cut) // 2)])
    recs = list(OpLog.replay(path))
    assert [r["q"] for r in recs] == [1]    # whole group dropped
    log2 = OpLog(path)                      # reopen tolerates the tear
    assert log2.last_seq == 1
    assert log2.append({"t": "a", "w": "w", "c": {"i": 9}}) == 2
    log2.close()


# --- store: one COW generation per ingest batch --------------------------------

def test_sync_bindings_publishes_one_generation():
    store = PosteriorStore()
    benches = make_benches()
    preds = {}
    for i, (t, w) in enumerate(TENANTS[:3]):
        preds[(t, w)] = make_predictor(salt=i)
        store.bind(t, w, preds[(t, w)], benches, sync=False)
    bindings = [store.binding(t, w) for t, w in TENANTS[:3]]
    gen_pre = store.generation
    rows0 = store.sync_bindings(bindings)       # never-synced: full sync
    assert rows0 == sum(len(list(p.task_names())) for p in preds.values())
    assert store.generation == gen_pre + 1      # one generation for all 3
    gen0 = store.generation
    for (t, w), p in preds.items():
        p.observe_many([TaskCompletion(w, f"u{k}", "bwa", "local",
                                       1.0 + k, 30.0 + k)
                        for k in range(3)])
    rows = store.sync_bindings(bindings)
    assert rows == 3                            # one dirty row per tenant
    assert store.generation == gen0 + 1         # ONE generation for all
    # nothing due afterwards; a second call is a no-op generation-wise
    assert store.sync_bindings(bindings) == 0
    assert store.generation == gen0 + 1
    # rows match what per-binding sync would have produced
    oracle = PosteriorStore()
    for i, (t, w) in enumerate(TENANTS[:3]):
        p = make_predictor(salt=i)
        p.observe_many([TaskCompletion(w, f"u{k}", "bwa", "local",
                                       1.0 + k, 30.0 + k)
                        for k in range(3)])
        oracle.bind(t, w, p, benches)
        oracle.binding(t, w).sync()
    for t, w in TENANTS[:3]:
        key = store.binding(t, w).key_str("bwa")
        got = store.snapshot().gather([key])
        want = oracle.snapshot().gather([key])
        assert set(got) == set(want)
        for leaf in got:
            np.testing.assert_array_equal(got[leaf], want[leaf],
                                          err_msg=f"leaf {leaf!r}")


def test_sync_bindings_default_and_detached():
    store = PosteriorStore()
    t, w = TENANTS[0]
    p = make_predictor(salt=0)
    store.bind(t, w, p, make_benches())
    p.observe(TaskCompletion(w, "u0", "bwa", "local", 1.0, 30.0))
    assert store.sync_bindings() == 1           # default: every binding
    b = store.binding(t, w)
    store.evict(t, w)
    with pytest.raises(RuntimeError):
        store.sync_bindings([b])


# --- serve tier: observe_many RPC, coalescing, stats ---------------------------

async def _boot_fleet(n, tmp, client_opts=None, **opts):
    sids = [f"s{i}" for i in range(n)]
    m = ShardMap([ShardInfo(s, "127.0.0.1", 0) for s in sids])
    servers = []
    for sid in sids:
        srv = boot_shard(
            sid, m, bootstrap,
            checkpoint_dir=os.path.join(tmp, sid + "_ckpt"),
            oplog_path=os.path.join(tmp, sid + ".oplog"),
            window_s=0.001, **opts)
        await srv.start()
        m = m.with_address(sid, "127.0.0.1", srv.port)
        servers.append(srv)
    for srv in servers:
        srv.map = m
    return servers, ServingClient(m, **(client_opts or {}))


async def _close_fleet(servers, client):
    await client.close()
    for srv in servers:
        await srv.aclose()


def test_observe_many_rpc_digest_matches_scalar_ingest(tmp_path):
    async def go():
        servers, client = await _boot_fleet(2, str(tmp_path))
        try:
            rng = np.random.default_rng(5)
            batch, oracles = [], {}
            for i, (t, w) in enumerate(TENANTS):
                oracles[(t, w)] = make_predictor(salt=i)
                for j in range(5):
                    node = ("local", "A1")[j % 2]
                    c = TaskCompletion(w, f"u{i}{j}", ADV_TASKS[j % 3],
                                       node, float(rng.uniform(0.1, 3.0)),
                                       float(rng.uniform(10.0, 200.0)))
                    batch.append((c, t, w))
                    oracles[(t, w)].observe(c)
            seqs = await client.observe_many(batch)
            assert all(isinstance(s, int) and s >= 1 for s in seqs)
            # per-shard acks are dense from 1
            per_shard = {}
            for (c, t, w), s in zip(batch, seqs):
                per_shard.setdefault(
                    client.map.shard_for(f"{t}/{w}"), []).append(s)
            for sid, ss in per_shard.items():
                assert sorted(ss) == list(range(1, len(ss) + 1))
            # the group-committed, fold-batched ingest produced EXACTLY
            # the scalar-chain state, namespace by namespace
            for (t, w), oracle in oracles.items():
                assert await client.digest(t, w) == state_digest(oracle)
            # ingest telemetry rides the health RPC; group commit means
            # strictly fewer flushes than records
            ing = IngestStats()
            for sid in client.map.shard_ids():
                h = await client.health(sid)
                assert "ingest" in h
                ing = ing.merge(IngestStats(**h["ingest"]))
            assert ing.records == len(batch)
            assert ing.flushes < ing.records
            assert ing.generations_published >= 1
            assert ing.lock_acquisitions < ing.records
        finally:
            await _close_fleet(servers, client)
    _run(go())


def test_client_observe_window_coalesces_scalar_observes(tmp_path):
    async def go():
        servers, client = await _boot_fleet(
            1, str(tmp_path), client_opts={"observe_window_s": 0.02})
        try:
            t, w = TENANTS[0]
            futs = [client.observe(
                TaskCompletion(w, f"cw{i}", "bwa", "local", 1.0 + i, 30.0),
                t, w) for i in range(8)]
            seqs = await asyncio.gather(*futs)
            assert sorted(seqs) == list(range(1, 9))
            h = await client.health("s0")
            ing = h["ingest"]
            # the window turned 8 RPC-less scalar observes into one
            # coalesced round: one batch, one lock, one group commit
            assert ing["records"] == 8
            assert ing["flushes"] == 1
            assert ing["lock_acquisitions"] == 1
            assert ing["generations_published"] == 1
        finally:
            await _close_fleet(servers, client)
    _run(go())


def test_observe_many_backpressure_nothing_applied(tmp_path):
    async def go():
        servers, client = await _boot_fleet(
            1, str(tmp_path),
            client_opts={"retry": RetryPolicy(max_attempts=2,
                                              base_backoff_s=0.01)},
            ingest_window_s=0.5, max_pending_ingest=2)
        try:
            t, w = TENANTS[0]
            parked = [asyncio.ensure_future(client.observe(
                TaskCompletion(w, f"p{i}", "bwa", "local", 1.0, 30.0),
                t, w)) for i in range(2)]
            await asyncio.sleep(0.05)
            with pytest.raises(QueueFullError):
                await client.observe_many(
                    [(TaskCompletion(w, f"x{i}", "bwa", "local", 1.0, 30.0),
                      t, w) for i in range(3)])
            # the parked pair still lands; the shed batch left NO trace
            assert sorted(await asyncio.gather(*parked)) == [1, 2]
            h = await client.health("s0")
            assert h["seq"] == 2
            assert h["ingest"]["records"] == 2
        finally:
            await _close_fleet(servers, client)
    _run(go())


def test_observe_many_wrong_shard_reroutes_whole_groups(tmp_path):
    async def go():
        grown = ShardMap([ShardInfo("s0", "127.0.0.1", 0)]) \
            .with_shard("s1", "127.0.0.1", 0)
        servers = []
        for sid in ("s0", "s1"):
            srv = boot_shard(
                sid, grown, bootstrap, window_s=0.001,
                oplog_path=os.path.join(str(tmp_path), sid + ".oplog"))
            await srv.start()
            grown = grown.with_address(sid, "127.0.0.1", srv.port)
            servers.append(srv)
        for srv in servers:
            srv.map = grown
        moved = [(t, w) for t, w in TENANTS
                 if grown.shard_for(f"{t}/{w}") == "s1"]
        assert moved, "fixture fleet must place something on s1"
        stale = ShardMap([ShardInfo("s0", *grown.address_of("s0"))])
        client = ServingClient(stale)
        try:
            batch = [(TaskCompletion(w, f"m{i}", "bwa", "local",
                                     1.0 + i, 25.0), t, w)
                     for i, (t, w) in enumerate(moved)]
            seqs = await client.observe_many(batch)
            assert all(s >= 1 for s in seqs)
            # one wrong_shard round adopted the newer map; the records
            # landed exactly once on the right shard
            assert client.map.version == grown.version
            ing = (await client.health("s1"))["ingest"]
            assert ing["records"] == len(batch)
        finally:
            await client.close()
            for srv in servers:
                await srv.aclose()
    _run(go())


def test_observe_window_drain_chains_for_midflight_arrivals(tmp_path):
    """Observes parked while a drain round is on the wire see a
    still-running drain task and schedule nothing — the finishing drain
    must chain a successor for them, or their futures strand forever."""
    async def go():
        # slow shard ingest window keeps the first drain's RPC in flight
        # long enough for a second observe to park behind it
        servers, client = await _boot_fleet(
            1, str(tmp_path), client_opts={"observe_window_s": 0.01},
            ingest_window_s=0.2)
        try:
            t, w = TENANTS[0]
            fut1 = asyncio.ensure_future(client.observe(
                TaskCompletion(w, "mf0", "bwa", "local", 1.0, 30.0), t, w))
            await asyncio.sleep(0.08)      # drain 1 is awaiting the shard
            fut2 = asyncio.ensure_future(client.observe(
                TaskCompletion(w, "mf1", "bwa", "local", 1.5, 40.0), t, w))
            seqs = await asyncio.wait_for(asyncio.gather(fut1, fut2), 10.0)
            assert sorted(seqs) == [1, 2]
        finally:
            await _close_fleet(servers, client)
    _run(go())


def test_observe_many_partial_round_keeps_survivor_acks(tmp_path):
    """A failing shard group fails only its own records: acks returned
    by the round's other groups are durable and must surface, not be
    discarded by a round-wide raise (retrying them would double-count)."""
    async def go():
        servers, client = await _boot_fleet(2, str(tmp_path))
        try:
            t, w = TENANTS[0]
            good_sid = client.map.shard_for(f"{t}/{w}")
            # an unbound namespace routed to the OTHER shard: its group
            # answers unknown_namespace while the good group lands
            gt = gw = None
            for i in range(200):
                cand = (f"ghost{i}", "wf")
                if client.map.shard_for(f"{cand[0]}/{cand[1]}") != good_sid:
                    gt, gw = cand
                    break
            assert gt is not None
            oracle = make_predictor(salt=0)
            comp = TaskCompletion(w, "pr0", "bwa", "local", 1.0, 30.0)
            with pytest.raises(PartialObserveError) as ei:
                await client.observe_many(
                    [(comp, t, w),
                     (TaskCompletion(gw, "pr1", "bwa", "local", 1.0, 30.0),
                      gt, gw)])
            e = ei.value
            assert e.seqs[0] == 1 and e.seqs[1] is None
            assert isinstance(e.errors[1], RemoteError)
            assert e.errors[1].kind == "unknown_namespace"
            # the acked record really landed, exactly once
            oracle.observe(comp)
            assert await client.digest(t, w) == state_digest(oracle)

            # the coalescing window resolves the same split per future:
            # the durable record gets its ack, only the bad one errors
            win = ServingClient(client.map, observe_window_s=0.01)
            try:
                comp2 = TaskCompletion(w, "pr2", "bwa", "local", 2.0, 50.0)
                res = await asyncio.wait_for(asyncio.gather(
                    win.observe(comp2, t, w),
                    win.observe(TaskCompletion(gw, "pr3", "bwa", "local",
                                               1.0, 30.0), gt, gw),
                    return_exceptions=True), 10.0)
                assert res[0] == 2
                assert isinstance(res[1], RemoteError)
                assert res[1].kind == "unknown_namespace"
            finally:
                await win.close()
            oracle.observe(comp2)
            assert await client.digest(t, w) == state_digest(oracle)
        finally:
            await _close_fleet(servers, client)
    _run(go())


def test_health_surfaces_and_clears_ingest_publish_failure(tmp_path):
    """A failed binding-sync publish after a drain must be visible to
    operators via the health RPC, and must clear once a later publish
    succeeds (it reflects CURRENT staleness, not history)."""
    async def go():
        servers, client = await _boot_fleet(1, str(tmp_path))
        try:
            srv = servers[0]
            t, w = TENANTS[0]
            orig = srv.store.sync_bindings

            def boom(*a, **k):
                raise RuntimeError("disk full")

            srv.store.sync_bindings = boom
            seq = await client.observe(
                TaskCompletion(w, "hf0", "bwa", "local", 1.0, 30.0), t, w)
            assert seq == 1            # ack stands: durability committed
            h = await client.health("s0")
            assert h["last_ingest_error"] is not None
            assert "disk full" in h["last_ingest_error"]
            srv.store.sync_bindings = orig
            await client.observe(
                TaskCompletion(w, "hf1", "bwa", "local", 1.5, 40.0), t, w)
            h = await client.health("s0")
            assert h["last_ingest_error"] is None
        finally:
            await _close_fleet(servers, client)
    _run(go())


def test_fold_stacked_auto_stays_on_float64_chain():
    """The ingest fold feeds digest-bearing streaming states, so its
    default is bitwise the scalar `nig_update` chain on EVERY backend,
    and it has no float32 form to opt into: a device impl name raises."""
    rng = np.random.default_rng(11)
    nigs = _fresh_nigs(rng, 5)
    xs = [list(rng.uniform(0.05, 3.0, int(rng.integers(0, 5))))
          for _ in nigs]
    ys = [[float(rng.uniform(4.0, 120.0)) for _ in row] for row in xs]
    got = bayes.nig_update_batch(nigs, xs, ys)
    for nig, xr, yr, g in zip(nigs, xs, ys, got):
        want = dict(nig)
        for x, y in zip(xr, yr):
            want = bayes.nig_update(want, x, y)
        for key in ("mu", "v", "prec", "a", "b", "n_obs"):
            np.testing.assert_array_equal(
                np.asarray(g[key]), np.asarray(want[key]),
                err_msg=f"the default fold diverges on leaf {key!r}")
    xs[0] = xs[0] or [1.0]
    ys[0] = ys[0] or [2.0]
    for impl in ("auto", "scan", "pallas", "interpret"):
        with pytest.raises(ValueError, match="unknown impl"):
            bayes.nig_update_batch(nigs, xs, ys, impl=impl)


# --- fused decision plane: batch-dirty rows in one pass ------------------------

def test_batch_ingest_feeds_fused_plane_in_one_pass():
    from repro.sched.fused import FusedPlane
    from repro.workflow.simulator import random_cluster
    from repro.sched.cluster import TARGET_MACHINES

    rng = np.random.default_rng(3)
    pred = make_predictor(salt=0)
    nodes = random_cluster(rng, list(TARGET_MACHINES), n_nodes=4)
    store = PosteriorStore()
    svc = PredictionService(pred, make_benches(), store=store,
                            tenant=TENANTS[0][0], workflow=TENANTS[0][1])
    entries = [(f"t{i}", ADV_TASKS[i % 3], 0.3 + 0.2 * i)
               for i in range(9)]
    plane = FusedPlane(svc, nodes, entries=entries)
    plane.sync()                                    # resident full gather
    d0 = plane.stats.predict_dispatches
    # one cross-task ingest batch dirties bwa + idx rows
    pred.observe_many([TaskCompletion("wf", f"u{k}", task, "local",
                                      0.5 + 0.1 * k, 22.0 + k)
                       for k, task in enumerate(("bwa", "idx", "bwa"))])
    refreshed = plane.sync()
    # every entry backed by a dirty task re-gathered — dirty detection
    # is block-granular, so co-located rows may ride along — in ONE
    # dirty-row pass -> ONE predictive dispatch for the whole batch
    dirty_entries = [u for u, task, _ in entries if task in ("bwa", "idx")]
    assert len(dirty_entries) <= refreshed <= len(entries)
    assert plane.stats.predict_dispatches == d0 + 1
    assert plane.stats.full_gathers == 1
    # the resident rows equal a cold plane's full re-gather
    cold = FusedPlane(svc, nodes, entries=entries)
    cold.sync()
    np.testing.assert_array_equal(plane._mean_raw, cold._mean_raw)
    np.testing.assert_array_equal(plane._std_raw, cold._std_raw)
