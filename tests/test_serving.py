"""Production-rate query serving: batched unknown-itemset sweeps
through the live dispatchers, the negative border, device-resident
top-k, per-kind server counters, and multi-tenant fairness."""
import itertools
import threading

import numpy as np
import pytest
from _hyp import given, settings, st

import repro.core.streaming as streaming_mod
from repro.core.fpm import mine
from repro.core.join_backend import NumpyBackend, SweepDispatcher
from repro.core.streaming import (PatternServer, PatternSnapshot,
                                  StreamingMiner, TenantHub)
from repro.core.tidlist import BitmapArena, pack_database


def rand_db(n, items=12, seed=7):
    rng = np.random.default_rng(seed)
    return [sorted(rng.choice(items, size=rng.integers(2, 6),
                              replace=False).tolist())
            for _ in range(n)]


def brute(db, itemset):
    want = set(itemset)
    return sum(1 for t in db if want <= set(t))


def batch_mine(db, n_items, ms, **kw):
    return mine(pack_database(db, n_items), ms, **kw)[0]


# ------------------------------------------------- dispatcher coalescing
def test_query_and_candidate_sweeps_share_one_flush():
    """A candidate-class sweep and a priority query sweep pending on
    the same dispatcher drain in ONE flush (occupancy 2) — the
    coalescing claim at its most deterministic: flush threshold 2,
    straggler window far beyond the test, so the only way both
    futures resolve is the shared batch."""
    db = rand_db(64, items=8, seed=3)
    arena = BitmapArena.from_bitmaps(pack_database(db, 8))
    disp = SweepDispatcher(arena, NumpyBackend(), n_clients=2,
                           flush_us=5_000_000.0,
                           query_flush_us=5_000_000.0)
    try:
        f_cand = disp.submit(0, (1,))                  # candidate-class
        f_query = disp.submit(2, (3,), priority=True)  # query-class
        assert int(f_cand.result(timeout=10)[0]) == brute(db, (0, 1))
        assert int(f_query.result(timeout=10)[0]) == brute(db, (2, 3))
        assert disp.flushes == 1
        assert disp.requests == 2
        assert disp.query_requests == 1
        assert disp.batch_occupancy > 1
    finally:
        disp.stop()


def test_priority_query_flushes_within_query_window():
    """A lone query-class request must not sit out the full straggler
    window: the dispatcher caps its wait at query_flush_us."""
    db = rand_db(32, items=6, seed=4)
    arena = BitmapArena.from_bitmaps(pack_database(db, 6))
    # candidate flush window 5s; query window 1ms
    disp = SweepDispatcher(arena, NumpyBackend(), n_clients=8,
                           flush_us=5_000_000.0, query_flush_us=1000.0)
    try:
        got = disp.submit(0, (1,), priority=True).result(timeout=2)
        assert int(got[0]) == brute(db, (0, 1))
    finally:
        disp.stop()


# ------------------------------------------------- exactness (sweeps)
@settings(max_examples=12, deadline=None)
@given(st.data())
def test_unknown_itemset_sweeps_match_brute_force(data):
    """support_many answers ARBITRARY itemsets exactly — hypothesis
    drives random databases and random (mostly never-counted) probes
    against per-transaction brute force."""
    n_items = 10
    db = data.draw(st.lists(
        st.lists(st.integers(0, n_items - 1), min_size=1, max_size=6,
                 unique=True),
        min_size=5, max_size=60))
    ms = data.draw(st.integers(1, max(1, len(db) // 2)))
    probes = data.draw(st.lists(
        st.lists(st.integers(0, n_items - 1), min_size=0, max_size=5,
                 unique=True),
        min_size=1, max_size=8))
    sm = StreamingMiner(n_items, ms, initial_db=db, n_workers=2,
                        max_k=3)
    sm.refresh()
    try:
        got = sm.support_many(probes)
        assert got == [brute(db, x) for x in probes]
        # repeats answer identically (now mostly dict hits)
        assert sm.support_many(probes) == got
    finally:
        sm.close()


def test_support_many_is_snapshot_consistent_across_publish():
    """A query batch racing a refresh answers ENTIRELY from the
    generation it was planned against: fired from the before_publish
    hook (mid-refresh, pre-swap) it must see the old boundary for
    every probe — singleton, known, and swept alike."""
    full = rand_db(300, items=12, seed=5)
    sm = StreamingMiner(12, 25, initial_db=full[:200], n_workers=2,
                        max_k=4)
    sm.refresh()
    sm.ingest(full[200:])
    probes = [(0, 1, 2, 3, 4), (3, 4), (1, 5, 7), (2,), ()]
    want_old = [brute(full[:200], x) if x else 200 for x in probes]
    want_new = [brute(full, x) if x else 300 for x in probes]
    seen = {}

    def hook(snapshot):
        seen["mid"] = sm.support_many(probes)

    sm.refresh(before_publish=hook)
    try:
        assert seen["mid"] == want_old
        # after the swap the same probes answer over the full database
        # (mid-refresh backfills went to the superseded store, so they
        # cannot leak stale counts into the new generation)
        assert sm.support_many(probes) == want_new
    finally:
        sm.close()


def test_query_backfill_repeat_hits_and_survives_refresh():
    """An answered query backfills the known store (repeat == dict
    hit) — and a later ingest touching its items re-sweeps rather
    than serving the stale backfill."""
    full = rand_db(260, items=10, seed=11)
    sm = StreamingMiner(10, 10_000, initial_db=full[:200],
                        n_workers=2, max_k=2)   # nothing frequent:
    sm.refresh()                                # every probe sweeps
    srv = PatternServer(sm)
    probe = (0, 1, 2)
    try:
        assert srv.support(probe) == brute(full[:200], probe)
        assert srv.merged_stats()["sweep"] == 1
        assert srv.support(probe) == brute(full[:200], probe)
        stats = srv.merged_stats()
        assert stats["sweep"] == 1 and stats["hit"] == 1
        sm.ingest(full[200:])
        sm.refresh()
        assert srv.support(probe) == brute(full, probe)
    finally:
        sm.close()


# ------------------------------------------------- negative border
def test_negative_border_published_and_served():
    db = ([[0, 1]] * 3 + [[0]] * 10 + [[1]] * 10 + [[2, 3]] * 12)
    sm = StreamingMiner(4, 5, initial_db=db, n_workers=2, max_k=3)
    sm.refresh()
    try:
        snap = sm.snapshot
        # counted but infrequent: published on the border, flagged
        assert snap.support((0, 1)) is None
        assert snap.support((0, 1), include_infrequent=True) == 3
        assert snap.lookup((0, 1)) == (3, True)
        assert snap.lookup((2, 3)) == (12, False)
        assert snap.lookup((0, 2))[1] is True   # support 0, counted
        srv = PatternServer(sm)
        assert srv.support((0, 1)) == 3         # border == dict hit,
        assert srv.merged_stats()["sweep"] == 0  # no sweep needed
    finally:
        sm.close()


# ------------------------------------------------- device-resident top-k
def _reference_top_k(supports, prefix, k):
    """The serving layer's documented ordering, computed the slow way:
    strict extensions of prefix, support descending, lexicographic
    ties."""
    prefix = tuple(sorted(prefix))
    rows = [(x, s) for x, s in supports.items()
            if len(x) > len(prefix) and x[:len(prefix)] == prefix]
    return [(x, -ns) for ns, x in
            sorted(((-s, x) for x, s in rows))[:k]]


def _tie_heavy_supports():
    rng = np.random.default_rng(0)
    supports = {}
    for i in range(20):
        supports[(i,)] = 50 + int(rng.integers(0, 4))
    for i, j in itertools.combinations(range(12), 2):
        supports[(i, j)] = 10 + (i + j) % 5          # dense tie bands
    for x in [(0, 1, 2), (0, 1, 3), (0, 2, 5), (1, 2, 3), (2, 3, 4)]:
        supports[x] = 7
    return supports


@pytest.mark.parametrize("prefix,k", [
    ((), 10), ((), 1000), ((0,), 4), ((1,), 1), ((0, 1), 5),
    ((0, 1, 2), 3), ((9, 10, 11, 12), 2), ((), 0),
])
def test_top_k_host_and_device_paths_match_reference(monkeypatch,
                                                     prefix, k):
    supports = _tie_heavy_supports()
    want = _reference_top_k(supports, prefix, k)
    host = PatternSnapshot(1, 100, 2, supports).top_k(prefix, k)
    assert host == want
    # force the device-resident path on the same data
    monkeypatch.setattr(streaming_mod, "TOPK_DEVICE_MIN", 0)
    dev = PatternSnapshot(1, 100, 2, supports).top_k(prefix, k)
    assert dev == want


def test_top_k_device_path_on_miner(monkeypatch):
    monkeypatch.setattr(streaming_mod, "TOPK_DEVICE_MIN", 0)
    db = rand_db(200, items=10, seed=13)
    sm = StreamingMiner(10, 20, initial_db=db, n_workers=2, max_k=4)
    sm.refresh()
    try:
        supports = dict(sm.snapshot.supports)
        for prefix in [(), (0,), (1, 3)]:
            assert sm.snapshot.top_k(prefix, 7) == _reference_top_k(
                supports, prefix, 7)
    finally:
        sm.close()


# ------------------------------------------------- server counters
def test_server_counts_queries_per_kind_thread_safe():
    db = rand_db(150, items=8, seed=17)
    sm = StreamingMiner(8, 15, initial_db=db, n_workers=2, max_k=3)
    sm.refresh()
    srv = PatternServer(sm)
    hot = next(iter(sm.snapshot.supports))
    per_thread = 50

    def hammer():
        for _ in range(per_thread):
            srv.support(hot)
            srv.top_k((), 3)
            srv.frequent()

    threads = [threading.Thread(target=hammer) for _ in range(8)]
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        stats = srv.merged_stats()
        # support(known) + frequent() count as hits; no lost updates
        assert stats["hit"] == 2 * 8 * per_thread
        assert stats["top_k"] == 8 * per_thread
        assert stats["sweep"] == 0
        assert srv.queries == 3 * 8 * per_thread
    finally:
        sm.close()


# ------------------------------------------------- multi-tenant hub
def test_tenant_hub_isolation_fairness_and_serving():
    db_a = rand_db(150, items=12, seed=1)
    db_b = rand_db(120, items=12, seed=2)
    with TenantHub(12, n_workers=2, max_k=4) as hub:
        ta = hub.tenant("a", 15, weight=4.0)
        tb = hub.tenant("b", 12)
        assert hub.tenant("a") is ta          # fetch by id
        ta.ingest(db_a[:100])
        tb.ingest(db_b)
        ta.refresh()
        tb.refresh()
        assert dict(ta.snapshot.supports) == batch_mine(
            db_a[:100], 12, 15, max_k=4)
        assert dict(tb.snapshot.supports) == batch_mine(
            db_b, 12, 12, max_k=4)
        # one tenant's second generation leaves the other untouched
        ta.ingest(db_a[100:])
        ta.refresh()
        assert dict(ta.snapshot.supports) == batch_mine(
            db_a, 12, 15, max_k=4)
        assert tb.snapshot.generation == 1
        assert dict(tb.snapshot.supports) == batch_mine(
            db_b, 12, 12, max_k=4)
        # segments are tagged and disjoint; cross-tenant compaction
        # is refused at the arena layer
        segs_a = hub.arena.tenant_segments("a")
        segs_b = hub.arena.tenant_segments("b")
        assert segs_a and segs_b and not set(segs_a) & set(segs_b)
        assert hub.arena.compact(hub.arena.n_segments) == 0
        # serving answers each tenant over ITS stream only — the
        # len-5 probe exceeds max_k, so it always sweeps
        probes = [(0, 1, 2, 3, 4), (3, 4)]
        assert ta.server.support_many(probes) == [
            brute(db_a, x) for x in probes]
        assert tb.server.support_many(probes) == [
            brute(db_b, x) for x in probes]
        stats = hub.tenant_stats()
        assert stats["a"]["queries"]["sweep"] >= 1
        assert stats["b"]["queries"]["sweep"] >= 1
        assert stats["a"]["generation"] == 2
        assert stats["b"]["generation"] == 1
        assert stats["a"]["weight"] == 4.0
        # tenant-tagged tasks were served under the fairness rule
        assert stats["a"]["tasks_served"] > 0
        assert stats["b"]["tasks_served"] > 0


def test_tenant_queries_concurrent_with_refresh_are_exact():
    db_a = rand_db(200, items=10, seed=21)
    db_b = rand_db(150, items=10, seed=22)
    with TenantHub(10, n_workers=2, max_k=3) as hub:
        ta = hub.tenant("a", 20)
        tb = hub.tenant("b", 15)
        ta.ingest(db_a)
        ta.refresh()
        tb.ingest(db_b[:100])
        tb.refresh()
        tb.ingest(db_b[100:])
        probes = [(0, 1, 2, 3, 4), (2, 5)]
        seen = {}

        def hook(snapshot):
            # mid-refresh of B, tenant A's serving stays exact and
            # B still answers over its OLD boundary
            seen["a"] = ta.support_many(probes)
            seen["b"] = tb.support_many(probes)

        tb.refresh(before_publish=hook)
        assert seen["a"] == [brute(db_a, x) for x in probes]
        assert seen["b"] == [brute(db_b[:100], x) for x in probes]
        assert tb.support_many(probes) == [brute(db_b, x)
                                           for x in probes]
