"""Bucket-sweep engine: planning, equivalence across policies ×
granularities × datasets, locality accounting, property tests."""
from itertools import combinations

import numpy as np
import pytest
from _hyp import given, settings, st

from repro.core import tidlist
from repro.core.buckets import (bucket_rows_touched,
                                candidate_rows_touched, rows_to_bytes)
from repro.core.fpm import mine, mine_serial
from repro.core.itemsets import (Bucket, brute_force_frequent,
                                 gen_buckets, gen_candidates, prefix_hash)
from repro.core.tidlist import pack_database
from repro.data.transactions import load

POLICIES = ["cilk", "fifo", "clustered", "nn"]


# ------------------------------------------------------------- planning
def _per_pair_candidates(frequent, known_frequent=()):
    """The per-pair Apriori join + prune that ``gen_buckets`` replaced,
    kept as the oracle: one tuple per candidate, every (k-1)-subset
    looked up."""
    fset = set(frequent) | set(known_frequent)
    if not frequent:
        return []
    k = len(frequent[0]) + 1
    by_prefix = {}
    for it in frequent:
        by_prefix.setdefault(it[:-1], []).append(it[-1])
    out = []
    for pref, lasts in by_prefix.items():
        lasts.sort()
        for i, a in enumerate(lasts):
            for b in lasts[i + 1:]:
                cand = pref + (a, b)
                if k <= 2 or all(cand[:j] + cand[j + 1:] in fset
                                 for j in range(k)):
                    out.append(cand)
    return out


def _group_by_prefix(cands):
    """The per-candidate regrouping the level driver used to run on the
    joined candidates: first-seen prefix order, sorted extensions."""
    groups = {}
    for c in cands:
        groups.setdefault((prefix_hash(c), c[:-1]), []).append(c[-1])
    return [Bucket(h, pref, tuple(sorted(ext)))
            for (h, pref), ext in groups.items()]


def _random_frontier(seed, k, n_items=11):
    """A seeded random sorted set of (k-1)-itemsets, and a known-frequent
    set of (k-1)-itemsets the frontier does not hold (itemsets a
    detached subtree mined)."""
    rng = np.random.default_rng(seed)
    every = list(combinations(range(n_items), k - 1))
    pick = rng.random(len(every))
    frontier = [c for c, u in zip(every, pick) if u < 0.6]
    known = [c for c, u in zip(every, pick) if 0.6 <= u < 0.8]
    return frontier, known


def _mushroom_level2():
    db, p = load("mushroom", seed=0)
    bm = pack_database(db[:200], p.n_dense_items)
    return sorted(f for f in mine_serial(bm, 60, max_k=2) if len(f) == 2)


PLAN_CASES = {
    "empty": lambda: ([], []),
    "one-item": lambda: ([(4,)], []),
    **{f"k{k}": (lambda k=k: (_random_frontier(k, k)[0], []))
       for k in range(2, 6)},
    **{f"k{k}-known": (lambda k=k: _random_frontier(k, k))
       for k in range(2, 6)},
    "hand-k3": lambda: ([(0, 1), (0, 2), (0, 3), (0, 5), (1, 2), (1, 3),
                         (1, 5), (2, 3), (2, 4), (2, 9), (3, 4), (3, 9)],
                        []),
    "mushroom-k3": lambda: (_mushroom_level2(), []),
}


@pytest.mark.parametrize("case", list(PLAN_CASES))
def test_gen_buckets_equals_grouped_per_pair_candidates(case):
    """gen_buckets plans a level in bucket form directly: the same
    buckets, in the same order, with the same key, prefix and
    extensions as grouping the per-pair join's candidates — so spawn
    order, flush composition and results are unchanged — and
    gen_candidates, its flattening, still equals the per-pair join."""
    frontier, known = PLAN_CASES[case]()
    oracle = _per_pair_candidates(frontier, known)
    buckets = gen_buckets(frontier, known)
    assert ([(b.key, b.prefix, b.exts) for b in buckets]
            == [(b.key, b.prefix, b.exts)
                for b in _group_by_prefix(oracle)])
    assert gen_candidates(frontier, known) == oracle
    # a partition of the candidates by prefix, extensions sorted, keyed
    # by the paper's prefix hash
    assert sum(len(b) for b in buckets) == len(oracle)
    assert len({b.prefix for b in buckets}) == len(buckets)
    for b in buckets:
        assert b.exts and b.exts == tuple(sorted(b.exts))
        assert b.key == prefix_hash(b.prefix + (b.exts[0],))
    if case == "hand-k3":
        # (2,5), (3,5) and (4,9) are not in the frontier, so the prune
        # drops (0,2,5), (0,3,5), (1,2,5), (1,3,5), (2,4,9), (3,4,9)
        assert [b.prefix for b in buckets] == [(0, 1), (0, 2), (1, 2),
                                                (2, 3)]
    if case.startswith("k") and not case.startswith("k2"):
        # the random frontiers are thin enough that the prune bites
        grouped = {}
        for f in frontier:
            grouped[f[:-1]] = grouped.get(f[:-1], 0) + 1
        joined = sum(n * (n - 1) // 2 for n in grouped.values())
        assert len(oracle) < joined
        if case.endswith("-known"):
            # known-frequent itemsets keep candidates the frontier
            # alone would prune
            assert len(oracle) > len(_per_pair_candidates(frontier))


def test_traffic_model_bucket_beats_candidate():
    # 1 bucket of E extensions at level k: (k-1)+E rows vs k*E rows
    k, e = 4, 32
    assert bucket_rows_touched(k - 1, e) < candidate_rows_touched(k, e)
    assert rows_to_bytes(10, 8) == 10 * 8 * 4


# ---------------------------------------------------------- equivalence
@pytest.fixture(scope="module")
def datasets():
    out = {}
    for name, n_txn, frac in [("mushroom", 250, 0.3), ("chess", 150, 0.8),
                              ("retail", 800, 0.03)]:
        db, p = load(name, seed=0)
        db = db[:n_txn]
        n_items = p.n_dense_items if p.kind == "dense" else p.n_items
        bm = pack_database(db, n_items)
        ms = int(frac * len(db))
        out[name] = (db, bm, ms)
    return out


@pytest.mark.parametrize("name", ["mushroom", "chess"])
def test_serial_matches_brute_force(datasets, name):
    db, bm, ms = datasets[name]
    assert mine_serial(bm, ms, max_k=4) == brute_force_frequent(
        db, ms, max_k=4)


@pytest.mark.parametrize("granularity",
                         ["bucket", "candidate", "depth-first"])
@pytest.mark.parametrize("policy", POLICIES)
@pytest.mark.parametrize("name", ["mushroom", "chess", "retail"])
def test_engine_equivalence(datasets, name, policy, granularity):
    """The acceptance matrix: every policy × every granularity returns
    supports identical to the serial reference, on three datasets
    (dense mushroom/chess + the sparse long-tail retail profile)."""
    db, bm, ms = datasets[name]
    ref = mine_serial(bm, ms, max_k=4)
    got, met = mine(bm, ms, policy=policy, n_workers=3, max_k=4,
                    granularity=granularity)
    assert got == ref, (name, policy, granularity)
    assert met.scheduler["tasks_run"] == met.scheduler["spawned"]
    if granularity == "depth-first" and name != "chess":
        # the default "auto" representation: dense mushroom stays
        # all-bitmap, the sparse retail profile hands sparse rows down
        assert (met.sparse_sweeps > 0) == (name == "retail"), name


def test_bucket_rows_touched_below_candidate(datasets):
    """Locality, measured: the bucket sweep reads each prefix once."""
    _, bm, ms = datasets["mushroom"]
    _, m_b = mine(bm, ms, policy="clustered", n_workers=3, max_k=4,
                  granularity="bucket")
    _, m_c = mine(bm, ms, policy="clustered", n_workers=3, max_k=4,
                  granularity="candidate")
    assert 0 < m_b.rows_touched < m_c.rows_touched
    assert 0 < m_b.bytes_swept < m_c.bytes_swept


@pytest.mark.parametrize("backend", ["numpy", "pallas-interpret"])
@pytest.mark.parametrize("granularity",
                         ["bucket", "candidate", "depth-first"])
def test_backend_granularity_equivalence(datasets, granularity, backend):
    """The arena/dispatcher acceptance matrix: every granularity ×
    every CPU-capable backend produces identical frequent itemsets
    through the handle-based request path."""
    _, bm, ms = datasets["mushroom"]
    ref = mine_serial(bm, ms, max_k=3)
    got, met = mine(bm, ms, policy="clustered", n_workers=2, max_k=3,
                    granularity=granularity, backend=backend)
    assert got == ref, (granularity, backend)
    # sweeps went through the dispatcher, and every request was
    # answered by a flush
    assert met.flushes > 0
    assert round(met.flushes * met.batch_occupancy) == \
        met.scheduler["sweeps_submitted"]
    if backend == "pallas-interpret":
        # device-resident arena: the h2d gauge saw the initial upload
        # plus incrementally synced prefix/handoff rows (at most ~2 per
        # sweep) — never a per-sweep re-upload of extension bitmaps
        row_bytes = bm.shape[1] * 4
        sweeps = met.scheduler["sweeps_submitted"]
        assert bm.nbytes <= met.h2d_bytes <= \
            bm.nbytes + 2 * sweeps * row_bytes


def test_bad_granularity_raises(datasets):
    _, bm, ms = datasets["mushroom"]
    with pytest.raises(ValueError, match="granularity"):
        mine(bm, ms, granularity="itemset")


@pytest.mark.parametrize("granularity", ["bucket", "candidate"])
def test_cache_size_zero_is_a_valid_no_cache_knob(datasets, granularity):
    """cache_size=0 (the 'no cache' A/B setting) must work: get()
    retains a caller reference before the instant eviction releases
    the cache's own, so the handle stays live through the sweep."""
    _, bm, ms = datasets["chess"]
    ref = mine_serial(bm, ms, max_k=4)
    got, met = mine(bm, ms, policy="clustered", n_workers=3, max_k=4,
                    granularity=granularity, cache_size=0)
    assert got == ref
    assert met.cache_hits == 0               # nothing ever cached


# ----------------------------------------------------- depth-first engine
def test_depth_first_handoff_makes_cache_vestigial(datasets):
    """The parent→child bitmap handoff: no prefix is ever recomputed or
    cache-probed, so the LRU cache shows zero traffic; the engine also
    reports its retained-bitmap peak (children exist on this dataset)."""
    _, bm, ms = datasets["retail"]
    got, met = mine(bm, ms, policy="clustered", n_workers=3, max_k=4,
                    granularity="depth-first")
    assert met.cache_hits == met.cache_misses == 0
    assert met.peak_retained_bitmaps > 0        # children were spawned
    assert met.peak_bytes_retained > 0
    assert met.buckets == met.scheduler["tasks_run"]
    assert got == mine_serial(bm, ms, max_k=4)


def test_depth_first_child_error_surfaces_on_driver(datasets, monkeypatch):
    """A task body raising inside a spawned-from-task child class must
    surface on the driver thread (not deadlock the terminal wait_all).
    Child classes are exactly the tasks whose prefix handle is an OWNED
    materialized arena row (handle >= n_base); root classes hand the
    pinned base row's handle (== item id)."""
    from repro.core import fpm as fpm_mod
    from repro.core.join_backend import NumpyBackend

    class ChildBomb(NumpyBackend):
        def sweep_many(self, arena, requests):
            if any(r.prefix_handle >= arena.n_base for r in requests):
                raise RuntimeError("child boom")
            return super().sweep_many(arena, requests)

    monkeypatch.setattr(fpm_mod, "resolve_backend",
                        lambda spec: ChildBomb())
    _, bm, ms = datasets["retail"]
    with pytest.raises(RuntimeError, match="child boom"):
        mine(bm, ms, policy="clustered", n_workers=3, max_k=4,
             granularity="depth-first")


def test_depth_first_single_frequent_item_spawns_nothing():
    db = [[0], [0], [0]]
    bm = pack_database(db, 1)
    got, met = mine(bm, 2, granularity="depth-first", n_workers=2)
    assert got == {(0,): 3}
    assert met.scheduler["spawned"] == 0


# ------------------------------------------------------ property tests
@settings(max_examples=30, deadline=None)
@given(st.lists(st.lists(st.integers(0, 15), max_size=8), min_size=1,
                max_size=30))
def test_property_pack_unpack_roundtrip(db):
    db = [sorted(set(t)) for t in db]
    bits = np.zeros((16, len(db)), dtype=bool)
    for t, txn in enumerate(db):
        for i in txn:
            bits[i, t] = True
    packed = tidlist.pack_bool(bits)
    back = tidlist.unpack_bool(packed, len(db))
    np.testing.assert_array_equal(back, bits)


@settings(max_examples=30, deadline=None)
@given(st.integers(1, 40), st.integers(1, 30), st.integers(0, 2 ** 31))
def test_property_support_counts_vs_naive_loop(e, w, seed):
    rng = np.random.default_rng(seed)
    prefix = rng.integers(0, 2 ** 32, size=w, dtype=np.uint32)
    exts = rng.integers(0, 2 ** 32, size=(e, w), dtype=np.uint32)
    got = tidlist.support_counts(prefix, exts)
    want = [sum(bin(int(prefix[j]) & int(exts[i, j])).count("1")
                for j in range(w)) for i in range(e)]
    np.testing.assert_array_equal(got, np.array(want))


@settings(max_examples=15, deadline=None)
@given(st.integers(0, 10_000))
def test_property_bucket_engine_equals_brute_force(seed):
    rng = np.random.default_rng(seed)
    db = [sorted(rng.choice(10, size=rng.integers(1, 6),
                            replace=False).tolist()) for _ in range(40)]
    ms = int(rng.integers(2, 10))
    ref = brute_force_frequent(db, ms, max_k=4)
    bm = pack_database(db, 10)
    for gran in ("bucket", "depth-first"):
        got, _ = mine(bm, ms, policy="clustered", n_workers=2, max_k=4,
                      granularity=gran)
        assert got == ref, gran
