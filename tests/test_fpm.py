"""Apriori FPM engine vs brute force, both policies + locality metrics."""
from collections import Counter

import numpy as np
import pytest
from _hyp import given, settings, st

from repro.core.fpm import mine, mine_serial
from repro.core.itemsets import brute_force_frequent, gen_candidates
from repro.core.tidlist import pack_database
from repro.data.transactions import load


@pytest.fixture(scope="module")
def small_db():
    db, p = load("mushroom", seed=0)
    return [t for t in db[:300]], p


@pytest.mark.parametrize("engine", ["serial", "bucket", "candidate",
                                    "auto", "hosts=2"])
def test_serial_matches_brute_force(small_db, engine):
    """The serial reference and ``mine()`` — at bucket, candidate and
    auto grain, and split over two hosts — against brute force, at a
    support where the Apriori prune drops level-3 candidates the
    prefix join formed."""
    db, p = small_db
    bm = pack_database(db, p.n_dense_items)
    ms = int(0.25 * len(db))
    ref = brute_force_frequent(db, ms, max_k=4)
    level2 = sorted(c for c in ref if len(c) == 2)
    group_sizes = Counter(c[:-1] for c in level2).values()
    joined = sum(n * (n - 1) // 2 for n in group_sizes)
    assert 0 < len(gen_candidates(level2)) < joined
    if engine == "serial":
        got = mine_serial(bm, ms, max_k=4)
    elif engine == "hosts=2":
        got, _ = mine(bm, ms, n_workers=2, max_k=4, hosts=2)
    else:
        got, _ = mine(bm, ms, n_workers=3, max_k=4, granularity=engine)
    assert got == ref


@pytest.mark.parametrize("policy", ["cilk", "fifo", "clustered"])
def test_parallel_matches_serial(small_db, policy):
    db, p = small_db
    bm = pack_database(db, p.n_dense_items)
    ms = int(0.3 * len(db))
    ref = mine_serial(bm, ms, max_k=4)
    got, metrics = mine(bm, ms, policy=policy, n_workers=4, max_k=4,
                        granularity="candidate")
    assert got == ref
    assert metrics.scheduler["tasks_run"] == metrics.candidates


@pytest.mark.parametrize("policy", ["cilk", "fifo", "clustered"])
def test_bucket_granularity_matches_serial(small_db, policy):
    """Default granularity: prefix buckets spawned as burst tasks of
    consecutive buckets, counts by vectorized sweep — identical
    supports, one task per burst and fewer bursts than buckets."""
    db, p = small_db
    bm = pack_database(db, p.n_dense_items)
    ms = int(0.3 * len(db))
    ref = mine_serial(bm, ms, max_k=4)
    got, metrics = mine(bm, ms, policy=policy, n_workers=4, max_k=4)
    assert got == ref
    assert metrics.scheduler["tasks_run"] == metrics.bursts
    assert 0 < metrics.bursts < metrics.buckets < metrics.candidates
    assert metrics.rows_touched > 0
    assert metrics.bytes_swept > 0


def test_clustered_has_better_locality_than_cilk(small_db):
    """The paper's central claim, in this reproduction's metrics.
    Candidate granularity: the cache hit-rate gap is exactly the
    incidental locality the bucket engine later makes structural."""
    db, p = small_db
    bm = pack_database(db, p.n_dense_items)
    ms = int(0.25 * len(db))
    _, m_clu = mine(bm, ms, policy="clustered", n_workers=4, max_k=5,
                    granularity="candidate")
    _, m_cilk = mine(bm, ms, policy="cilk", n_workers=4, max_k=5,
                     granularity="candidate")
    assert m_clu.cache_hit_rate > m_cilk.cache_hit_rate
    assert (m_clu.scheduler["tasks_per_steal"]
            >= m_cilk.scheduler["tasks_per_steal"])


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 10_000))
def test_property_mine_equals_brute_force_random_db(seed):
    rng = np.random.default_rng(seed)
    n_items, n_tx = 12, 60
    db = [sorted(rng.choice(n_items, size=rng.integers(1, 7),
                            replace=False).tolist())
          for _ in range(n_tx)]
    ms = int(rng.integers(2, 12))
    ref = brute_force_frequent(db, ms, max_k=4)
    bm = pack_database(db, n_items)
    got, _ = mine(bm, ms, policy="clustered", n_workers=3, max_k=4)
    assert got == ref


def test_mine_frees_its_arena_without_the_cyclic_collector(small_db,
                                                           monkeypatch):
    """A finished mine drops its arena, and so its device mirror, by
    reference counting alone: nothing of the run sits in a reference
    cycle that only the cyclic garbage collector would free."""
    import gc
    import weakref

    from repro.core import fpm as fpm_mod
    db, p = small_db
    bm = pack_database(db, p.n_dense_items)
    arenas = []
    build = fpm_mod.BitmapArena.from_bitmaps

    def tracked(*a, **k):
        store = build(*a, **k)
        arenas.append(weakref.ref(store))
        return store

    monkeypatch.setattr(fpm_mod.BitmapArena, "from_bitmaps", tracked)
    gc.collect()
    gc.disable()
    try:
        got, _ = mine(bm, int(0.3 * len(db)), n_workers=2, max_k=3)
        assert got and len(arenas) == 1
        assert arenas[0]() is None
    finally:
        gc.enable()


def test_min_support_one_includes_every_item_present():
    db = [[0], [1], [2, 3]]
    bm = pack_database(db, 4)
    got = mine_serial(bm, 1, max_k=3)
    assert (0,) in got and (3,) in got and (2, 3) in got
