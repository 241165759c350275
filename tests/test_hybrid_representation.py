"""Hybrid sparse representation: dEclat diffsets, the gather-intersect
kernel, and density-driven per-subtree selection.

Covers the four satellite test axes:
 - numpy-reference vs pallas-interpret parity for the gather-intersect
   kernel (ragged tid lists, empty payloads, a single extension);
 - mixed-representation engine equivalence (every granularity x
   representation cell mines the identical frequent set);
 - a hypothesis property test of diffset support arithmetic against
   brute-force set algebra (skips cleanly without hypothesis);
 - streaming refresh over sparse rows.
"""
import itertools

import numpy as np
import pytest
from _hyp import given, settings, st

from repro.core import join_backend as jb
from repro.core import tidlist
from repro.core.buckets import DensityModel
from repro.core.fpm import mine, mine_serial
from repro.core.tidlist import BitmapArena, pack_database

RNG = np.random.default_rng(7)


def rand_db(n_tx, n_items=16, lo=1, hi=6, rng=RNG):
    return [list(rng.choice(n_items, size=rng.integers(lo, hi),
                            replace=False))
            for _ in range(n_tx)]


def naive_supports(db, itemset):
    s = set(itemset)
    return sum(1 for tx in db if s.issubset(tx))


# ------------------------------------------------ kernel parity (numpy
# reference vs pallas-interpret; ragged batches, empties, E == 1)
def _rand_sparse_batch(b, s, e, w, rng=RNG, ragged=True):
    """Random [B,S] padded tid batch + [B,E,W] ext word-columns."""
    tids = np.full((b, s), -1, np.int32)
    for i in range(b):
        n = int(rng.integers(0, s + 1)) if ragged else s
        t = rng.choice(32 * w, size=n, replace=False)
        t.sort()
        tids[i, :n] = t
    exts = rng.integers(0, 2 ** 32, size=(b, e, w), dtype=np.uint32)
    return tids, exts


@pytest.mark.parametrize("b,s,e,w", [(1, 7, 1, 2), (3, 16, 4, 3),
                                     (5, 33, 2, 8), (2, 64, 6, 4),
                                     # several W tiles and S tiles
                                     (2, 1100, 3, 8200),
                                     (3, 2500, 130, 9000)])
def test_gather_intersect_interpret_matches_numpy_ref(b, s, e, w):
    jax = pytest.importorskip("jax")
    from repro.kernels.gather_intersect.kernel import (
        gather_intersect_many_kernel)
    from repro.kernels.gather_intersect.ref import (
        gather_intersect_many_np)
    tids, exts = _rand_sparse_batch(b, s, e, w)
    want = gather_intersect_many_np(tids, exts)
    got = np.asarray(gather_intersect_many_kernel(
        jax.numpy.asarray(tids), jax.numpy.asarray(exts),
        interpret=True))
    np.testing.assert_array_equal(got, want)


def test_gather_intersect_empty_tid_axis_is_all_zero():
    jax = pytest.importorskip("jax")
    from repro.kernels.gather_intersect.ops import gather_intersect_many
    exts = jax.numpy.asarray(
        RNG.integers(0, 2 ** 32, size=(2, 3, 4), dtype=np.uint32))
    tids = jax.numpy.zeros((2, 0), np.int32)
    out = np.asarray(gather_intersect_many(tids, exts, mode="ref"))
    assert out.shape == (2, 3) and not out.any()


def test_gather_intersect_all_padded_rows_count_zero():
    jax = pytest.importorskip("jax")
    from repro.kernels.gather_intersect.kernel import (
        gather_intersect_many_kernel)
    tids = np.full((2, 9), -1, np.int32)
    tids[0, :3] = [1, 40, 63]
    exts = np.full((2, 2, 2), 0xFFFFFFFF, np.uint32)
    got = np.asarray(gather_intersect_many_kernel(
        jax.numpy.asarray(tids), jax.numpy.asarray(exts),
        interpret=True))
    np.testing.assert_array_equal(got, [[3, 3], [0, 0]])


# ---------------------------------------- dispatcher sparse/dense mix
def _tid_arena(n=8, w=6, rng=RNG):
    rows = rng.integers(0, 2 ** 32, size=(n, w), dtype=np.uint32)
    return BitmapArena.from_bitmaps(rows), rows


def naive_counts(prow, erows):
    return [int(tidlist.popcount32(prow & r).sum()) for r in erows]


def test_one_flush_mixes_representations():
    """A single dispatcher flush carries dense, tid-list and diffset
    prefixes; each request is routed to its representation's sweep and
    the counts agree with dense brute force."""
    arena, rows = _tid_arena()
    pt = tidlist.bitmap_to_tids(rows[0] & rows[1])
    ht = arena.push_tids(pt)                        # tids(0&1)
    sub = tidlist.bitmap_to_tids(rows[0] & rows[1] & rows[2])
    hd = arena.push_diffset(tidlist.sorted_difference(pt, sub),
                            anchor=ht, support=len(sub))
    disp = jb.SweepDispatcher(arena, jb.get_backend("numpy"),
                              n_clients=3, flush_us=500_000)
    try:
        exts = (3, 4, 5)
        fd = disp.submit(0, exts)                   # dense prefix
        ft = disp.submit(ht, exts)                  # tid-list prefix
        fx = disp.submit(hd, exts)                  # diffset prefix
        np.testing.assert_array_equal(
            fd.result(10), naive_counts(rows[0], rows[3:6]))
        np.testing.assert_array_equal(
            ft.result(10), naive_counts(rows[0] & rows[1], rows[3:6]))
        # diffset requests count |diff ∩ e|: support(P+e) follows by
        # the dEclat identity support(anchor+e) - |diff ∩ e|
        want_sub = naive_counts(rows[0] & rows[1] & rows[2], rows[3:6])
        got = [a - d for a, d in zip(ft.result(10), fx.result(10))]
        assert got == want_sub
        assert disp.flushes == 1 and disp.requests == 3
    finally:
        disp.stop()


def test_gather_bits_rows_matches_per_tid_bit_test():
    arena, rows = _tid_arena(n=5, w=4)
    tids = np.sort(RNG.choice(32 * 4, size=20, replace=False)
                   ).astype(np.uint32)
    got = arena.gather_bits_rows(tids, [1, 3])
    for j, h in enumerate([1, 3]):
        want = [(int(rows[h][t >> 5]) >> (int(t) & 31)) & 1
                for t in tids]
        np.testing.assert_array_equal(got[j], want)


# ------------------------------------- engine equivalence (the matrix)
def test_mixed_representation_equivalence_matrix():
    """Every granularity x representation cell mines the identical
    frequent set; sparse runs actually take sparse sweeps. The database
    is dense enough that the lattice reaches k=4 — sparse prefixes only
    exist once classes hand rows down (k >= 3)."""
    db = rand_db(600, n_items=12, lo=3, hi=9)
    bm, counts = pack_database(db, 12, return_counts=True)
    ms = 40
    ref = mine_serial(bm, ms, max_k=5)
    assert ref, "degenerate test database"
    for gran, rep in itertools.product(
            ("bucket", "depth-first", "auto"),
            ("bitmap", "sparse", "auto")):
        res, met = mine(bm, ms, n_workers=3, max_k=5, backend="numpy",
                        granularity=gran, representation=rep,
                        item_counts=counts)
        assert res == ref, f"{gran}/{rep} mismatch"
        if rep == "bitmap":
            assert met.sparse_sweeps == 0 and not met.rep_picks
        if rep == "sparse" and gran != "candidate":
            assert met.sparse_sweeps > 0
            assert met.sparse_bytes_swept > 0


def test_depth_first_sparse_subtrees_project_without_arena_rows():
    """The host backend mines sparse subtrees the way the kernels do:
    sparse children are arena rows handed down (no projected bit
    matrices), so numpy and pallas-interpret make the same
    representation choices and push the same sparse rows."""
    db = rand_db(300, n_items=12, lo=3, hi=9)
    bm, counts = pack_database(db, 12, return_counts=True)
    ref = mine_serial(bm, 20, max_k=5)
    runs = {}
    for backend in ("numpy", "pallas-interpret"):
        res, met = mine(bm, 20, n_workers=3, max_k=5, backend=backend,
                        granularity="depth-first",
                        representation="sparse", item_counts=counts)
        assert res == ref, backend
        assert met.sparse_sweeps > 0
        assert met.sparse_rows > 0           # arena rows, not masks
        runs[backend] = met
    np_met, pi_met = runs["numpy"], runs["pallas-interpret"]
    assert np_met.sparse_rows == pi_met.sparse_rows
    assert np_met.rep_picks == pi_met.rep_picks
    assert np_met.rep_picks["diffset"] > 0


def test_pallas_interpret_sparse_matches_serial():
    """Kernel-backend path: sparse rows live in the arena, diffset
    chains resolve through anchors, and the gather-intersect kernel
    (interpret mode) produces the same frequent set."""
    pytest.importorskip("jax")
    db = rand_db(250, n_items=10)
    bm, counts = pack_database(db, 10, return_counts=True)
    ms = 25
    ref = mine_serial(bm, ms, max_k=4)
    for rep in ("sparse", "auto"):
        res, met = mine(bm, ms, n_workers=2, max_k=4,
                        backend="pallas-interpret",
                        granularity="depth-first", representation=rep,
                        item_counts=counts)
        assert res == ref, f"pallas-interpret/{rep} mismatch"
        if rep == "sparse":
            assert met.sparse_rows > 0       # arena rows, not masks


# -------------------------------------------------- streaming, sparse
@pytest.mark.parametrize("rep", ["sparse", "auto"])
def test_streaming_refresh_over_sparse_rows(rep):
    """Ingest+refresh rounds with sparse representations stay exact at
    every generation (delta sweeps searchsort tid payloads into the
    pending segments' windows)."""
    from repro.core.streaming import StreamingMiner
    full = rand_db(400, n_items=12, lo=3, hi=9)
    cuts = [260, 330, 400]
    ms = 30
    sm = StreamingMiner(12, ms, initial_db=full[:cuts[0]],
                        granularity="depth-first", n_workers=3,
                        max_k=5, representation=rep)
    prev = cuts[0]
    for cut in cuts:
        if cut != prev:
            sm.ingest(full[prev:cut])
            prev = cut
        sm.refresh()
        ref = mine(pack_database(full[:cut], 12), ms,
                   granularity="depth-first", n_workers=3, max_k=5)[0]
        assert dict(sm.snapshot.supports) == ref


# ------------------------------------------- diffset arithmetic (hyp.)
@settings(max_examples=40, deadline=None)
@given(st.data())
def test_property_diffset_support_arithmetic(data):
    """support(P+e) == support(P) - |diff ∩ tids(P)∩e ... | via the
    arena: push a random parent tid-list, carve a random child as a
    diffset, and check resolve_tids + sparse_support against
    brute-force set algebra, including empty diffs and empty children."""
    n_words = data.draw(st.integers(1, 4), label="n_words")
    univ = 32 * n_words
    parent = sorted(data.draw(
        st.sets(st.integers(0, univ - 1), min_size=1, max_size=univ),
        label="parent"))
    child = sorted(data.draw(
        st.sets(st.sampled_from(parent), max_size=len(parent)),
        label="child"))
    pt = np.asarray(parent, np.uint32)
    ct = np.asarray(child, np.uint32)
    base = RNG.integers(0, 2 ** 32, size=(2, n_words), dtype=np.uint32)
    arena = BitmapArena.from_bitmaps(base)
    hp = arena.push_tids(pt)
    diff = tidlist.sorted_difference(pt, ct)
    assert sorted(diff) == sorted(set(parent) - set(child))
    hc = arena.push_diffset(diff, anchor=hp, support=len(ct))
    np.testing.assert_array_equal(arena.resolve_tids(hc), ct)
    assert arena.sparse_support(hc) == len(child)
    # the dEclat identity against a random extension row
    erow = tidlist.tids_to_bitmap(
        np.asarray(sorted(data.draw(
            st.sets(st.integers(0, univ - 1), max_size=univ),
            label="ext")), np.uint32), n_words)
    inter_parent = naive_bit_and_count(pt, erow)
    inter_diff = naive_bit_and_count(diff, erow)
    want_child = naive_bit_and_count(ct, erow)
    assert inter_parent - inter_diff == want_child


def naive_bit_and_count(tids, row):
    return sum(1 for t in tids
               if (int(row[int(t) >> 5]) >> (int(t) & 31)) & 1)


# ------------------------------------------------------- density model
def test_density_model_child_rep_thresholds_and_ties():
    m = DensityModel(n_words=100, tids_per_word=2.0)
    # cheap child tid-list: S/tpw < W
    assert m.pick_child_rep(1000, 150) == "tidlist"
    # near-total child: tiny diffset wins when allowed
    assert m.pick_child_rep(1000, 990) == "diffset"
    assert m.pick_child_rep(1000, 990,
                            allow_diffset=False) == "bitmap"
    # huge child: bitmap (S/tpw and D/tpw both above W)
    assert m.pick_child_rep(1000, 500) == "bitmap"
    # exact tie prefers the simpler representation: cost 100 == W
    assert m.pick_child_rep(400, 200) == "bitmap"
    # tidlist/diffset tie at equal size prefers tidlist
    assert m.pick_child_rep(300, 150) == "tidlist"
    assert (m.bitmap_picks, m.tidlist_picks, m.diffset_picks) \
        == (3, 2, 1)


def test_density_model_force_pins_representation():
    mb = DensityModel(n_words=10, force="bitmap")
    ms_ = DensityModel(n_words=10, force="sparse")
    assert mb.pick_child_rep(100, 1) == "bitmap"
    assert ms_.pick_child_rep(100, 99) == "diffset"
    assert ms_.pick_child_rep(100, 1) == "tidlist"
    assert mb.pick_rep(1) == "bitmap" and ms_.pick_rep(999) == "tidlist"


def test_density_model_seed_and_ewma_observe():
    m = DensityModel.from_counts(4, [32, 64, 32])   # mean 32/word? no:
    assert m.ones_per_word == pytest.approx((32 + 64 + 32) / (3 * 4))
    before = m.ones_per_word
    m.observe([400, 400])                           # 100 ones/word
    assert before < m.ones_per_word < 100 / 1.0     # EWMA moved toward
    m2 = DensityModel.from_counts(4, None)
    assert m2.ones_per_word == 0.0


def test_density_model_granularity_split():
    m = DensityModel(n_words=100, tids_per_word=2.0)
    assert m.pick_granularity(150) == "depth-first"   # sparse subtree
    assert m.pick_granularity(1000) == "depth-first"  # 10 ones/word
    assert m.pick_granularity(5000) == "bucket"       # 50 ones/word


def test_pack_database_counts_match_bitmaps():
    db = rand_db(200)
    bm, counts = pack_database(db, 16, return_counts=True)
    np.testing.assert_array_equal(
        counts,
        [int(tidlist.popcount32(bm[i]).sum()) for i in range(16)])
    bm2 = pack_database(db, 16)
    np.testing.assert_array_equal(bm, bm2)
