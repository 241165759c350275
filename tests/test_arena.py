"""BitmapArena lifecycle: refcounts, handle reuse, device-mirror sync
accounting, and engine-level refcount hygiene on task error."""
import numpy as np
import pytest

from repro.core import fpm as fpm_mod
from repro.core.fpm import mine
from repro.core.join_backend import NumpyBackend
from repro.core.tidlist import MIRROR_MIN_ROWS, BitmapArena, pack_database

RNG = np.random.default_rng(11)


def small_arena(n=6, w=4, backing="auto"):
    rows = RNG.integers(0, 2 ** 32, size=(n, w), dtype=np.uint32)
    return BitmapArena.from_bitmaps(rows, backing=backing), rows


# ----------------------------------------------------------- lifecycle
def test_base_rows_are_pinned_item_handles():
    arena, rows = small_arena()
    assert arena.n_base == 6 and arena.n_rows == 6
    for i in range(6):
        np.testing.assert_array_equal(arena.row(i), rows[i])
        arena.release(i)                     # no-op on pinned rows
        assert arena.refcount(i) == 1
    assert arena.live_extra == 0


def test_push_retain_release_refcounts():
    arena, rows = small_arena()
    h = arena.push(rows[0] | rows[1])
    assert h == 6 and arena.refcount(h) == 1 and arena.live_extra == 1
    arena.retain(h)
    assert arena.refcount(h) == 2
    arena.release(h)
    assert arena.refcount(h) == 1 and arena.live_extra == 1
    arena.release(h)
    assert arena.live_extra == 0             # freed


def test_handle_reuse_after_free():
    arena, rows = small_arena()
    h1 = arena.push(rows[0])
    h2 = arena.push(rows[1])
    arena.release(h1)
    h3 = arena.push(rows[2])                 # recycles h1's slot
    assert h3 == h1 and h3 != h2
    np.testing.assert_array_equal(arena.row(h3), rows[2])
    assert arena.n_rows == 8                 # no growth past high-water


def test_materialize_is_the_and_of_both_rows():
    arena, rows = small_arena()
    h = arena.materialize(2, 4)
    np.testing.assert_array_equal(arena.row(h), rows[2] & rows[4])
    child = arena.materialize(h, 1)          # chained (depth-first)
    np.testing.assert_array_equal(arena.row(child),
                                  rows[2] & rows[4] & rows[1])
    assert arena.peak_live_extra == 2
    assert arena.peak_bytes_extra == 2 * arena.n_words * 4


def test_growth_preserves_rows_and_views_stay_correct():
    arena, rows = small_arena(n=3, w=5)
    view = arena.row(1)
    handles = [arena.push(rows[i % 3]) for i in range(300)]  # force grow
    np.testing.assert_array_equal(arena.row(1), rows[1])
    np.testing.assert_array_equal(view, rows[1])   # old view still right
    for h in handles:
        arena.release(h)
    assert arena.live_extra == 0


def test_gather_contiguous_is_view_strided_is_copy():
    arena, rows = small_arena()
    g = arena.gather([2, 3, 4])
    assert g.base is not None                # slice view, zero-copy
    np.testing.assert_array_equal(g, rows[2:5])
    s = arena.gather([0, 2, 5])
    np.testing.assert_array_equal(s, rows[[0, 2, 5]])


def test_bad_backing_rejected():
    with pytest.raises(ValueError, match="backing"):
        BitmapArena(4, backing="cuda")


# -------------------------------------------------------- device mirror
def test_device_sync_is_incremental_and_counts_h2d():
    arena, rows = small_arena(n=4, w=8)
    row_bytes = 8 * 4
    dev = arena.device_rows()                # initial upload: 4 rows
    # the mirror is a fixed-capacity buffer: 4 live rows + zero padding
    assert dev.shape == (MIRROR_MIN_ROWS, 8)
    assert arena.h2d_bytes == 4 * row_bytes
    np.testing.assert_array_equal(np.asarray(dev)[:4], rows)
    assert not np.asarray(dev)[4:].any()
    dev = arena.device_rows()                # no change -> no upload
    assert arena.h2d_bytes == 4 * row_bytes
    h = arena.push(rows[0] & rows[1])
    dev = arena.device_rows()                # one appended row
    assert dev.shape == (MIRROR_MIN_ROWS, 8)  # same shape: no recompile
    assert arena.h2d_bytes == 5 * row_bytes
    np.testing.assert_array_equal(np.asarray(dev[h]), rows[0] & rows[1])
    # recycled slot: freed row rewritten -> resynced as dirty, not
    # re-uploading the whole store
    arena.release(h)
    h2 = arena.push(rows[2] | rows[3])
    assert h2 == h
    dev = arena.device_rows()
    assert arena.h2d_bytes == 6 * row_bytes
    np.testing.assert_array_equal(np.asarray(dev[h2]), rows[2] | rows[3])


def test_mirror_capacity_doubles_and_stays_exact():
    """Appending rows one sync at a time changes the mirror's shape only
    when its power-of-two capacity doubles (bounded recompiles), and the
    live rows always equal the store."""
    arena, rows = small_arena(n=4, w=8)
    shapes = set()
    for i in range(20):
        arena.push(rows[i % 4] & rows[(i + 1) % 4])
        dev = arena.device_rows()
        shapes.add(dev.shape)
        np.testing.assert_array_equal(np.asarray(dev)[:arena.n_rows],
                                      arena.seg_view(0))
        assert not np.asarray(dev)[arena.n_rows:].any()
    assert shapes == {(8, 8), (16, 8), (32, 8)}
    assert arena.h2d_bytes == 24 * 8 * 4      # padding rows not billed


def test_numpy_backing_never_creates_device_mirror():
    arena, _ = small_arena(backing="numpy")
    assert not arena.device_enabled
    assert arena.device_rows() is None
    assert arena.h2d_bytes == 0


def test_jax_backing_uploads_eagerly():
    arena, _ = small_arena(n=5, w=3, backing="jax")
    assert arena.h2d_bytes == 5 * 3 * 4


# ------------------------------------------------------- sharded mode
def sharded_arena(n=6, w=4, backing="numpy", n_shards=2):
    rows = RNG.integers(0, 2 ** 32, size=(n, w), dtype=np.uint32)
    return BitmapArena.from_bitmaps(rows, backing=backing,
                                    n_shards=n_shards), rows


def test_sharded_ownership_and_base_replication():
    arena, _ = sharded_arena()
    assert arena.n_shards == 2
    for i in range(arena.n_base):
        assert arena.owner_of(i) == -1       # replicated, never owned
    h0 = arena.materialize(0, 1, shard=0)
    h1 = arena.materialize(2, 3, shard=1)
    assert arena.owner_of(h0) == 0 and arena.owner_of(h1) == 1


def test_foreign_fetch_counts_d2d_once_per_residency():
    arena, _ = sharded_arena(w=8)
    row_bytes = 8 * 4
    h = arena.materialize(0, 1, shard=0)
    arena.note_access(0, [h, 0, 1])          # owner reads: free
    assert arena.d2d_bytes == 0
    arena.note_access(1, [h, 0])             # shard 1 fetches h
    assert arena.d2d_bytes == row_bytes
    arena.note_access(1, [h])                # cached: no recount
    assert arena.d2d_bytes == row_bytes
    # recycling the slot invalidates residency everywhere
    arena.release(h)
    h2 = arena.materialize(2, 3, shard=0)
    assert h2 == h
    arena.note_access(1, [h2])               # re-fetch after recycle
    assert arena.d2d_bytes == 2 * row_bytes


def test_migrate_reowners_and_accounts():
    arena, _ = sharded_arena(w=4)
    row_bytes = 4 * 4
    h = arena.materialize(0, 1, shard=0)
    moved = arena.migrate([h, 0, h], dst=1)  # base row 0 never moves;
    assert moved == 1                        # second h already at dst
    assert arena.owner_of(h) == 1
    assert arena.migrations == 1
    assert arena.d2d_bytes == row_bytes
    # after migration the new owner reads it for free
    arena.note_access(1, [h])
    assert arena.d2d_bytes == row_bytes


def test_migrate_after_fetch_is_free():
    """A row the destination already fetched (resident in its mirror)
    crossed the link once — migrating it flips ownership without a
    second d2d bill."""
    arena, _ = sharded_arena(w=8)
    row_bytes = 8 * 4
    h = arena.materialize(0, 1, shard=0)
    arena.note_access(1, [h])                # fetch: billed once
    assert arena.d2d_bytes == row_bytes
    moved = arena.migrate([h], dst=1)
    assert moved == 1 and arena.migrations == 1
    assert arena.d2d_bytes == row_bytes      # no double count


def test_migrated_row_lands_on_dst_mirror_without_h2d():
    """Device-backed shards: a migrated row's physical landing in the
    destination mirror is the d2d transfer already billed by migrate()
    — it must not also be billed as a host upload."""
    arena, rows = sharded_arena(n=4, w=8, backing="auto")
    row_bytes = 8 * 4
    h = arena.materialize(0, 1, shard=0)
    arena.device_rows(0, needed=[h])         # shard 0: base + own row
    h2d_before = arena.h2d_bytes
    arena.migrate([h], dst=1)
    assert arena.d2d_bytes == row_bytes
    d1 = arena.device_rows(1, needed=[h])
    np.testing.assert_array_equal(np.asarray(d1[h]), rows[0] & rows[1])
    # shard 1's first sync uploads only the replicated base rows; the
    # migrated row rides its prepaid d2d transfer
    assert arena.h2d_bytes == h2d_before + arena.n_base * row_bytes
    assert arena.d2d_bytes == row_bytes      # still billed exactly once


def test_sharded_device_mirrors_fetch_foreign_rows():
    """Device-backed shards: each mirror holds base rows + its own
    rows; a foreign row is fetched on demand (content-correct, counted
    as d2d) and zero-filled until then."""
    arena, rows = sharded_arena(n=4, w=8, backing="auto")
    h = arena.materialize(0, 1, shard=0)
    d0 = arena.device_rows(0, needed=[h, 0])
    np.testing.assert_array_equal(np.asarray(d0[h]), rows[0] & rows[1])
    assert arena.d2d_bytes == 0
    d1 = arena.device_rows(1, needed=[0, 2])  # base rows only: no d2d
    np.testing.assert_array_equal(np.asarray(d1[:4]), rows)
    assert (np.asarray(d1[h]) == 0).all()     # unfetched foreign row
    assert arena.d2d_bytes == 0
    d1 = arena.device_rows(1, needed=[h])     # now fetch it
    np.testing.assert_array_equal(np.asarray(d1[h]), rows[0] & rows[1])
    assert arena.d2d_bytes == 8 * 4


def test_sharded_ctor_validation():
    with pytest.raises(ValueError, match="n_shards"):
        BitmapArena(4, n_shards=0)
    with pytest.raises(ValueError, match="devices"):
        BitmapArena(4, n_shards=2, devices=[object()])


# --------------------------------------------- engine refcount hygiene
@pytest.fixture()
def capture_arena(monkeypatch):
    """Route fpm.mine's arena construction through a spy so the test
    can inspect refcounts after mining ends."""
    captured = []
    orig = BitmapArena.from_bitmaps.__func__

    class Spy(BitmapArena):
        @classmethod
        def from_bitmaps(cls, bitmaps, backing="auto", **kw):
            arena = orig(cls, bitmaps, backing, **kw)
            captured.append(arena)
            return arena

    monkeypatch.setattr(fpm_mod, "BitmapArena", Spy)
    return captured


def retail_bitmaps():
    from repro.data.transactions import load
    db, p = load("retail", seed=0)
    db = db[:800]
    return pack_database(db, p.n_items), int(0.03 * len(db))


def test_depth_first_releases_every_handoff_row(capture_arena):
    """Clean depth-first run: every materialized child handle is
    released by its task's ``finally`` — no live rows beyond the
    pinned base remain when mining ends."""
    bm, ms = retail_bitmaps()
    _, met = mine(bm, ms, policy="clustered", n_workers=3, max_k=4,
                  granularity="depth-first")
    (arena,) = capture_arena
    assert met.peak_retained_bitmaps > 0     # handoffs happened
    assert arena.live_extra == 0             # ... and all released


def test_refcount_released_on_task_error(capture_arena):
    """A class task that errors mid-subtree must still release its own
    handle AND the handles of children it materialized but never
    spawned — an error may not leak arena rows."""

    class ChildBomb(NumpyBackend):
        def sweep_many(self, arena, requests):
            if any(r.prefix_handle >= arena.n_base for r in requests):
                raise RuntimeError("child boom")
            return super().sweep_many(arena, requests)

    import repro.core.fpm as fpm
    bm, ms = retail_bitmaps()
    orig_resolve = fpm.resolve_backend
    fpm.resolve_backend = lambda spec: ChildBomb()
    try:
        with pytest.raises(RuntimeError, match="child boom"):
            mine(bm, ms, policy="clustered", n_workers=3, max_k=4,
                 granularity="depth-first")
    finally:
        fpm.resolve_backend = orig_resolve
    (arena,) = capture_arena
    assert arena.peak_live_extra > 0         # children were materialized
    assert arena.live_extra == 0             # ... and none leaked


def test_mine_with_jax_arena_matches_serial():
    from repro.core.fpm import mine_serial
    bm, ms = retail_bitmaps()
    ref = mine_serial(bm, ms, max_k=4)
    got, met = mine(bm, ms, n_workers=3, max_k=4, arena="jax",
                    backend="pallas-interpret")
    assert got == ref
    assert met.h2d_bytes >= bm.nbytes        # the eager initial upload


# ----------------------------------------------- segmented arena (streaming)
def test_add_segment_extends_base_rows_only():
    arena, rows = small_arena(n=4, w=3)
    seg = RNG.integers(0, 2 ** 32, size=(4, 2), dtype=np.uint32)
    g = arena.add_segment(seg)
    assert g == 1 and arena.n_segments == 2
    assert arena.n_words == 5 and arena.seg_words(1) == 2
    for i in range(4):
        np.testing.assert_array_equal(arena.row(i),
                                      np.concatenate([rows[i], seg[i]]))
        np.testing.assert_array_equal(arena.seg_row(1, i), seg[i])


def test_add_segment_rejects_wrong_row_count():
    arena, _ = small_arena(n=4, w=3)
    with pytest.raises(ValueError, match="n_base"):
        arena.add_segment(np.zeros((3, 2), np.uint32))


def test_pre_segment_rows_read_zeros_beyond_their_coverage():
    """A row materialized BEFORE an ingest covers only the segments
    that existed then — its words in later segments read as zeros, so
    a stale retained row can never fabricate support in transactions
    it never saw."""
    arena, rows = small_arena(n=4, w=3)
    h = arena.materialize(0, 1)
    seg = np.full((4, 2), 0xFFFFFFFF, np.uint32)
    arena.add_segment(seg)
    got = arena.row(h)
    np.testing.assert_array_equal(got[:3], rows[0] & rows[1])
    assert (got[3:] == 0).all()
    # a row pushed AFTER the ingest covers both segments
    h2 = arena.push(arena.row(0))
    np.testing.assert_array_equal(arena.row(h2), arena.row(0))
    # and a materialize of base rows post-ingest spans both segments
    h3 = arena.materialize(2, 3)
    np.testing.assert_array_equal(
        arena.row(h3), np.concatenate([rows[2] & rows[3],
                                       seg[2] & seg[3]]))


def test_segment_mirror_sync_bills_only_new_segment_bytes():
    """Device mirrors are per-segment: after an ingest, syncing the new
    segment uploads exactly its payload; the old segment's mirror is
    untouched (no re-upload of the whole arena)."""
    arena, rows = small_arena(n=4, w=8)
    arena.device_rows()                          # seg 0: 4 rows x 8 w
    assert arena.h2d_bytes == 4 * 8 * 4
    seg = RNG.integers(0, 2 ** 32, size=(4, 2), dtype=np.uint32)
    arena.add_segment(seg)
    dev1 = arena.device_rows(segment=1)
    assert arena.h2d_bytes == 4 * 8 * 4 + arena.seg_nbytes(1)
    assert arena.seg_nbytes(1) == 4 * 2 * 4
    np.testing.assert_array_equal(np.asarray(dev1)[:4], seg)
    arena.device_rows()                          # seg 0 unchanged:
    assert arena.h2d_bytes == 4 * 8 * 4 + 4 * 2 * 4   # no new upload


def test_eager_backing_uploads_each_segment_once():
    arena, _ = small_arena(n=5, w=3, backing="jax")
    assert arena.h2d_bytes == 5 * 3 * 4
    arena.add_segment(np.ones((5, 4), np.uint32))
    # eager: the ingest itself mirrored the new segment — and ONLY it
    assert arena.h2d_bytes == 5 * 3 * 4 + 5 * 4 * 4


def test_slot_recycle_across_segments_invalidates_every_mirror():
    """A recycled slot's stale words must be invalidated (and resynced
    on demand) in EVERY segment mirror, not just segment 0."""
    arena, rows = small_arena(n=4, w=4)
    seg = RNG.integers(0, 2 ** 32, size=(4, 3), dtype=np.uint32)
    arena.add_segment(seg)
    h = arena.materialize(0, 1)
    arena.device_rows(segment=0)
    arena.device_rows(segment=1)
    h2d = arena.h2d_bytes
    arena.release(h)
    h2 = arena.materialize(2, 3)
    assert h2 == h                               # slot recycled
    d0 = arena.device_rows(segment=0)
    d1 = arena.device_rows(segment=1)
    np.testing.assert_array_equal(np.asarray(d0[h2]), rows[2] & rows[3])
    np.testing.assert_array_equal(np.asarray(d1[h2]), seg[2] & seg[3])
    # reupload billed per segment at that segment's width
    assert arena.h2d_bytes == h2d + 4 * 4 + 3 * 4


def test_segmented_sweep_restricted_to_segment_subset():
    """The numpy backend sums per-segment joins; a segments= request
    reads only those segments (the streaming delta sweep)."""
    from repro.core.join_backend import NumpyBackend, SweepRequest
    from repro.core.tidlist import popcount32
    arena, rows = small_arena(n=4, w=3)
    seg = RNG.integers(0, 2 ** 32, size=(4, 2), dtype=np.uint32)
    arena.add_segment(seg)
    be = NumpyBackend()
    full = be.sweep_many(arena, [SweepRequest(0, (1, 2))])[0]
    want_full = [int(popcount32(np.concatenate([rows[0] & rows[e],
                                                seg[0] & seg[e]])).sum())
                 for e in (1, 2)]
    assert list(full) == want_full
    delta = be.sweep_many(arena,
                          [SweepRequest(0, (1, 2), segments=(1,))])[0]
    want_delta = [int(popcount32(seg[0] & seg[e]).sum()) for e in (1, 2)]
    assert list(delta) == want_delta
    both = be.sweep_many(arena,
                         [SweepRequest(0, (1, 2), segments=(0, 1))])[0]
    assert list(both) == want_full


def test_zero_width_segments_are_skipped():
    """An empty initial database (or empty batch) packs to a
    zero-width segment; sweeps skip it and counts stay correct."""
    from repro.core.join_backend import NumpyBackend, SweepRequest
    from repro.core.tidlist import popcount32
    arena = BitmapArena.from_bitmaps(np.zeros((3, 0), np.uint32))
    seg = RNG.integers(0, 2 ** 32, size=(3, 2), dtype=np.uint32)
    arena.add_segment(seg)
    arena.add_segment(np.zeros((3, 0), np.uint32))
    be = NumpyBackend()
    counts = be.sweep_many(arena, [SweepRequest(0, (1, 2))])[0]
    want = [int(popcount32(seg[0] & seg[e]).sum()) for e in (1, 2)]
    assert list(counts) == want


def test_pallas_interpret_matches_numpy_on_segmented_arena():
    from repro.core.join_backend import (NumpyBackend,
                                         PallasInterpretBackend,
                                         SweepRequest)
    arena, rows = small_arena(n=6, w=4)
    arena.add_segment(RNG.integers(0, 2 ** 32, size=(6, 3),
                                   dtype=np.uint32))
    reqs = [SweepRequest(0, (1, 2, 3)),
            SweepRequest(1, (2, 4), segments=(1,)),
            SweepRequest(2, (3,), segments=(0,))]
    a = NumpyBackend().sweep_many(arena, reqs)
    b = PallasInterpretBackend().sweep_many(arena, reqs)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)


# ---------------------------------------------------------- compaction
def test_compact_merges_segments_preserving_rows_and_handles():
    """compact() collapses the segment axis only: every handle reads
    the same full-width row before and after, coverage semantics
    (zeros beyond a stale row's ingest horizon) included."""
    arena, rows = small_arena(n=4, w=3)
    h_pre = arena.materialize(0, 1)             # covers segment 0 only
    seg1 = RNG.integers(0, 2 ** 32, size=(4, 2), dtype=np.uint32)
    seg2 = RNG.integers(0, 2 ** 32, size=(4, 1), dtype=np.uint32)
    arena.add_segment(seg1)
    arena.add_segment(seg2)
    h_post = arena.materialize(2, 3)            # covers all three
    before = {h: arena.row(h).copy()
              for h in (0, 1, 2, 3, h_pre, h_post)}
    removed = arena.compact(3)
    assert removed == 2
    assert arena.n_segments == 1
    assert arena.seg_words(0) == 3 + 2 + 1 == arena.n_words
    assert arena.compactions == 1
    assert arena.compaction_bytes == arena.n_rows * 6 * 4
    for h, want in before.items():
        np.testing.assert_array_equal(arena.row(h), want)
    # the pre-ingest row still reads zeros beyond its old coverage
    assert (arena.row(h_pre)[3:] == 0).all()


def test_compact_partial_prefix_and_segment_id_shift():
    """compact(upto=2) folds only the cold prefix; the remaining
    segment shifts down and keeps serving segment-restricted sweeps."""
    from repro.core.join_backend import NumpyBackend, SweepRequest
    from repro.core.tidlist import popcount32
    arena, rows = small_arena(n=4, w=2)
    seg1 = RNG.integers(0, 2 ** 32, size=(4, 1), dtype=np.uint32)
    seg2 = RNG.integers(0, 2 ** 32, size=(4, 3), dtype=np.uint32)
    arena.add_segment(seg1)
    arena.add_segment(seg2)
    full_before = arena.row(0).copy()
    assert arena.compact(2) == 1
    assert arena.n_segments == 2
    assert arena.seg_words(0) == 3 and arena.seg_words(1) == 3
    np.testing.assert_array_equal(arena.row(0), full_before)
    # old segment 2 is now segment 1
    delta = NumpyBackend().sweep_many(
        arena, [SweepRequest(0, (1, 2), segments=(1,))])[0]
    want = [int(popcount32(seg2[0] & seg2[e]).sum()) for e in (1, 2)]
    assert list(delta) == want


def test_compact_guards_reject_trivial_or_out_of_range():
    arena, _ = small_arena(n=4, w=2)
    assert arena.compact(1) == 0                # nothing to merge
    assert arena.compact(2) == 0                # only one segment
    arena.add_segment(np.ones((4, 1), np.uint32))
    assert arena.compact(3) == 0                # beyond segment count
    assert arena.compactions == 0
    assert arena.compact(2) == 1


def test_compact_recycled_slot_spans_compaction():
    """A slot recycled BEFORE a compaction keeps its new content and
    its new coverage through the merge."""
    arena, rows = small_arena(n=4, w=2)
    h = arena.materialize(0, 1)
    seg1 = RNG.integers(0, 2 ** 32, size=(4, 2), dtype=np.uint32)
    arena.add_segment(seg1)
    arena.release(h)
    h2 = arena.materialize(2, 3)                # recycles the slot,
    assert h2 == h                              # now covers both segs
    arena.compact(2)
    np.testing.assert_array_equal(
        arena.row(h2), np.concatenate([rows[2] & rows[3],
                                       seg1[2] & seg1[3]]))


def test_compact_fully_synced_mirror_merges_without_h2d():
    """Eager backing keeps every segment mirror complete, so compact()
    merges them device-side: the next device_rows() is free."""
    arena, rows = small_arena(n=4, w=2, backing="jax")
    seg1 = RNG.integers(0, 2 ** 32, size=(4, 1), dtype=np.uint32)
    arena.add_segment(seg1)
    h2d = arena.h2d_bytes
    arena.compact(2)
    dev = arena.device_rows(segment=0)
    assert arena.h2d_bytes == h2d               # no re-upload
    np.testing.assert_array_equal(
        np.asarray(dev)[:4], np.concatenate([rows, seg1], axis=1))


def test_compact_unsynced_mirror_resyncs_from_host():
    """With a lazily-backed arena that never synced, compact() leaves
    the merged block host-only; a later device_rows() re-syncs it at
    the merged width and the content is exact."""
    arena, rows = small_arena(n=4, w=2)
    seg1 = RNG.integers(0, 2 ** 32, size=(4, 1), dtype=np.uint32)
    arena.add_segment(seg1)
    arena.compact(2)
    dev = arena.device_rows(segment=0)
    if dev is not None:                         # device backing enabled
        np.testing.assert_array_equal(
            np.asarray(dev)[:4], np.concatenate([rows, seg1], axis=1))
        assert arena.h2d_bytes >= 4 * 3 * 4


def test_sweeps_identical_across_compaction():
    """The same batch of (tuple-prefix, segment-restricted) sweeps
    returns identical counts before and after compact()."""
    from repro.core.join_backend import NumpyBackend, SweepRequest

    def reqs():
        return [SweepRequest(0, (1, 2, 3)),
                SweepRequest((0, 1), (2, 3)),
                SweepRequest(2, (3,), segments=(2,))]

    arena, rows = small_arena(n=5, w=2)
    arena.add_segment(RNG.integers(0, 2 ** 32, (5, 1), np.uint32))
    arena.add_segment(RNG.integers(0, 2 ** 32, (5, 2), np.uint32))
    be = NumpyBackend()
    before = be.sweep_many(arena, reqs())
    arena.compact(2)                            # old seg 2 -> seg 1
    after = be.sweep_many(
        arena, [SweepRequest(0, (1, 2, 3)),
                SweepRequest((0, 1), (2, 3)),
                SweepRequest(2, (3,), segments=(1,))])
    for x, y in zip(before, after):
        np.testing.assert_array_equal(x, y)
