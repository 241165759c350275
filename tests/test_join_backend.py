"""Join-backend layer: batched numpy vs Pallas parity, the sweep
dispatcher's coalescing/flush/error semantics, backend resolution."""
import threading

import numpy as np
import pytest

from repro.core import join_backend as jb
from repro.core import tidlist
from repro.core.tidlist import BitmapArena

RNG = np.random.default_rng(7)


def rand_arena(n_rows, w, backing="auto"):
    rows = RNG.integers(0, 2 ** 32, size=(n_rows, w), dtype=np.uint32)
    return BitmapArena.from_bitmaps(rows, backing=backing), rows


def naive_counts(prefix, exts):
    return np.array([sum(bin(int(prefix[w]) & int(exts[i, w])).count("1")
                         for w in range(len(prefix)))
                     for i in range(exts.shape[0])], dtype=np.int64)


def make_requests(n_rows, specs):
    """specs: list of (prefix_handle, ext_handles) pairs."""
    return [jb.SweepRequest(p, tuple(e)) for p, e in specs]


# ------------------------------------------------------------- backends
@pytest.mark.parametrize("e,w", [(1, 1), (5, 9), (33, 64)])
def test_numpy_backend_matches_naive(e, w):
    arena, rows = rand_arena(e + 1, w)
    reqs = make_requests(e + 1, [(0, range(1, e + 1))])
    (got,) = jb.get_backend("numpy").sweep_many(arena, reqs)
    np.testing.assert_array_equal(got, naive_counts(rows[0], rows[1:]))


@pytest.mark.parametrize("backing", ["auto", "numpy"])
def test_numpy_vs_pallas_interpret_parity_ragged(backing):
    """The batched kernel path must be bit-exact with the numpy path on
    a ragged batch (different extension counts per request — the padded
    and masked lanes must not leak into any request's counts), for both
    the device-gather and host-gather arena paths."""
    arena, rows = rand_arena(12, 40, backing=backing)
    specs = [(0, range(1, 12)),          # wide
             (3, [7]),                   # single extension
             (11, [0, 2, 4, 6, 8, 10]),  # strided
             (5, range(6, 9))]           # narrow
    a = jb.get_backend("numpy").sweep_many(
        arena, make_requests(12, specs))
    b = jb.get_backend("pallas-interpret").sweep_many(
        arena, make_requests(12, specs))
    assert len(a) == len(b) == len(specs)
    for (p, e), x, y in zip(specs, a, b):
        np.testing.assert_array_equal(x, y)
        np.testing.assert_array_equal(
            x, naive_counts(rows[p], rows[list(e)]))
        assert y.dtype == np.int64


def flush_arena(backing, widths):
    """Twelve base item rows over segments of the given word widths, a
    materialized dense row, two tid-lists and a diffset (over the whole
    tid range, so each segment holds some of their tids)."""
    n = 12
    arena = BitmapArena.from_bitmaps(
        RNG.integers(0, 2 ** 32, size=(n, widths[0]), dtype=np.uint32),
        backing=backing)
    for w in widths[1:]:
        arena.add_segment(RNG.integers(0, 2 ** 32, size=(n, w),
                                       dtype=np.uint32))
    bits = 32 * arena.n_words

    def tids(k):
        return np.sort(RNG.choice(bits, size=min(k, bits), replace=False))

    handles = {"and": arena.materialize(0, 1),
               "tids": arena.push_tids(tids(40)),
               "tids_long": arena.push_tids(tids(300))}
    handles["diff"] = arena.push_diffset(tids(9), anchor=0, support=1)
    return arena, handles


# (arena backing, segment widths, [(prefix, exts, segments)]): prefix is
# an item handle, a tuple of them, or the name of a pushed row
FLUSHES = {
    "mixed": ("auto", (40,), [
        (0, range(1, 12), None), ("tids", (2, 5, 7), None),
        ("and", (3, 4), None), ("diff", range(12), None),
        ("tids_long", (), None), (5, (), None)]),
    "tuple-prefixes": ("auto", (40,), [
        ((0, 1), range(2, 12), None), ((2, 3, 4), (5, 6), None),
        (7, (8,), None), ((9, 10, 11, 0, 1), (2, 3, 4), None),
        ("tids", (1, 2), None)]),
    "delta-segments": ("auto", (40, 3, 70), [
        ((0, 1), range(2, 12), (1, 2)), (3, (4, 5, 6), (2,)),
        ("tids_long", range(12), (1, 2)), ("tids", (0, 11), None),
        ((6, 7), (), (1,)), (8, (9, 10), None)]),
    "host-gather": ("numpy", (40, 5), [
        ((0, 1), range(2, 12), None), ("tids_long", range(12), None),
        ("and", (2, 3), (1,)), ("diff", (4, 5, 6), None),
        (9, (), None)]),
}


@pytest.mark.parametrize("case", sorted(FLUSHES))
def test_packed_flush_matches_numpy_backend(case):
    """Each flush part's packed descriptor (prefix or tid columns, then
    extension handles, -1 sentinels) gives the numpy backend's counts:
    dense and sparse parts launched together, tuple prefixes of several
    lengths, ragged and empty extension lists, delta sweeps over
    segments of different widths, and the host-gather baseline."""
    backing, widths, specs = FLUSHES[case]
    arena, handles = flush_arena(backing, widths)

    def requests():
        return [jb.SweepRequest(handles.get(p, p) if isinstance(p, str)
                                else p, tuple(e), segments=seg)
                for p, e, seg in specs]

    want = jb.get_backend("numpy").sweep_many(arena, requests())
    got = jb.get_backend("pallas-interpret").sweep_many(arena, requests())
    assert len(got) == len(want) == len(specs)
    for (p, e, _), x, y in zip(specs, want, got):
        assert y.dtype == np.int64 and len(y) == len(tuple(e))
        np.testing.assert_array_equal(y, x, err_msg=str(p))
    assert any(x.any() for x in want)


def test_bitmap_join_many_mask_zeroes_padding():
    import jax.numpy as jnp

    from repro.kernels.bitmap_join.ops import bitmap_join_many
    prefixes = jnp.asarray(RNG.integers(0, 2 ** 32, size=(2, 8),
                                        dtype=np.uint32))
    exts = jnp.asarray(RNG.integers(0, 2 ** 32, size=(2, 5, 8),
                                    dtype=np.uint32))
    mask = jnp.asarray(np.array([[1, 1, 1, 0, 0],
                                 [1, 0, 0, 0, 0]], dtype=bool))
    got = np.asarray(bitmap_join_many(prefixes, exts, mask, mode="ref"))
    assert (got[0, 3:] == 0).all() and (got[1, 1:] == 0).all()
    assert got[0, 0] > 0 or got[0, 1] > 0   # real lanes survive


def test_support_counts_chunked_matches_unchunked():
    prefix = RNG.integers(0, 2 ** 32, size=16, dtype=np.uint32)
    exts = RNG.integers(0, 2 ** 32, size=(50, 16), dtype=np.uint32)
    full = tidlist.support_counts(prefix, exts)
    chunked = tidlist.support_counts(prefix, exts, chunk=7)
    np.testing.assert_array_equal(full, chunked)


# ----------------------------------------------------------- dispatcher
def test_dispatcher_coalesces_full_batch():
    """n_clients pending requests flush as ONE batched launch (the
    dispatcher knows no further request can arrive once every client
    is blocked). flush_us is set high so a premature partial flush
    would be visible as flushes > 1."""
    arena, rows = rand_arena(9, 6)
    disp = jb.SweepDispatcher(arena, jb.get_backend("numpy"),
                              n_clients=4, flush_us=500_000)
    try:
        futs = [disp.submit(p, tuple(range(p + 1, 9))) for p in range(4)]
        for p, f in enumerate(futs):
            np.testing.assert_array_equal(
                f.result(timeout=10),
                naive_counts(rows[p], rows[p + 1:]))
        assert disp.flushes == 1
        assert disp.batch_occupancy == 4.0
    finally:
        disp.stop()


def test_dispatcher_partial_flush_on_timeout():
    """A lone request must not wait for a batch that will never fill:
    the flush_us deadline bounds its latency."""
    arena, rows = rand_arena(4, 3)
    disp = jb.SweepDispatcher(arena, jb.get_backend("numpy"),
                              n_clients=8, flush_us=1_000)
    try:
        got = disp.sweep(0, (1, 2, 3))
        np.testing.assert_array_equal(got, naive_counts(rows[0], rows[1:]))
        assert disp.flushes == 1 and disp.batch_occupancy == 1.0
    finally:
        disp.stop()


def test_dispatcher_error_resolves_every_future():
    class Bomb(jb.JoinBackend):
        def sweep_many(self, arena, requests):
            raise RuntimeError("batch boom")

    arena, _ = rand_arena(4, 3)
    disp = jb.SweepDispatcher(arena, Bomb(), n_clients=2,
                              flush_us=200_000)
    try:
        f1 = disp.submit(0, (1,))
        f2 = disp.submit(1, (2, 3))
        for f in (f1, f2):
            with pytest.raises(RuntimeError, match="batch boom"):
                f.result(timeout=10)
    finally:
        disp.stop()


def test_dispatcher_concurrent_clients_agree_with_serial():
    """Many threads hammering the dispatcher get exactly their own
    counts back (no cross-request mixups under coalescing)."""
    arena, rows = rand_arena(20, 10)
    disp = jb.SweepDispatcher(arena, jb.get_backend("numpy"),
                              n_clients=6)
    errs = []

    def client(p):
        try:
            exts = tuple(i for i in range(20) if i != p)
            for _ in range(5):
                got = disp.sweep(p, exts)
                np.testing.assert_array_equal(
                    got, naive_counts(rows[p], rows[list(exts)]))
        except BaseException as e:  # noqa: BLE001
            errs.append(e)

    threads = [threading.Thread(target=client, args=(p,))
               for p in range(6)]
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
    finally:
        disp.stop()
    assert not errs, errs
    assert disp.requests == 30


def test_dispatcher_submit_after_stop_raises():
    arena, _ = rand_arena(2, 2)
    disp = jb.SweepDispatcher(arena, jb.get_backend("numpy"), n_clients=1)
    disp.stop()
    with pytest.raises(RuntimeError, match="stopped"):
        disp.submit(0, (1,))


# ------------------------------------------------------------ resolution
def test_get_backend_unknown_name_raises():
    with pytest.raises(ValueError, match="unknown join backend"):
        jb.get_backend("cuda")


def test_resolve_backend_named():
    assert jb.resolve_backend("pallas-interpret").name == \
        "pallas-interpret"
    assert jb.resolve_backend("numpy").name == "numpy"


def test_resolve_backend_auto_is_numpy_on_cpu():
    import jax
    if jax.default_backend() == "tpu":
        pytest.skip("auto resolution differs on TPU")
    assert jb.resolve_backend("auto").name == "numpy"


def test_available_backends_always_has_cpu_paths():
    names = jb.available_backends()
    assert "numpy" in names and "pallas-interpret" in names


def test_e_pad_floor_matches_kernel_tile():
    """The batch E-pad floor must track the kernel's E tile: a smaller
    floor would mint distinct jit shapes the kernel re-pads to one tile
    anyway (pure compile-cache waste)."""
    from repro.kernels.bitmap_join.kernel import EB_TILE
    from repro.kernels.gather_intersect.kernel import E_TILE
    assert jb.E_PAD_FLOOR == EB_TILE == E_TILE


def test_ops_mode_dispatch_parity():
    import jax.numpy as jnp

    from repro.kernels.bitmap_join.ops import bitmap_join, bitmap_join_many
    prefix = RNG.integers(0, 2 ** 32, size=12, dtype=np.uint32)
    exts = RNG.integers(0, 2 ** 32, size=(9, 12), dtype=np.uint32)
    ref = bitmap_join(jnp.asarray(prefix), jnp.asarray(exts), mode="ref")
    itp = bitmap_join(jnp.asarray(prefix), jnp.asarray(exts),
                      mode="pallas-interpret")
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(itp))
    with pytest.raises(ValueError, match="mode"):
        bitmap_join(jnp.asarray(prefix), jnp.asarray(exts), mode="gpu")
    with pytest.raises(ValueError, match="mode"):
        bitmap_join_many(jnp.asarray(prefix[None]),
                         jnp.asarray(exts[None]), mode="gpu")


def test_unavailable_backend_fails_fast():
    """pallas-jit off-TPU must raise at backend resolution — not inside
    the dispatcher thread mid-mine (regression: this deadlocked
    wait_all before the scheduler recorded task errors)."""
    import jax
    if jax.default_backend() == "tpu":
        pytest.skip("pallas-jit is available on TPU")
    with pytest.raises(ValueError, match="not available"):
        jb.resolve_backend("pallas-jit")


def test_mine_with_unavailable_backend_raises_not_hangs():
    import jax
    if jax.default_backend() == "tpu":
        pytest.skip("pallas-jit is available on TPU")
    from repro.core.fpm import mine
    bm = RNG.integers(0, 2 ** 32, size=(6, 2), dtype=np.uint32)
    with pytest.raises(ValueError, match="not available"):
        mine(bm, 1, n_workers=2, max_k=3, backend="pallas-jit")


# ------------------------------------------- every sweep is a flush
def _basket_db(n_tx, n_items, seed):
    rng = np.random.default_rng(seed)
    return [sorted(rng.choice(n_items, size=int(rng.integers(3, 9)),
                              replace=False).tolist())
            for _ in range(n_tx)]


@pytest.mark.parametrize("phase", ["mine", "refresh"])
@pytest.mark.parametrize("granularity",
                         ["bucket", "candidate", "depth-first", "auto"])
def test_every_sweep_runs_on_a_dispatcher_thread(monkeypatch, granularity,
                                                 phase):
    """No engine path runs the backend itself: every ``sweep_many``
    call of a mine, and of a refresh after an ingest, comes from a
    dispatcher thread, at every granularity — on the host backend
    exactly as on the kernels — and the requests it carries are the
    sweeps the workers submitted."""
    from repro.core.fpm import mine, mine_serial
    from repro.core.streaming import StreamingMiner
    calls = []
    sweep_many = jb.NumpyBackend.sweep_many

    def recording(self, arena, requests):
        calls.append((threading.current_thread().name, len(requests)))
        return sweep_many(self, arena, requests)

    monkeypatch.setattr(jb.NumpyBackend, "sweep_many", recording)
    db = _basket_db(400, 12, seed=11)
    ms = 30
    if phase == "mine":
        bm, counts = tidlist.pack_database(db, 12, return_counts=True)
        res, met = mine(bm, ms, n_workers=3, max_k=5, backend="numpy",
                        granularity=granularity, item_counts=counts)
        assert res == mine_serial(bm, ms, max_k=5)
        assert sum(n for _, n in calls) == \
            met.scheduler["sweeps_submitted"]
    else:
        sm = StreamingMiner(12, ms, initial_db=db[:340], n_workers=3,
                            max_k=5, granularity=granularity,
                            backend="numpy")
        try:
            sm.refresh()
            sm.ingest(db[340:])
            calls.clear()
            rep = sm.refresh()
            assert rep.swept_delta > 0        # a delta path ran
            if granularity != "depth-first":
                # the level drivers' dirty buckets go out as chunk
                # bursts, enqueued at once, so they share flushes
                assert max(n for _, n in calls) > 1
            full = tidlist.pack_database(db, 12)
            assert dict(sm.snapshot.supports) == mine_serial(full, ms,
                                                             max_k=5)
        finally:
            sm.close()
    assert {name for name, _ in calls} == {"sweep-dispatcher-0"}
