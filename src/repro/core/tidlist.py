"""Transaction-ID (TID) bitmap machinery.

The paper's per-task computation is a TID-list join: support(itemset) =
|∩_{i∈itemset} tidlist(i)|. On TPU (and for GIL-released numpy in the
shared-memory scheduler) TID lists are packed uint32 bitmaps: the join is
AND + popcount — VPU work that the Pallas ``bitmap_join`` kernel tiles so
the shared *prefix* bitmap stays VMEM-resident (the paper's cache reuse,
re-expressed; DESIGN.md §3).
"""
from __future__ import annotations

import functools
import threading
import time
from typing import List, Optional, Sequence, Tuple

import numpy as np

WORD = 32


def n_words(n_transactions: int) -> int:
    return (n_transactions + WORD - 1) // WORD


# Device mirrors keep a power-of-two row capacity of at least this many
# rows (see BitmapArena.device_rows).
MIRROR_MIN_ROWS = 8


def pow2(n: int, lo: int = 1) -> int:
    """Smallest power-of-two multiple of ``lo`` that is >= ``n``."""
    p = lo
    while p < n:
        p *= 2
    return p


@functools.lru_cache(maxsize=None)
def _mirror_ops():
    """The two jitted mirror updates, built once: ``write_rows`` lands a
    block of fresh rows at a traced offset, ``set_rows`` scatters rows
    by handle. Both keep the mirror's shape."""
    import jax

    @jax.jit
    def write_rows(dev, rows, lo):
        return jax.lax.dynamic_update_slice(dev, rows, (lo, np.int32(0)))

    @jax.jit
    def set_rows(dev, idx, rows):
        return dev.at[idx].set(rows)

    return write_rows, set_rows


def _pad_rows(dev, cap: int):
    """Grow a mirror buffer to ``cap`` rows with zero rows."""
    import jax.numpy as jnp
    return jnp.pad(dev, ((0, cap - dev.shape[0]), (0, 0)))


def _pow2_set(handles: Sequence[int], rows: np.ndarray):
    """Pad a (handles, rows) scatter to a power-of-two length by
    repeating its first entry — a duplicate write of identical values,
    so the result is unchanged while the scatter's shape stays in a
    small set."""
    k = pow2(len(handles))
    idx = np.full(k, handles[0], np.int32)
    idx[:len(handles)] = handles
    out = np.repeat(rows[:1], k, axis=0)
    out[:len(handles)] = rows
    return idx, out


def pack_database(db: Sequence[Sequence[int]], n_items: int,
                  return_counts: bool = False):
    """db: list of transactions (item id lists) -> [n_items, W] uint32.

    Packs per-word directly — O(n_items × W) memory, never the dense
    [n_items, n_transactions] bool matrix (which on scaled Quest/retail
    profiles could exceed the packed bitmaps by 32× and blow host
    memory before mining even starts).

    With ``return_counts=True`` also returns the per-item ones count
    (``[n_items] int64``) tallied during the same pass — the level-1
    supports and density seed, with no post-hoc popcount sweep over
    the packed words."""
    m = len(db)
    out = np.zeros((n_items, n_words(m)), dtype=np.uint32)
    counts = np.zeros(n_items, dtype=np.int64)
    for t, txn in enumerate(db):
        word = t >> 5
        bit = np.uint32(1 << (t & 31))
        for i in txn:
            if not out[i, word] & bit:
                counts[i] += 1
            out[i, word] |= bit
    if return_counts:
        return out, counts
    return out


def pack_bool(bits: np.ndarray) -> np.ndarray:
    """[I, T] bool -> [I, W] uint32 (little-endian bit order per word)."""
    i, t = bits.shape
    w = n_words(t)
    padded = np.zeros((i, w * WORD), dtype=bool)
    padded[:, :t] = bits
    packed = np.packbits(padded.reshape(i, w, WORD)[:, :, ::-1], axis=-1)
    return packed.view(">u4").astype(np.uint32).reshape(i, w)


def unpack_bool(packed: np.ndarray, n_transactions: int) -> np.ndarray:
    """[I, W] uint32 -> [I, T] bool."""
    i, w = packed.shape
    be = packed.astype(">u4")
    by = be.view(np.uint8).reshape(i, w, 4)
    bits = np.unpackbits(by, axis=-1).reshape(i, w * WORD).astype(bool)
    # restore per-word little-endian bit order
    bits = bits.reshape(i, w, WORD)[:, :, ::-1].reshape(i, w * WORD)
    return bits[:, :n_transactions]


def popcount32(x: np.ndarray) -> np.ndarray:
    """Vectorized popcount for uint32 arrays (numpy, GIL-released)."""
    if hasattr(np, "bitwise_count"):          # numpy >= 2.0: one ufunc pass
        return np.bitwise_count(x).astype(np.int64)
    if x.dtype != np.uint32:                  # hot path: no copy when the
        x = x.astype(np.uint32)               # input is already uint32
    x = x - ((x >> 1) & np.uint32(0x55555555))
    x = (x & np.uint32(0x33333333)) + ((x >> 2) & np.uint32(0x33333333))
    x = (x + (x >> 4)) & np.uint32(0x0F0F0F0F)
    return ((x * np.uint32(0x01010101)) >> 24).astype(np.int64)


def intersect(bitmaps: np.ndarray) -> np.ndarray:
    """AND-reduce [k, W] -> [W]."""
    out = bitmaps[0].copy()
    for b in bitmaps[1:]:
        out &= b
    return out


def support_of(bitmap_rows: np.ndarray) -> int:
    """|∩ rows| for a [k, W] stack of bitmaps."""
    return int(popcount32(intersect(bitmap_rows)).sum())


# Target working-set size for one [chunk, W] AND+popcount temporary:
# roughly half an L2 slice, so the chunk stays cache-resident even on
# scaled datasets where W grows with the transaction count.
CHUNK_TARGET_BYTES = 4 << 20


def support_counts(prefix: np.ndarray, exts: np.ndarray,
                   chunk: int | None = None) -> np.ndarray:
    """counts[e] = |prefix ∩ exts[e]|. prefix: [W]; exts: [E, W].

    This is the numpy bucket-sweep: one fused AND+popcount pass with the
    prefix row broadcast (cache-resident) across all extensions — the
    vectorized analogue of the Pallas bitmap_join kernel. ``chunk``
    bounds the [chunk, W] temporary; by default it adapts to W so the
    temporary stays ~CHUNK_TARGET_BYTES regardless of dataset scale."""
    e, w = exts.shape
    if e == 1:
        # single-extension fast path (deep, narrow equivalence classes):
        # skip the [E, W] broadcast temporary entirely
        return popcount32(exts[0] & prefix).sum(keepdims=True)
    if chunk is None:
        chunk = max(64, CHUNK_TARGET_BYTES // max(w * (WORD // 8), 1))
    if e <= chunk:
        return popcount32(exts & prefix[None, :]).sum(axis=1)
    out = np.empty(e, dtype=np.int64)
    for lo in range(0, e, chunk):
        hi = min(lo + chunk, e)
        out[lo:hi] = popcount32(exts[lo:hi] & prefix[None, :]).sum(axis=1)
    return out


# ---------------------------------------------------------------------------
# Sparse (tid-list / dEclat diffset) row helpers
# ---------------------------------------------------------------------------
# A *tid* is a global bit position on the concatenated segment word
# axis: tid = 32 * word_index + bit. Packing zero-fills past the real
# transaction count and compact() concatenates segments in order, so a
# sparse row's tids stay valid across ingest and compaction without
# rewriting.

REP_BITMAP, REP_TIDLIST, REP_DIFFSET = 0, 1, 2
REP_NAMES = ("bitmap", "tidlist", "diffset")


def bitmap_to_tids(words: np.ndarray) -> np.ndarray:
    """[W] uint32 word-column -> sorted uint32 tids of its set bits."""
    w = words.shape[0]
    if w == 0:
        return np.zeros(0, np.uint32)
    bits = unpack_bool(words[None, :], w * WORD)[0]
    return np.flatnonzero(bits).astype(np.uint32)


def tids_to_bitmap(tids: np.ndarray, n_words_: int) -> np.ndarray:
    """Sorted uint32 tids -> [n_words_] uint32 word-column."""
    out = np.zeros(n_words_, np.uint32)
    if len(tids):
        t = np.asarray(tids, np.uint32)
        np.bitwise_or.at(out, t >> np.uint32(5),
                         np.uint32(1) << (t & np.uint32(31)))
    return out


def sorted_difference(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """a \\ b for sorted unique uint32 arrays (diffset reconstruction:
    tids(P) = tids(parent) \\ diffset). Binary-search based — ``np.isin``
    re-sorts the concatenation, which dominates diffset-chain walks."""
    if len(b) == 0 or len(a) == 0:
        return a
    idx = np.searchsorted(b, a)
    np.minimum(idx, len(b) - 1, out=idx)
    return a[b[idx] != a]


def partition_words(n_words_: int, n_hosts: int) -> List[Tuple[int, int]]:
    """Contiguous balanced word ranges ``[(w0, w1), ...]`` over the
    transaction axis, one per host.

    Multi-host mining slices the packed ``[n_items, W]`` database on
    the word (= 32-transaction block) axis: host ``h`` builds its
    local :class:`BitmapArena` from ``bitmaps[:, w0:w1]`` and sweeps
    only those columns. Word granularity keeps every host's slice a
    plain view with no bit surgery, and the remainder is spread over
    the leading hosts so slice widths differ by at most one word.
    Hosts beyond ``n_words_`` get empty ``(w, w)`` ranges — legal, the
    backends skip zero-width segments."""
    if n_hosts < 1:
        raise ValueError(f"n_hosts must be >= 1, got {n_hosts}")
    base, extra = divmod(n_words_, n_hosts)
    ranges: List[Tuple[int, int]] = []
    w = 0
    for h in range(n_hosts):
        width = base + (1 if h < extra else 0)
        ranges.append((w, w + width))
        w += width
    return ranges


# ---------------------------------------------------------------------------
# BitmapArena: the device-resident home of every TID bitmap
# ---------------------------------------------------------------------------

ARENA_BACKINGS = ("auto", "numpy", "jax")


class BitmapArena:
    """Append-only ``[N, W]`` uint32 row store with integer handles.

    Every bitmap the mining engines touch lives here: the pinned item
    bitmaps loaded once by :meth:`from_bitmaps` (handle == item id),
    cached prefix intersections, and the depth-first engine's
    materialized child bitmaps. Tasks pass *handles* around instead of
    floating ndarrays, so the sweep dispatcher can batch many workers'
    requests into one multi-prefix kernel launch without re-marshalling
    bitmap payloads.

    Rows are refcounted: :meth:`push`/:meth:`materialize` return a
    handle with refcount 1, :meth:`retain`/:meth:`release` adjust it,
    and a row whose count reaches zero goes on a free list — the next
    push reuses the slot, so the depth-first engine's churn of child
    bitmaps recycles storage instead of growing ``N`` without bound.
    Rows below ``n_base`` (the item bitmaps) are pinned: retain/release
    on them are no-ops.

    Device residency (``backing``):
      "auto"   a jax mirror is created lazily on the first
               :meth:`device_rows` call and kept in sync incrementally —
               only rows appended or recycled since the last sync are
               uploaded, and those payload bytes accumulate in
               ``h2d_bytes`` (index uploads, 4 B/row vs ``4·W`` B of
               payload, are not counted).
      "jax"    same, but the initial upload happens eagerly at load.
      "numpy"  host-only; :meth:`device_rows` returns None, so Pallas
               backends fall back to per-batch host gathers (the old
               transfer-bound behaviour, kept as the A/B baseline for
               the h2d benchmark).

    Sharded mode (``n_shards`` > 1, optionally with a ``devices`` list
    from a jax mesh): one mirror per shard. Pinned item rows are
    *replicated* into every shard's mirror; a materialized row is
    *owned* by the shard that created it (``push``/``materialize``
    take a ``shard=`` argument) and lives only in its owner's mirror.
    When a sweep on shard *s* references a row owned by shard *t*, the
    row is fetched into *s*'s mirror on demand and the payload is
    counted in the ``d2d_bytes`` gauge — the modeled cross-device
    traffic (on this container's virtual devices the bits physically
    route through the host, but the gauge records what a real mesh
    would ship device-to-device). :meth:`migrate` re-owners rows
    explicitly (the scheduler's cross-device bucket steal) and counts
    the same gauge. Host-only ("numpy") backings keep the identical
    ownership/residency bookkeeping via :meth:`note_access`, so the
    tier-1 CPU suite exercises the same d2d accounting without a
    device in sight.

    Segmented transaction axis (streaming ingest): the store is a list
    of per-segment ``[cap, W_seg]`` word-column blocks sharing one slot
    space. :meth:`add_segment` appends a FRESH block holding the new
    transactions' packed item bitmaps — the existing segments are never
    repacked or re-uploaded, so an ingest's device cost is exactly the
    new segment's payload. A row's logical bitmap is the concatenation
    of its per-segment words; ``cover[h]`` records how many leading
    segments a row has real data in (base item rows are extended by
    every ``add_segment`` and always cover all segments; pushed /
    materialized rows cover the segments that existed when they were
    created, and read as zeros beyond). Sweeps may restrict themselves
    to a segment subset — the streaming engine's support-delta pass
    reads ONLY the freshly ingested segments.

    Thread-safe: workers push/release concurrently; each shard's
    mirror is touched only by that shard's dispatcher thread. Growth
    reallocates the backing store, but handed-out row views keep the
    old buffer alive and live rows are never mutated, so views stay
    content-correct.
    """

    GROW = 2                      # capacity doubling factor

    def __init__(self, n_words_: int, backing: str = "auto",
                 capacity: int = 64, n_shards: int = 1,
                 devices: Optional[Sequence] = None):
        if backing not in ARENA_BACKINGS:
            raise ValueError(
                f"arena backing must be one of {ARENA_BACKINGS}, "
                f"got {backing!r}")
        if n_shards < 1:
            raise ValueError(f"n_shards must be >= 1, got {n_shards}")
        if devices is not None and len(devices) != n_shards:
            raise ValueError(
                f"devices list ({len(devices)}) must match n_shards "
                f"({n_shards})")
        self.backing = backing
        self.n_shards = n_shards
        self.devices = list(devices) if devices is not None else None
        cap = max(capacity, 1)
        # per-segment word-column stores sharing one slot space;
        # segment 0 is the load-time database
        self._seg_words: List[int] = [n_words_]
        # owning tenant per segment (multi-tenant serving): None =
        # default/single-tenant. Purely bookkeeping — sweeps restrict
        # by explicit segment lists, so tenants isolate by construction
        self._seg_tenant: List[object] = [None]
        self._stores: List[np.ndarray] = [np.zeros((cap, n_words_),
                                                   np.uint32)]
        self._refs = np.zeros(cap, np.int32)
        # owning shard per row; -1 = replicated (pinned base rows)
        self._owner = np.full(cap, -1, np.int32)
        # leading segments a row has data in (see class docstring)
        self._cover = np.zeros(cap, np.int32)
        self.n_rows = 0               # high-water mark (rows ever used)
        self.n_base = 0               # pinned item rows [0, n_base)
        self._free: List[int] = []
        self._lock = threading.Lock()
        # live-row gauges (rows beyond the pinned base — the engines'
        # retained-bitmap memory bound)
        self.live_extra = 0
        self.peak_live_extra = 0
        # per-(shard, segment) mirror state, all dicts keyed by segment
        # id so freshly added segments default to "nothing synced". A
        # handle h < _dev_n[s][g] is resident in mirror (s, g) iff
        # h not in _invalid[s][g]; _invalid holds foreign rows never
        # fetched plus recycled slots whose mirror content went stale.
        self._dev: List[dict] = [dict() for _ in range(n_shards)]
        self._dev_n: List[dict] = [dict() for _ in range(n_shards)]
        self._invalid: List[dict] = [dict() for _ in range(n_shards)]
        # rows whose transfer to this shard was already billed as d2d
        # (by migrate) but whose payload has not physically landed in
        # the mirror yet — their eventual placement is free
        self._migrated_in: List[dict] = [dict() for _ in range(n_shards)]
        self.h2d_bytes = 0            # bitmap payload uploaded, total
        self.d2d_bytes = 0            # modeled cross-shard row traffic
        self.migrations = 0           # rows re-owned by migrate()
        self.compaction_bytes = 0     # host bytes repacked by compact()
        self.compactions = 0          # compact() calls that merged
        # observability: None = off (the engines attach a tracer;
        # mirror syncs, migrations and compactions then emit spans on
        # the calling lane)
        self.tracer = None
        # hybrid sparse representation: per-slot tag plus a
        # variable-length tid/diffset store sharing the same handle
        # space, refcounting, coverage and accounting as word-columns.
        # Sparse slots carry NO payload in the word-column stores or
        # device mirrors; their tid arrays ship per-launch (billed at
        # actual nbytes) and cross-shard reads bill d2d once per
        # residency via _note_sparse.
        self._rep = np.zeros(cap, np.int8)        # REP_* tag per slot
        self._sparse: dict = {}                   # handle -> uint32 tids
        self._anchor: dict = {}                   # diffset -> parent handle
        self._ssupport: dict = {}                 # handle -> support
        self._sparse_res: List[set] = [set() for _ in range(n_shards)]
        self.sparse_pushed = 0        # sparse rows ever created
        self.sparse_live = 0          # live sparse rows gauge
        self.sparse_bytes_live = 0    # live sparse payload bytes
        self.peak_sparse_bytes = 0
        self.densify_ops = 0          # sparse->dense conversions billed
        self.densify_bytes = 0
        self.sparsify_ops = 0         # dense->sparse conversions billed
        self.sparsify_bytes = 0

    # ---------------------------------------------------------- segments --
    @property
    def n_words(self) -> int:
        """Total logical row width (words) across all segments."""
        return sum(self._seg_words)

    @property
    def n_segments(self) -> int:
        return len(self._seg_words)

    def seg_words(self, seg: int) -> int:
        return self._seg_words[seg]

    def seg_nbytes(self, seg: int) -> int:
        """Payload bytes of one segment's pinned base rows — what an
        ingest must upload to a device mirror (and nothing more)."""
        return self.n_base * self._seg_words[seg] * 4

    def seg_tenant(self, seg: int):
        """Owning tenant of one segment (None = default)."""
        return self._seg_tenant[seg]

    def tenant_segments(self, tenant) -> Tuple[int, ...]:
        """All segment ids owned by ``tenant``, ascending — the
        segment set every one of that tenant's sweeps restricts to."""
        return tuple(g for g, t in enumerate(self._seg_tenant)
                     if t == tenant)

    def _covered(self, handle: int, seg: int) -> bool:
        return seg < int(self._cover[handle])

    def n_words_upto(self, upto: int) -> int:
        """Total row width (words) of the first ``upto`` segments."""
        return sum(self._seg_words[:upto])

    def compact(self, upto: int) -> int:
        """Merge the first ``upto`` segments into one wide word-column
        store (LSM-style). Handles, refcounts, owners and the free list
        are untouched — only the segment axis collapses, so the
        per-segment sweep loop and the jit shape zoo stop growing with
        ingest count. Segments at index >= ``upto`` shift down by
        ``upto - 1``; a row's coverage is remapped accordingly (a row
        that covered any merged segment now covers the merged block —
        its store content beyond the old coverage is already zero, so
        reads stay identical). Host repack bytes are billed to
        ``compaction_bytes``. Device mirrors are merged device-side up
        to the least-synced row count; rows beyond that re-sync (and
        re-bill) on the next :meth:`device_rows`, which for the
        streaming engine's fully-synced mirrors means no extra h2d.

        Must not run concurrently with sweeps that hold segment ids —
        the streaming engine serializes it with refresh/ingest (and
        gates it behind in-flight query sweeps). Refuses (returns 0)
        when the merge prefix spans more than one tenant: positional
        merging would fuse foreign transactions into one segment and
        every tenant-restricted segment list would go stale.
        Returns the number of segments removed (``upto - 1``)."""
        t0 = time.perf_counter() if self.tracer is not None else 0.0
        with self._lock:
            if not 2 <= upto <= len(self._seg_words):
                return 0
            if len(set(self._seg_tenant[:upto])) > 1:
                return 0
            new_w = sum(self._seg_words[:upto])
            merged = np.concatenate(self._stores[:upto], axis=1)
            self._stores[:upto] = [np.ascontiguousarray(merged)]
            self._seg_words[:upto] = [new_w]
            self._seg_tenant[:upto] = [self._seg_tenant[0]]
            self.compaction_bytes += self.n_rows * new_w * 4
            self.compactions += 1
            # cover remap: >= upto -> minus (upto-1); in (0, upto) -> 1
            cov = self._cover
            self._cover = np.where(
                cov >= upto, cov - (upto - 1),
                np.minimum(cov, 1)).astype(np.int32)
            for s in range(self.n_shards):
                self._merge_mirror(s, upto)
            if self.tracer is not None:
                self.tracer.span(
                    "compaction", t0, cat="arena",
                    args={"merged": upto,
                          "bytes": self.n_rows * new_w * 4})
            return upto - 1

    def _merge_mirror(self, shard: int, upto: int) -> None:
        # caller holds self._lock
        dn, dev = self._dev_n[shard], self._dev[shard]
        inv, mig = self._invalid[shard], self._migrated_in[shard]

        def _remap(d: dict, merged_val) -> dict:
            out = {0: merged_val}
            for g in sorted(k for k in d if k >= upto):
                out[g - (upto - 1)] = d[g]
            return out
        nmin = min(dn.get(g, 0) for g in range(upto))
        self._dev_n[shard] = _remap(dn, nmin)
        # a row stale in ANY merged segment is stale in the merged block
        inv_m = set()
        for g in range(upto):
            inv_m |= {h for h in inv.get(g, ()) if h < nmin}
        self._invalid[shard] = _remap(inv, inv_m)
        mig_m = set()
        for g in range(upto):
            mig_m |= mig.get(g, set())
        self._migrated_in[shard] = _remap(mig, mig_m)
        if not self.device_enabled:
            # host-only backing: residency bookkeeping merged above,
            # no physical mirrors to touch
            self._dev[shard] = {}
            return
        blocks = [dev.get(g) for g in range(upto)]
        if nmin > 0 and all(b is not None for b in blocks):
            import jax.numpy as jnp
            new_dev = _remap(dev, _pad_rows(jnp.concatenate(
                [b[:nmin] for b in blocks], axis=1),
                pow2(nmin, lo=MIRROR_MIN_ROWS)))
        else:
            # nothing fully mirrored yet: the merged block re-syncs
            # from scratch on the next device_rows
            self._dev_n[shard][0] = 0
            self._invalid[shard][0] = set()
            new_dev = _remap(dev, None)
            del new_dev[0]
        self._dev[shard] = new_dev

    def add_segment(self, base_bitmaps: np.ndarray,
                    tenant=None) -> int:
        """Append a fresh transaction segment: ``base_bitmaps`` is the
        ``[n_base, W_seg]`` packed item bitmaps of the NEW transactions
        only. Existing segments are untouched — no repack, no
        re-upload; with eager ("jax") backing the new segment's base
        payload is mirrored immediately and its bytes (exactly
        :meth:`seg_nbytes`) are the entire h2d bill. ``tenant`` tags
        the segment's owner for multi-tenant serving (None = default).
        Returns the new segment id."""
        bm = np.ascontiguousarray(base_bitmaps, dtype=np.uint32)
        if bm.ndim != 2 or bm.shape[0] != self.n_base:
            raise ValueError(
                f"segment bitmaps must be [n_base={self.n_base}, W_seg], "
                f"got {bm.shape}")
        with self._lock:
            w = bm.shape[1]
            seg = len(self._seg_words)
            cap = self._refs.shape[0]
            store = np.zeros((cap, w), np.uint32)
            store[:self.n_base] = bm
            self._seg_words.append(w)
            self._seg_tenant.append(tenant)
            self._stores.append(store)
            # base item rows now extend into the new segment; live
            # non-base rows keep their creation-time coverage and read
            # as zeros there
            self._cover[:self.n_base] = seg + 1
        if self.backing == "jax":
            for s in range(self.n_shards):
                self.device_rows(s, segment=seg)   # eager, W_seg only
        return seg

    # ------------------------------------------------------------- load --
    @classmethod
    def from_bitmaps(cls, bitmaps: np.ndarray, backing: str = "auto",
                     n_shards: int = 1, devices: Optional[Sequence] = None
                     ) -> "BitmapArena":
        """Load packed item bitmaps as the pinned base rows (handle ==
        item id). One copy, once — every later sweep references rows by
        handle instead of re-marshalling them."""
        n, w = bitmaps.shape
        arena = cls(w, backing, capacity=max(64, 2 * n),
                    n_shards=n_shards, devices=devices)
        arena._stores[0][:n] = bitmaps
        arena._refs[:n] = 1
        arena._cover[:n] = 1
        arena.n_rows = arena.n_base = n
        if backing == "jax":
            for s in range(arena.n_shards):
                arena.device_rows(s)  # eager initial (replicated) upload
        return arena

    @classmethod
    def from_database(cls, db: Sequence[Sequence[int]], n_items: int,
                      backing: str = "auto") -> "BitmapArena":
        """pack_database straight into the arena (no intermediate)."""
        return cls.from_bitmaps(pack_database(db, n_items), backing)

    # ------------------------------------------------------ row lifecycle --
    def _alloc_slot(self) -> int:
        # caller holds self._lock
        if self._free:
            slot = self._free.pop()
            for s in range(self.n_shards):
                dn = self._dev_n[s]
                for g in range(len(self._seg_words)):
                    if slot < dn.get(g, 0):
                        # mirror content stale in every segment block
                        self._invalid[s].setdefault(g, set()).add(slot)
                    mig = self._migrated_in[s].get(g)
                    if mig:
                        mig.discard(slot)  # old row is gone
            return slot
        if self.n_rows == self._refs.shape[0]:
            cap = self.GROW * self._refs.shape[0]
            for g, old in enumerate(self._stores):
                store = np.zeros((cap, self._seg_words[g]), np.uint32)
                store[:self.n_rows] = old[:self.n_rows]
                self._stores[g] = store
            refs = np.zeros(cap, np.int32)
            refs[:self.n_rows] = self._refs[:self.n_rows]
            owner = np.full(cap, -1, np.int32)
            owner[:self.n_rows] = self._owner[:self.n_rows]
            cover = np.zeros(cap, np.int32)
            cover[:self.n_rows] = self._cover[:self.n_rows]
            rep = np.zeros(cap, np.int8)
            rep[:self.n_rows] = self._rep[:self.n_rows]
            self._refs, self._owner, self._cover = refs, owner, cover
            self._rep = rep
        slot = self.n_rows
        self.n_rows += 1
        return slot

    def _bump_live(self) -> None:
        self.live_extra += 1
        self.peak_live_extra = max(self.peak_live_extra, self.live_extra)

    def push(self, row: np.ndarray, shard: int = 0,
             cover: Optional[int] = None) -> int:
        """Append (or recycle a slot for) one bitmap row; refcount 1.
        ``shard`` records the owning shard in sharded mode. Without
        ``cover``, ``row`` is the full-width concatenation over all
        segments; with ``cover=c``, ``row`` spans only the first ``c``
        segments (:meth:`n_words_upto`) and the slot is zeroed beyond —
        an overlapped refresh pushes rows at its generation boundary
        even after an ingest has appended newer segments."""
        with self._lock:
            slot = self._alloc_slot()
            cov = len(self._seg_words) if cover is None else cover
            off = 0
            for g, w in enumerate(self._seg_words):
                if g < cov:
                    self._stores[g][slot] = row[off:off + w]
                    off += w
                else:
                    self._stores[g][slot] = 0
            self._refs[slot] = 1
            self._owner[slot] = shard
            self._cover[slot] = cov
            self._rep[slot] = REP_BITMAP
            self._bump_live()
            return slot

    def materialize(self, prefix_handle: int, ext_handle: int,
                    shard: int = 0) -> int:
        """``row(prefix) ∧ row(ext)`` appended in place — the depth-first
        parent→child handoff, with no floating temporary. The new row is
        owned by ``shard`` (the materializing worker's device) and
        covers the segments both parents cover (beyond that it is
        zeroed, so recycled-slot garbage can never leak into a read)."""
        with self._lock:
            slot = self._alloc_slot()
            cov = min(int(self._cover[prefix_handle]),
                      int(self._cover[ext_handle]))
            for g, store in enumerate(self._stores):
                if g < cov:
                    np.bitwise_and(store[prefix_handle],
                                   store[ext_handle],
                                   out=store[slot])
                else:
                    store[slot] = 0
            self._refs[slot] = 1
            self._owner[slot] = shard
            self._cover[slot] = cov
            self._rep[slot] = REP_BITMAP
            self._bump_live()
            return slot

    # ------------------------------------------------- sparse lifecycle --
    def _push_sparse(self, rep: int, tids: np.ndarray, support: int,
                     shard: int, cover: Optional[int],
                     anchor: Optional[int] = None) -> int:
        t = np.ascontiguousarray(tids, dtype=np.uint32)
        with self._lock:
            slot = self._alloc_slot()
            self._refs[slot] = 1
            self._owner[slot] = shard
            self._cover[slot] = (len(self._seg_words) if cover is None
                                 else cover)
            self._rep[slot] = rep
            self._sparse[slot] = t
            self._ssupport[slot] = int(support)
            if anchor is not None:
                self._anchor[slot] = anchor
                if anchor >= self.n_base:     # pin the diffset's parent
                    self._refs[anchor] += 1
            self.sparse_pushed += 1
            self.sparse_live += 1
            self.sparse_bytes_live += t.nbytes
            self.peak_sparse_bytes = max(self.peak_sparse_bytes,
                                         self.sparse_bytes_live)
            self._bump_live()
            return slot

    def push_tids(self, tids: np.ndarray, shard: int = 0,
                  cover: Optional[int] = None) -> int:
        """Append one sparse row as a sorted uint32 tid-list; refcount 1.
        Shares the handle space (and refcounting / coverage / owner
        bookkeeping) with word-column rows, but carries no word-column
        payload — the slot's store words are dead and device mirrors
        keep it zeroed."""
        return self._push_sparse(REP_TIDLIST, tids, len(tids), shard,
                                 cover)

    def push_diffset(self, diff: np.ndarray, anchor: int, support: int,
                     shard: int = 0, cover: Optional[int] = None) -> int:
        """Append one dEclat diffset row: ``diff`` holds the tids of the
        *anchor* (parent prefix) row NOT in this row, so this row's tid
        set is ``tids(anchor) \\ diff`` and its support is
        ``support(anchor) - len(diff)`` (stored explicitly as
        ``support``). The anchor is retained until this row is
        released — releasing a diffset cascades one release to its
        anchor."""
        return self._push_sparse(REP_DIFFSET, diff, support, shard,
                                 cover, anchor=anchor)

    def sparsify_push(self, row: np.ndarray, shard: int = 0,
                      cover: Optional[int] = None) -> int:
        """Scan a dense word-row into a tid-list row (billed sparsify
        conversion) — the prefix cache's path when the density model
        says a freshly built intersection should live sparse."""
        t = bitmap_to_tids(row)
        with self._lock:
            self.sparsify_ops += 1
            self.sparsify_bytes += row.nbytes
        return self.push_tids(t, shard=shard, cover=cover)

    def rep_of(self, handle: int) -> int:
        """REP_BITMAP / REP_TIDLIST / REP_DIFFSET tag of a row."""
        return int(self._rep[handle])

    def rep_name(self, handle: int) -> str:
        return REP_NAMES[self.rep_of(handle)]

    def cover_of(self, handle: int) -> int:
        return int(self._cover[handle])

    def tids_of(self, handle: int) -> np.ndarray:
        """Raw sparse payload of a tid-list or diffset row (for a
        diffset this is the *difference*, not the tid set — see
        :meth:`resolve_tids`)."""
        return self._sparse[handle]

    def anchor_of(self, handle: int) -> Optional[int]:
        return self._anchor.get(handle)

    def sparse_support(self, handle: int) -> int:
        """Stored support of a sparse row (len(tids) for tid-lists,
        anchor support minus difference size for diffsets)."""
        return self._ssupport[handle]

    def resolve_tids(self, handle: int) -> np.ndarray:
        """Explicit sorted tid set of ANY row. Tid-lists are returned
        as-is; diffsets reconstruct ``tids(anchor) \\ diff`` (walking
        the anchor chain); bitmap rows are scanned — billed as a
        sparsify conversion, since it turns W words into a tid array."""
        rep = int(self._rep[handle])
        if rep == REP_TIDLIST:
            return self._sparse[handle]
        if rep == REP_DIFFSET:
            parent = self.resolve_tids(self._anchor[handle])
            return sorted_difference(parent, self._sparse[handle])
        tids = bitmap_to_tids(self.row(handle))
        with self._lock:
            self.sparsify_ops += 1
            self.sparsify_bytes += self.n_words * 4
        return tids

    def densify(self, handle: int) -> np.ndarray:
        """Full-width dense word-column of ANY row; for sparse rows
        this is a billed densify conversion (the transient bitmap a
        dense-only consumer forces)."""
        rep = int(self._rep[handle])
        if rep == REP_BITMAP:
            return self.row(handle)
        if rep == REP_TIDLIST:
            out = tids_to_bitmap(self._sparse[handle], self.n_words)
        else:
            anchor = self.densify(self._anchor[handle])
            out = anchor.copy()
            d = self._sparse[handle]
            if len(d):
                np.bitwise_and.at(
                    out, d >> np.uint32(5),
                    ~(np.uint32(1) << (d & np.uint32(31))))
        with self._lock:
            self.densify_ops += 1
            self.densify_bytes += self.n_words * 4
        return out

    def seg_tid_range(self, seg: int) -> Tuple[int, int]:
        """[lo, hi) global tid bounds of one segment — the searchsorted
        window a segment-restricted sparse sweep filters tids with."""
        lo = 32 * sum(self._seg_words[:seg])
        return lo, lo + 32 * self._seg_words[seg]

    def gather_bits_rows(self, tids: np.ndarray,
                         handles: Sequence[int]) -> np.ndarray:
        """[len(handles), len(tids)] bool: bit test of each handle's
        DENSE row at each tid — the class task's batched child carve.
        One ``np.ix_`` gather per segment serves every row at once;
        one gather per child pays ~10x numpy call overhead for the
        same reads."""
        out = np.zeros((len(handles), len(tids)), bool)
        if not len(tids) or not len(handles):
            return out
        hs = [int(h) for h in handles]
        for g in range(self.n_segments):
            if not self.seg_words(g):
                continue
            lo, hi = self.seg_tid_range(g)
            i0, i1 = np.searchsorted(tids, [lo, hi])
            if i0 == i1:
                continue
            t = tids[i0:i1].astype(np.int64) - lo
            w = self.seg_view(g)[np.ix_(hs, t >> 5)]
            out[:, i0:i1] = (w >> (t & 31).astype(np.uint32)[None, :]
                             ) & np.uint32(1)
        return out

    def owner_of(self, handle: int) -> int:
        """Owning shard of a row; -1 for replicated (pinned base) rows."""
        if handle < self.n_base:
            return -1
        return int(self._owner[handle])

    def migrate(self, handles: Sequence[int], dst: int) -> int:
        """Re-owner rows onto shard ``dst`` — the explicit transfer
        behind a cross-device bucket steal. A row's payload is billed
        to ``d2d_bytes`` exactly once per crossing: a row the
        destination already fetched (resident in its mirror) flips
        owner for free, and a billed-here row's later physical landing
        in the destination mirror costs no additional h2d/d2d. Pinned
        base rows are replicated everywhere and never migrate. Returns
        the number of rows moved."""
        moved = 0
        tr = self.tracer
        t0 = time.perf_counter() if tr is not None else 0.0
        d2d0 = self.d2d_bytes
        with self._lock:
            dn = self._dev_n[dst]
            inv = self._invalid[dst]
            mig = self._migrated_in[dst]
            for h in handles:
                if h < self.n_base:
                    continue
                if int(self._owner[h]) == dst:
                    continue
                self._owner[h] = dst
                if self._rep[h] != REP_BITMAP:
                    # sparse payload crosses once, at its actual size
                    if h not in self._sparse_res[dst]:
                        self.d2d_bytes += self._sparse[h].nbytes
                        self._sparse_res[dst].add(h)
                else:
                    for g in range(int(self._cover[h])):
                        wb = self._seg_words[g] * 4
                        if not wb:
                            continue
                        resident = (h < dn.get(g, 0)
                                    and h not in inv.get(g, ()))
                        if not resident:
                            self.d2d_bytes += wb
                            mig.setdefault(g, set()).add(h)
                self.migrations += 1
                moved += 1
        if tr is not None and moved:
            tr.span("d2d-migrate", t0, cat="arena",
                    args={"rows": moved, "dst": dst,
                          "bytes": self.d2d_bytes - d2d0})
        return moved

    def retain(self, handle: int) -> None:
        if handle < self.n_base:
            return                    # pinned item row
        with self._lock:
            self._refs[handle] += 1

    def release(self, handle: int) -> None:
        """Drop one reference; a freed diffset row cascades one release
        to its anchor (the parent row it pinned at push time), walking
        the chain iteratively outside the lock."""
        h: Optional[int] = handle
        while h is not None:
            h = self._release_one(h)

    def _release_one(self, handle: int) -> Optional[int]:
        if handle < self.n_base:
            return None               # pinned item row
        with self._lock:
            self._refs[handle] -= 1
            if self._refs[handle] == 0:
                self._free.append(handle)
                self.live_extra -= 1
                if self._rep[handle] != REP_BITMAP:
                    t = self._sparse.pop(handle)
                    self.sparse_live -= 1
                    self.sparse_bytes_live -= t.nbytes
                    self._ssupport.pop(handle, None)
                    self._rep[handle] = REP_BITMAP
                    for s in range(self.n_shards):
                        self._sparse_res[s].discard(handle)
                    return self._anchor.pop(handle, None)
            elif self._refs[handle] < 0:   # pragma: no cover - API misuse
                raise RuntimeError(f"double release of handle {handle}")
        return None

    def refcount(self, handle: int) -> int:
        return int(self._refs[handle])

    # ------------------------------------------------------------ access --
    def row(self, handle: int) -> np.ndarray:
        """[n_words] view of one live row. Zero-copy for single-segment
        arenas (the non-streaming hot path); for segmented arenas this
        is a concatenated copy, zero-filled past the row's coverage.
        Sparse rows densify on the fly (billed — see :meth:`densify`),
        so dense-only consumers stay correct on any handle."""
        if self._rep[handle] != REP_BITMAP:
            return self.densify(handle)
        if len(self._stores) == 1:
            return self._stores[0][handle]
        cov = int(self._cover[handle])
        return np.concatenate(
            [store[handle] if g < cov
             else np.zeros(self._seg_words[g], np.uint32)
             for g, store in enumerate(self._stores)])

    def row_upto(self, handle: int, upto: int) -> np.ndarray:
        """Row words over the first ``upto`` segments only, zero-filled
        past the row's coverage — the boundary-consistent read for an
        overlapped refresh (segments appended after the boundary are
        invisible, so two reads of the same handle agree in width)."""
        if self._rep[handle] != REP_BITMAP:
            return self.densify(handle)[:self.n_words_upto(upto)]
        if upto == 1:
            return self._stores[0][handle]
        cov = int(self._cover[handle])
        return np.concatenate(
            [store[handle] if g < cov
             else np.zeros(self._seg_words[g], np.uint32)
             for g, store in enumerate(self._stores[:upto])])

    def seg_row(self, seg: int, handle: int) -> np.ndarray:
        """Zero-copy [W_seg] view of one row's words in one segment."""
        return self._stores[seg][handle]

    def seg_view(self, seg: int) -> np.ndarray:
        """Zero-copy [n_rows, W_seg] view of one segment's store (numpy
        backend sweeps index this directly)."""
        return self._stores[seg][:self.n_rows]

    def rows_view(self) -> np.ndarray:
        """[n_rows, n_words] view of the whole store — zero-copy for
        single-segment arenas, a concatenated copy otherwise."""
        if len(self._stores) == 1:
            return self._stores[0][:self.n_rows]
        return np.concatenate([s[:self.n_rows] for s in self._stores],
                              axis=1)

    def seg_gather(self, seg: int, handles: Sequence[int]) -> np.ndarray:
        """One segment's rows for ``handles`` — a zero-copy slice view
        when the handles are contiguous (item ranges often are), a
        fancy-index copy otherwise."""
        store = self._stores[seg]
        h0 = handles[0]
        n = len(handles)
        if all(handles[i] == h0 + i for i in range(1, n)):
            return store[h0:h0 + n]
        return store[list(handles)]

    def gather(self, handles: Sequence[int]) -> np.ndarray:
        """Full-width rows for ``handles`` (see :meth:`seg_gather`)."""
        if len(self._stores) == 1:
            return self.seg_gather(0, handles)
        return np.concatenate(
            [self.seg_gather(g, handles)
             for g in range(len(self._stores))], axis=1)

    @property
    def live_bytes_extra(self) -> int:
        """Retained non-base payload: dense rows at full row width,
        sparse rows at their actual tid-array size."""
        return ((self.live_extra - self.sparse_live) * self.n_words * 4
                + self.sparse_bytes_live)

    @property
    def peak_bytes_extra(self) -> int:
        return self.peak_live_extra * self.n_words * 4

    @property
    def nbytes_base(self) -> int:
        return self.n_base * self.n_words * 4

    # ------------------------------------------------------------ device --
    @property
    def device_enabled(self) -> bool:
        return self.backing != "numpy"

    def _sync_plan(self, shard: int, seg: int,
                   needed: Optional[Sequence[int]]
                   ) -> Tuple[int, int, List[int], int,
                              List[int], List[int]]:
        """Advance mirror (shard, seg) bookkeeping to ``n_rows`` and
        classify work.

        Caller holds the lock. Returns ``(lo, n, fresh_owned, fresh_h2d,
        reupload, fetch)``: rows [lo, n) are new to this mirror (of
        which ``fresh_owned`` — owned-by-shard or replicated base, live,
        and covering this segment — carry payload, ``fresh_h2d`` of
        them at h2d cost; the rest enter ``_invalid`` as unfetched
        foreign/stale rows); ``reupload`` are owned rows whose mirror
        content went stale (recycled slots), billed h2d; ``fetch`` are
        rows placed without an h2d bill — foreign rows ``needed`` now
        (their payload is counted in ``d2d_bytes`` here, once per
        residency; a later recycle invalidates and recounts),
        migrated-in rows whose d2d was prepaid by :meth:`migrate`, and
        dead/uncovered rows whose placement carries no real payload."""
        n = self.n_rows
        lo = self._dev_n[shard].get(seg, 0)
        inv = self._invalid[shard].setdefault(seg, set())
        mig = self._migrated_in[shard].setdefault(seg, set())
        fresh_owned: List[int] = []
        fresh_h2d = 0

        def _live(h: int) -> bool:
            return h < self.n_base or int(self._refs[h]) > 0

        def _owned(h: int) -> bool:
            return h < self.n_base or int(self._owner[h]) in (-1, shard)

        for h in range(lo, n):
            if (_owned(h) and _live(h) and self._covered(h, seg)
                    and self._rep[h] == REP_BITMAP):
                fresh_owned.append(h)
                if h in mig:          # transfer billed at migrate time
                    mig.discard(h)
                else:
                    fresh_h2d += 1
            else:
                inv.add(h)
        self._dev_n[shard][seg] = n
        reupload: List[int] = []
        fetch: List[int] = []
        row_bytes = self._seg_words[seg] * 4

        def _classify(h: int) -> None:
            inv.discard(h)
            if (not (_live(h) and self._covered(h, seg))
                    or self._rep[h] != REP_BITMAP):
                # no word-column payload: dead/uncovered rows, and
                # sparse rows (their tid payload ships per-launch and
                # bills via _note_sparse / count_h2d instead)
                fetch.append(h)
            elif _owned(h):
                if h in mig:          # prepaid migration landing
                    mig.discard(h)
                    fetch.append(h)
                else:
                    reupload.append(h)
            else:
                fetch.append(h)
                self.d2d_bytes += row_bytes

        if needed is not None:
            for h in set(needed):
                if h in inv:
                    _classify(h)
        else:
            # no access set: refresh every stale owned row (the
            # pre-sharding "dirty" semantics); foreign rows wait for a
            # needed-based sync
            for h in sorted(inv):
                if _owned(h):
                    _classify(h)
        return lo, n, fresh_owned, fresh_h2d, reupload, fetch

    def note_access(self, shard: int, handles: Sequence[int],
                    segments: Optional[Sequence[int]] = None) -> None:
        """Residency/d2d bookkeeping for host-only sweeps: a sweep on
        ``shard`` reading a row owned elsewhere counts one cross-shard
        fetch (``d2d_bytes``), after which the row is resident there
        until its slot recycles. ``segments`` restricts the bill to the
        segment subset actually swept (a streaming delta pass reads —
        and ships — only the fresh segments). Device-backed arenas get
        the same accounting (plus the physical mirror ops) via
        :meth:`device_rows`."""
        if self.n_shards == 1:
            return
        with self._lock:
            self._note_sparse(shard, handles)
            segs = (segments if segments is not None
                    else range(len(self._seg_words)))
            for g in segs:
                self._sync_plan(shard, g, handles)

    def _note_sparse(self, shard: int, handles: Sequence[int]) -> None:
        """Cross-shard residency billing for sparse rows (caller holds
        the lock): a foreign tid/diffset payload read by ``shard`` is
        billed to d2d once per residency, at its actual nbytes — the
        sparse analogue of _sync_plan's per-row word-column bill."""
        res = self._sparse_res[shard]
        for h in set(handles):
            if (self._rep[h] != REP_BITMAP and h not in res
                    and int(self._owner[h]) not in (-1, shard)):
                t = self._sparse.get(h)
                if t is not None:
                    self.d2d_bytes += t.nbytes
                    res.add(h)

    def device_rows(self, shard: int = 0,
                    needed: Optional[Sequence[int]] = None,
                    segment: int = 0):
        """jax mirror of one segment's ``seg_view()`` for one shard,
        synced incrementally (only that shard's dispatcher thread calls
        this). Returns None for host-only ("numpy") backing.

        The mirror is a ``[cap, W_seg]`` buffer: rows ``[0, n_rows)``
        mirror the store and the rest are zero padding, ``cap`` a power
        of two (at least ``MIRROR_MIN_ROWS``). Its shape changes only
        when the capacity doubles, so the gathers and updates that read
        it compile a handful of times per run, not once per appended
        row — on a chip, a per-flush recompile would cost more than the
        sweep.

        ``needed`` lists the handles the caller is about to gather:
        foreign rows among them are fetched into this shard's mirror
        and counted in ``d2d_bytes``. Without ``needed`` (single-shard
        callers), every stale owned row is refreshed.

        "Incremental" bounds host→device PAYLOAD (the ``h2d_bytes``
        gauge): only changed rows cross the bus, and only this
        segment's words — an ingest that appended segment g uploads
        ``seg_nbytes(g)``, never the older segments (fresh rows pad to
        a power-of-two count with zero rows, which are not billed). The
        functional update still rebuilds the mirror buffer on device,
        an O(cap) device-to-device copy per sync with fresh rows —
        acceptable while mirrors are MBs; a donated buffer would remove
        it when arenas reach device memory scale."""
        if not self.device_enabled:
            if needed is not None:
                self.note_access(shard, needed, segments=(segment,))
            return None
        tr = self.tracer
        t_sync = time.perf_counter() if tr is not None else 0.0
        with self._lock:
            if needed is not None:
                self._note_sparse(shard, needed)
            lo, n, fresh_owned, fresh_h2d, reupload, fetch = \
                self._sync_plan(shard, segment, needed)
            store = self._stores[segment]
            fresh = None
            if n > lo:
                fresh = np.zeros((pow2(n - lo), store.shape[1]),
                                 np.uint32)
                fresh[:n - lo] = store[lo:n]
                owned = set(fresh_owned)
                for j, h in enumerate(range(lo, n)):
                    if h not in owned:
                        fresh[j] = 0          # unfetched foreign row
            re_rows = store[reupload].copy() if reupload else None
            fe_rows = store[fetch].copy() if fetch else None
            if fe_rows is not None:
                for j, h in enumerate(fetch):
                    if self._rep[h] != REP_BITMAP:
                        fe_rows[j] = 0    # sparse slot: store words dead
        import jax
        import jax.numpy as jnp

        device = self.devices[shard] if self.devices is not None else None

        def _place(arr):
            # straight from host memory to the shard's device: staging
            # through jnp.asarray would land on the default device
            # first and route every other shard's upload through it
            if device is not None:
                return jax.device_put(arr, device)
            return jnp.asarray(arr)

        write_rows, set_rows = _mirror_ops()
        row_bytes = self._seg_words[segment] * 4
        h2d_delta = 0
        dev = self._dev[shard].get(segment)
        cap = pow2(max(n, lo + (len(fresh) if fresh is not None else 0)),
                    lo=MIRROR_MIN_ROWS)
        if dev is None:
            dev = jnp.zeros((cap, store.shape[1]), jnp.uint32,
                            device=device)
        elif dev.shape[0] < cap:
            dev = _pad_rows(dev, cap)
        if fresh is not None:
            dev = write_rows(dev, _place(fresh), np.int32(lo))
            h2d_delta += fresh_h2d * row_bytes
        if re_rows is not None:
            dev = set_rows(dev, *map(_place, _pow2_set(reupload, re_rows)))
            h2d_delta += len(reupload) * row_bytes
        if fe_rows is not None:
            # payload already billed (d2d at fetch/migrate time) or
            # dead/uncovered (no real payload)
            dev = set_rows(dev, *map(_place, _pow2_set(fetch, fe_rows)))
        self._dev[shard][segment] = dev
        if h2d_delta:
            self.count_h2d(h2d_delta)
            if tr is not None:
                # only syncs that actually moved payload get a span —
                # the steady-state no-op sync stays invisible
                tr.span("h2d-sync", t_sync, cat="arena",
                        args={"shard": shard, "segment": segment,
                              "bytes": h2d_delta})
        return dev

    def count_h2d(self, nbytes: int) -> None:
        """Backends add per-batch host→device payload here (the
        host-gather fallback path). Locked: with one dispatcher thread
        per shard, concurrent flushes update the shared gauge."""
        with self._lock:
            self.h2d_bytes += nbytes

    def __repr__(self) -> str:   # pragma: no cover - debugging aid
        return (f"<BitmapArena rows={self.n_rows} base={self.n_base} "
                f"live_extra={self.live_extra} backing={self.backing} "
                f"shards={self.n_shards} segments={self.n_segments}>")
