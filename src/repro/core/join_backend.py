"""Batched join backends + the sweep dispatcher.

A *bucket sweep* is the paper's per-task TID join restructured at bucket
granularity: one (k-1)-prefix bitmap against the bucket's E extension
bitmaps, producing E support counts in one vectorized call. The old
design gave every scheduler worker its own single-prefix ``sweep`` call
and serialized all JAX dispatch behind a module-global lock, so the
"TPU fast path" was transfer-bound (every sweep re-uploaded its
extension bitmaps host→device) and single-dispatch.

This layer inverts that around two pieces:

  ``BitmapArena`` (repro.core.tidlist)  every bitmap lives in one
      refcounted, append-only row store with integer handles; the
      device mirror is synced incrementally, so repeated sweeps cost
      ~one initial upload instead of one upload per sweep.
  ``SweepDispatcher``  workers enqueue handle-based ``SweepRequest``s
      and block on a future; one dedicated dispatcher thread coalesces
      pending requests into a padded batch and launches ONE
      multi-prefix ``bitmap_join_many`` kernel for all of them. Only
      the dispatcher thread ever touches JAX — no lock exists at all.
      Every sweep of every granularity, on every backend, is such a
      request: no engine path calls a backend itself.

Backends implement the same batched API:

  numpy             per-request ``tidlist.support_counts`` over
                    zero-copy arena row views — the CPU tier-1 path,
                    called from the same dispatcher flushes as the
                    kernels, so tier-1 runs the chip's control flow.
  pallas-interpret  ``bitmap_join_many`` under the Pallas interpreter —
                    bit-exact with the TPU kernel, runnable anywhere.
  pallas-jit        the compiled kernel — TPU only; each request's
                    prefix tile stays VMEM-resident across its
                    extension sweep while B requests share the launch.
"""
from __future__ import annotations

import functools
import threading
import time
from concurrent.futures import Future
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.core import tidlist
from repro.core.tidlist import BitmapArena
from repro.core.tidlist import pow2 as _pow2
from repro.obs import region
from repro.obs import schema as obs_schema

# Dispatcher defaults: how many requests one kernel launch may carry,
# and how long (µs) the dispatcher waits for stragglers to coalesce
# before flushing a partial batch.
MAX_BATCH = 32
FLUSH_US = 200.0
# Straggler cap once a QUERY-class (priority) request is pending: a
# serving query still coalesces into whatever flush is forming, but
# it will not sit out the full mining straggler window — the p99 a
# lone query pays is bounded by this, not FLUSH_US.
QUERY_FLUSH_US = 50.0


@dataclass
class SweepRequest:
    """One bucket sweep, by handle: counts[i] = |row(prefix) ∧ row(ext_i)|.

    ``prefix_handle`` is either one arena handle (a cached/materialized
    prefix bitmap) or a TUPLE of handles whose rows are AND-reduced per
    segment inside the backend — the streaming delta path sweeps
    base-item tuples this way, so a 2-word delta sweep never pays a
    full-width prefix intersection build just to read 2 words of it.

    ``shard`` is the device shard the request executes on — stamped by
    the (per-device) dispatcher that accepted it, so backends know
    which arena mirror to gather from. ``segments`` restricts the join
    to a subset of the arena's transaction segments (None = all): the
    streaming engine's support-delta sweeps read ONLY the freshly
    ingested segments, so a small ingest costs a small sweep.

    Hybrid representation: when ``prefix_handle`` is a SPARSE arena row
    (tid-list or diffset), the backend runs the gather-intersect path
    instead of AND+popcount and the counts are ``|payload ∩ ext_i|``
    over the raw sparse payload — for a tid-list that IS the support,
    for a diffset it is the subtrahend the engine turns into
    ``parent_support - count``. One flush may mix representations; the
    backend partitions per launch. Tuple prefixes are always dense
    (streaming sweeps AND base item rows).

    ``priority`` marks a QUERY-class request (the serving layer's
    unknown-itemset sweeps): it jumps to the front of the pending
    queue (guaranteed into the next flush) and caps the dispatcher's
    straggler wait at ``QUERY_FLUSH_US`` — queries coalesce with
    candidate sweeps but never wait out the full mining window.
    ``t_submit`` (``perf_counter``) starts the request's queue wait,
    which ends when the flush that carries it starts.

    ``desc`` is the request's portable descriptor for multi-host runs:
    the prefix as base ITEM ids, meaningful on any host's arena slice.
    Arena handles are host-local (a cached prefix row exists only on
    the host that built it), so cluster mode's cross-host reduction
    re-evaluates the flush from descriptors — call sites sweeping a
    derived handle must pass the prefix itemset here. Tuple prefixes
    and base-row handles self-describe; single-host runs ignore it."""
    prefix_handle: "int | Tuple[int, ...]"
    ext_handles: Tuple[int, ...]
    shard: int = 0
    segments: Optional[Tuple[int, ...]] = None
    priority: bool = False
    desc: Optional[Tuple[int, ...]] = None
    future: Future = field(default_factory=Future)
    t_submit: float = field(default_factory=time.perf_counter)

    @property
    def prefix_handles(self) -> Tuple[int, ...]:
        p = self.prefix_handle
        return p if isinstance(p, tuple) else (p,)

    def segment_ids(self, arena: BitmapArena) -> Tuple[int, ...]:
        if self.segments is not None:
            return self.segments
        return tuple(range(arena.n_segments))

    def is_sparse(self, arena: BitmapArena) -> bool:
        """True when the prefix row is a tid-list/diffset (gather-
        intersect path); tuple prefixes AND base rows, always dense."""
        p = self.prefix_handle
        return (not isinstance(p, tuple)
                and arena.rep_of(p) != tidlist.REP_BITMAP)


class _DispatchThread(threading.local):
    """What a backend reads on the calling dispatcher thread: that
    dispatcher's per-kernel counters (``SweepDispatcher.work``) and its
    tracer, for the ``flush.*`` spans on its lane. Elsewhere (a
    backend called directly) both stay None, and nothing is counted or
    traced. Per thread, so one shared backend serves every shard's
    dispatcher and ``sweep_many(arena, requests)`` keeps the
    two-argument form that backend subclasses and wrappers override."""

    work: Optional[Dict[str, int]] = None
    tracer = None


_dispatch_thread = _DispatchThread()


class JoinBackend:
    """Batched executor: ``sweep_many(arena, requests)`` returns one
    int64 counts array per request (ragged — each sized to the
    request's own extension count)."""

    name: str = "base"

    def sweep_many(self, arena: BitmapArena,
                   requests: Sequence[SweepRequest]) -> List[np.ndarray]:
        raise NotImplementedError

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return f"<JoinBackend {self.name}>"


class NumpyBackend(JoinBackend):
    """Zero-copy arena row views into the fused AND+popcount ufunc
    pass, batched: a flush's requests are grouped per segment and
    binned by padded shape, then each bin executes as a handful of
    wide numpy passes (index gather → AND-reduce → fused popcount)
    instead of ~10 tiny numpy calls per request. On the streaming
    delta path the per-request work is a 2-word AND — Python call
    overhead dwarfed the arithmetic until the batch was vectorized.
    Called only from dispatcher flushes, like the kernels, so CPU
    tier-1 tests exercise the identical request/batch/flush
    machinery. In sharded mode the batch's row accesses are booked
    against the requests' shard first (cross-shard reads land in the
    arena's ``d2d_bytes`` gauge). On a dispatcher thread the passes are
    the flush's one ``flush.launch`` span; they run synchronously, so
    the flush has no ``flush.wait``."""

    name = "numpy"
    # bound on a bin pass's [B, E, W] AND temporary (slices B)
    PASS_BYTES = 4 << 20

    def sweep_many(self, arena, requests):
        with region(_dispatch_thread.tracer, "flush.launch",
                    cat="flush") as args:
            if args is not None:
                args["kernel"] = "numpy"
            return self._sweep(arena, requests)

    def _sweep(self, arena, requests):
        if arena.n_shards > 1:
            # booked per request: batches are shard-homogeneous today
            # (each dispatcher stamps its own shard), but a mixed batch
            # must not misattribute traffic to requests[0]'s shard —
            # and a delta sweep bills only the segments it reads
            for r in requests:
                arena.note_access(r.shard,
                                  (*r.prefix_handles, *r.ext_handles),
                                  segments=r.segments)
        totals: List[Optional[np.ndarray]] = [None] * len(requests)
        by_seg: Dict[int, List[int]] = {}
        for i, r in enumerate(requests):
            if r.is_sparse(arena):
                # gather-intersect path: O(S) per ext, never W — the
                # request loops its own segments internally.  Kept
                # scalar deliberately: a flat cross-request gather
                # (repeat/tile + reduceat) was measured ~2x SLOWER than
                # per-request np.ix_ outer indexing at class shapes.
                totals[i] = self._sweep_sparse(arena, r)
                continue
            for g in r.segment_ids(arena):
                if arena.seg_words(g):   # skip zero-width (empty batch)
                    by_seg.setdefault(g, []).append(i)
        for g, idxs in sorted(by_seg.items()):
            rows = arena.seg_view(g)
            if len(idxs) == 1:
                i = idxs[0]
                c = self._sweep_one(rows, requests[i])
                totals[i] = c if totals[i] is None else totals[i] + c
                continue
            # bin by padded (L, E) so one fancy-index gather serves the
            # whole bin without per-request ragged handling
            bins: Dict[Tuple[int, int], List[int]] = {}
            for i in idxs:
                r = requests[i]
                key = (_pow2(len(r.prefix_handles)),
                       _pow2(len(r.ext_handles)))
                bins.setdefault(key, []).append(i)
            for (lp, ep), bi in sorted(bins.items()):
                counts = self._sweep_bin(
                    rows, [requests[i] for i in bi], lp, ep)
                for j, i in enumerate(bi):
                    c = counts[j, :len(requests[i].ext_handles)]
                    totals[i] = (c if totals[i] is None
                                 else totals[i] + c)
        return [t if t is not None
                else np.zeros(len(r.ext_handles), np.int64)
                for t, r in zip(totals, requests)]

    @staticmethod
    def _sweep_sparse(arena, r):
        """Sparse-prefix sweep: for each extension, gather the ext word
        at every prefix tid and test one bit — ``np.ix_`` outer-indexes
        the segment store directly into an [E, S] word block, so no
        [E, W] dense gather copy is ever built. Segment-restricted
        (delta) sweeps searchsorted the sorted tid payload down to the
        swept segments' global tid windows."""
        out = np.zeros(len(r.ext_handles), np.int64)
        tids = arena.tids_of(r.prefix_handle)
        if not len(tids) or not len(r.ext_handles):
            return out
        eh = list(r.ext_handles)
        for g in r.segment_ids(arena):
            if not arena.seg_words(g):
                continue
            lo, hi = arena.seg_tid_range(g)
            i0, i1 = np.searchsorted(tids, [lo, hi])
            if i0 == i1:
                continue
            t = (tids[i0:i1].astype(np.int64) - lo)
            wi = t >> 5
            bp = (t & 31).astype(np.uint32)
            words = arena.seg_view(g)[np.ix_(eh, wi)]       # [E, S]
            out += ((words >> bp[None, :]) & np.uint32(1)
                    ).sum(axis=1, dtype=np.int64)
        return out

    @staticmethod
    def _sweep_one(rows, r):
        """Single-request path: no padding copies, and
        ``support_counts`` chunks its own [E, W] temporary — the right
        shape for one wide full sweep."""
        ph = r.prefix_handles
        prefix = rows[ph[0]]
        for h in ph[1:]:              # tuple prefix: AND per segment
            prefix = prefix & rows[h]
        return tidlist.support_counts(
            prefix, rows[list(r.ext_handles)])

    def _sweep_bin(self, rows, reqs, lp, ep):
        """[B, E]-batched sweep over one segment: prefix tuples pad by
        repeating their first handle (AND-idempotent), extension pads
        gather row 0 and are sliced off by the caller."""
        b = len(reqs)
        w = rows.shape[1]
        pidx = np.zeros((b, lp), np.int64)
        eidx = np.zeros((b, ep), np.int64)
        for i, r in enumerate(reqs):
            ph = r.prefix_handles
            pidx[i] = ph + (ph[0],) * (lp - len(ph))
            eidx[i, :len(r.ext_handles)] = r.ext_handles
        pr = rows[pidx.ravel()].reshape(b, lp, w)
        prefix = pr[:, 0]
        for j in range(1, lp):
            prefix = prefix & pr[:, j]
        out = np.empty((b, ep), np.int64)
        step = max(1, self.PASS_BYTES // max(ep * w * 4, 1))
        for lo in range(0, b, step):
            hi = min(lo + step, b)
            ex = rows[eidx[lo:hi].ravel()].reshape(hi - lo, ep, w)
            out[lo:hi] = tidlist.popcount32(
                ex & prefix[lo:hi, None, :]).sum(axis=2)
        return out


# E-padding floor = the batched kernels' E tile (bitmap_join's EB_TILE
# and gather_intersect's E_TILE, one 128-lane vreg; not imported to
# keep jax out of this module's import path): any narrower pad would be
# re-padded to one tile inside the kernel anyway, so distinct sub-tile
# shapes would only multiply jit compilations. The sparse path's tid
# axis pads to the same floor (one lane-dense SMEM block).
E_PAD_FLOOR = 128


class _PallasBackend(JoinBackend):
    """Shared plumbing for the kernel modes. A flush splits into parts,
    one per (transaction segment, representation) its batch touches:
    dense requests go to ``bitmap_join_many``, sparse ones to
    ``gather_intersect_many``. Full sweeps touch every segment, delta
    sweeps only the fresh ones; single-segment arenas (every
    non-streaming run) have one part per representation. Each part is
    ONE jitted program fed ONE packed int32 descriptor
    (``_flush_fns``): it gathers its rows from the segment's device
    mirror, pads W and runs the kernel.

    A flush launches every part before it reads any counts back: each
    segment's mirror is synced once, before its first part, and the
    host builds part B's descriptor while the device runs part A, so
    the flush waits for the device once, not once per part. Each
    request's counts are then sliced out and summed across segments.
    B, L, E, S and W pad to powers of two so the jit cache stays
    bounded (~log × log shapes per run).

    On a dispatcher thread each part is three ``flush.*`` spans on its
    lane — ``flush.prepare`` (the descriptor, and the mirror sync
    before a segment's first part), ``flush.launch`` (the one jitted
    call, enqueued without waiting) and ``flush.wait`` (the copy of its
    counts back, which waits for the device) — every launch before the
    first wait. Each part adds its logical and padded work to the
    dispatcher's counters: dense words read ``Σ (L + E) × W`` against
    ``B' × (L' + E') × W'``, sparse probes ``Σ S × E`` against
    ``B' × S' × E'``; ``overlapped_launches`` counts the launches
    enqueued while an earlier part of the same flush was unread.

    Without a device mirror (arena backing "numpy") each part's rows
    are gathered on the host and shipped with its launch, billed to
    ``h2d_bytes``: the transfer-bound baseline, in the same
    launch-then-read order."""

    mode = "pallas-interpret"

    def sweep_many(self, arena, requests):
        work, tracer = _dispatch_thread.work, _dispatch_thread.tracer
        sparse = [r.is_sparse(arena) for r in requests]
        by_seg: Dict[int, List[int]] = {}
        for i, r in enumerate(requests):
            for g in r.segment_ids(arena):
                if arena.seg_words(g):
                    by_seg.setdefault(g, []).append(i)
        launched = []          # (request indices, counts on the device)
        for g, idxs in sorted(by_seg.items()):
            synced = False
            for rep, kernel, prepare in (
                    (False, "bitmap_join", self._prepare_dense),
                    (True, "gather_intersect", self._prepare_sparse)):
                part = [i for i in idxs if sparse[i] == rep]
                if not part:
                    continue
                with region(tracer, "flush.prepare", cat="flush"):
                    if not synced:
                        dev = arena.device_rows(
                            requests[idxs[0]].shard,
                            needed=self._needed(
                                arena, [requests[i] for i in idxs]),
                            segment=g)
                        synced = True
                    program, args, static = prepare(
                        arena, g, dev, [requests[i] for i in part], work)
                with region(tracer, "flush.launch", cat="flush") as a:
                    if a is not None:
                        a["kernel"] = kernel
                    launched.append((part, program(*args, **static)))
        if work is not None and launched:
            work["overlapped_launches"] += len(launched) - 1
        totals = [np.zeros(len(r.ext_handles), np.int64)
                  for r in requests]
        for part, out in launched:
            with region(tracer, "flush.wait", cat="flush"):
                counts = np.asarray(out)
            for j, i in enumerate(part):
                totals[i] += counts[j, :len(requests[i].ext_handles)]
        return totals

    def _prepare_dense(self, arena, seg, dev, requests, work):
        """Dense part: descriptor ``[B', L' + E']`` — each row the
        request's prefix tuple, padded by repeating its first handle
        (AND-idempotent), then its extension handles, padded with -1.
        Padded rows are all -1."""
        b = len(requests)
        bp = _pow2(b)
        lp = _pow2(max(len(r.prefix_handles) for r in requests))
        ep = _pow2(max(len(r.ext_handles) for r in requests),
                   lo=E_PAD_FLOOR)
        w = arena.seg_words(seg)
        # pad W to a pow2 too: delta sweeps see one fresh W per ingest,
        # and without the pad every (segment width, shape) pair mints a
        # new jit cache entry — recompile stalls that grow with ingest
        # count. Zero pad words AND to zero and add no popcount.
        wp = _pow2(w)
        desc = np.full((bp, lp + ep), -1, np.int32)
        for i, r in enumerate(requests):
            ph = r.prefix_handles
            desc[i, :len(ph)] = ph
            desc[i, len(ph):lp] = ph[0]
            desc[i, lp:lp + len(r.ext_handles)] = r.ext_handles
        if dev is None:
            dev, desc = _host_rows(arena, seg, desc, 0)
            arena.count_h2d(dev.nbytes)
        if work is not None:
            work["bitmap_join_launches"] += 1
            work["bitmap_join_words"] += w * sum(
                len(r.prefix_handles) + len(r.ext_handles)
                for r in requests)
            work["bitmap_join_padded_words"] += bp * (lp + ep) * wp
        dense, _ = _flush_fns(self.mode)
        return dense, (dev, desc), {"lp": lp, "wp": wp}

    def _prepare_sparse(self, arena, seg, dev, requests, work):
        """Sparse part: descriptor ``[B', S' + E']`` — each row the
        prefix's tids in this segment, then its extension handles, each
        padded with -1. Prefixes are tid/diffset payloads, shipped
        host→device with the descriptor (billed at the tid columns'
        nbytes — sparse rows have no resident mirror payload); tids are
        searchsorted down to this segment's global tid window and
        rebased."""
        b = len(requests)
        bp = _pow2(b)
        ep = _pow2(max(len(r.ext_handles) for r in requests),
                   lo=E_PAD_FLOOR)
        w = arena.seg_words(seg)
        wp = _pow2(w)
        lo, hi = arena.seg_tid_range(seg)
        local: List[np.ndarray] = []
        for r in requests:
            tids = arena.tids_of(r.prefix_handle)
            i0, i1 = np.searchsorted(tids, [lo, hi])
            local.append(tids[i0:i1])
        sp = _pow2(max(1, max(len(t) for t in local)), lo=E_PAD_FLOOR)
        desc = np.full((bp, sp + ep), -1, np.int32)
        for i, (t, r) in enumerate(zip(local, requests)):
            desc[i, :len(t)] = t - lo
            desc[i, sp:sp + len(r.ext_handles)] = r.ext_handles
        arena.count_h2d(bp * sp * 4)              # tid payload, per launch
        if dev is None:
            dev, desc = _host_rows(arena, seg, desc, sp)
            arena.count_h2d(dev.nbytes)
        if work is not None:
            work["gather_intersect_launches"] += 1
            work["gather_intersect_probes"] += sum(
                len(t) * len(r.ext_handles)
                for t, r in zip(local, requests))
            work["gather_intersect_padded_probes"] += bp * sp * ep
        _, sparse = _flush_fns(self.mode)
        return sparse, (dev, desc), {"sp": sp, "wp": wp}

    @staticmethod
    def _needed(arena, requests):
        """Handles a sharded mirror must hold for this batch (None on
        one shard: every stale owned row is refreshed)."""
        if arena.n_shards == 1:
            return None
        return [h for r in requests
                for h in (*r.prefix_handles, *r.ext_handles)]


def _host_rows(arena, seg, desc, col):
    """Host-gather baseline: gather the rows named by ``desc``'s columns
    from ``col`` on from the segment's host store into one ``[n, W]``
    block, and renumber those columns to index it (-1 sentinels kept),
    so the part's program reads the block as it would a mirror."""
    take = desc[:, col:]
    rows = arena.seg_view(seg)[np.maximum(take, 0).ravel()]
    desc = desc.copy()
    desc[:, col:] = np.where(
        take >= 0, np.arange(take.size, dtype=np.int32).reshape(take.shape),
        -1)
    return rows, desc


@functools.lru_cache(maxsize=None)
def _flush_fns(mode: str):
    """The two jitted programs of one kernel mode's flush, built once,
    one per part of a flush. Each takes a segment's mirror ``[cap, W]``
    and one packed int32 descriptor, gathers the rows it names (zero
    pad words to ``wp``) and runs the kernel; an extension column of -1
    is a padded lane, read as row 0 and masked to a count of 0.

    ``dense(mirror, desc [B', L' + E'], lp, wp)`` AND-reduces the first
    ``lp`` columns' rows into each request's prefix and runs
    ``bitmap_join_many`` against the rest. ``sparse(mirror, desc
    [B', S' + E'], sp, wp)`` reads the first ``sp`` columns as tids
    (-1 = padded lane) and runs ``gather_intersect_many`` against the
    rest's rows. Each compiles once per padded shape (mirror capacity,
    B', L' or S', E', W') — a handful per run."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.bitmap_join.ops import bitmap_join_many
    from repro.kernels.gather_intersect.ops import gather_intersect_many

    def rows(mirror, idx, wp):
        got = mirror[jnp.maximum(idx, 0)]
        return jnp.pad(got, ((0, 0), (0, 0), (0, wp - mirror.shape[1])))

    @functools.partial(jax.jit, static_argnames=("lp", "wp"))
    def dense(mirror, desc, lp, wp):
        pr, ext = rows(mirror, desc[:, :lp], wp), desc[:, lp:]
        prefixes = pr[:, 0]
        for j in range(1, lp):            # tuple prefix: AND-reduce
            prefixes = prefixes & pr[:, j]
        return bitmap_join_many(prefixes, rows(mirror, ext, wp), ext >= 0,
                                mode=mode)

    @functools.partial(jax.jit, static_argnames=("sp", "wp"))
    def sparse(mirror, desc, sp, wp):
        ext = desc[:, sp:]
        return gather_intersect_many(desc[:, :sp], rows(mirror, ext, wp),
                                     ext >= 0, mode=mode)

    return dense, sparse


class PallasInterpretBackend(_PallasBackend):
    name = "pallas-interpret"
    mode = "pallas-interpret"


class PallasJitBackend(_PallasBackend):
    name = "pallas-jit"
    mode = "pallas-jit"


_REGISTRY: Dict[str, Callable[[], JoinBackend]] = {
    "numpy": NumpyBackend,
    "pallas-interpret": PallasInterpretBackend,
    "pallas-jit": PallasJitBackend,
}
_instances: Dict[str, JoinBackend] = {}


def get_backend(name: str) -> JoinBackend:
    if name not in _REGISTRY:
        raise ValueError(
            f"unknown join backend {name!r}; known: {sorted(_REGISTRY)}")
    b = _instances.get(name)
    if b is None:
        b = _instances[name] = _REGISTRY[name]()
    return b


def _on_tpu() -> bool:
    """True when jax's default backend is a TPU. A backend that fails to
    initialize raises here rather than reading as "no TPU"."""
    import jax
    return jax.default_backend() == "tpu"


def available_backends() -> List[str]:
    """Backends that can execute on this host. The compiled Pallas
    kernel only lowers on TPU; the interpreter runs anywhere."""
    names = ["numpy", "pallas-interpret"]
    if _on_tpu():
        names.append("pallas-jit")
    return names


def resolve_backend(spec: str = "auto") -> JoinBackend:
    """One backend per run (batching replaced the per-bucket choice:
    narrow buckets now amortize a launch by sharing it, so there is no
    tiny-bucket penalty to route around). "auto" is the compiled
    kernel when jax's default backend is a TPU and numpy otherwise —
    the interpreter is a correctness tool, not a fast path. A named
    backend that cannot run here raises; nothing falls back."""
    if spec == "auto":
        return get_backend("pallas-jit" if _on_tpu() else "numpy")
    avail = available_backends()
    if spec not in avail:
        # fail fast: an unavailable backend must error here, not
        # inside a scheduler worker thread mid-mine
        get_backend(spec)                     # unknown name -> ValueError
        raise ValueError(
            f"join backend {spec!r} is not available on this host "
            f"(available: {avail})")
    return get_backend(spec)


class SweepDispatcher:
    """Coalesces many workers' sweep requests into batched launches.

    In mesh runs there is ONE dispatcher per device shard: workers
    submit to the dispatcher matching their device affinity, requests
    are stamped with that shard, and each dispatcher flushes
    ``bitmap_join_many`` against its own arena mirror — per-device
    batching, per-device occupancy gauges.

    Workers call :meth:`sweep` (or :meth:`submit` + ``future.result()``)
    and block; the dedicated dispatcher thread gathers pending requests
    and flushes a batch when either

      * ``min(max_batch, n_clients)`` requests are pending — since
        ``sweep`` blocks its caller, pending requests count currently
        blocked clients, so once every client is waiting no further
        request can arrive and waiting longer is pure latency; or
      * ``flush_us`` elapsed since the flush started forming — bounding
        the latency a lone straggler pays when other workers are busy
        with non-sweep work.

    Every sweep, on every backend and at every granularity, is a
    request flushed by this thread: no caller runs the backend itself,
    so ``flushes`` and ``sweep_requests`` count every join the engine
    made, and ``batch_occupancy`` (requests per flush) shows whether
    batching actually happened.

    Errors from the backend resolve every future in the flight batch,
    so task bodies re-raise through the scheduler's normal task-error
    machinery.
    """

    def __init__(self, arena: BitmapArena, backend: JoinBackend,
                 n_clients: int, max_batch: int = MAX_BATCH,
                 flush_us: float = FLUSH_US, shard: int = 0,
                 query_flush_us: float = QUERY_FLUSH_US, cluster=None,
                 tracer=None, trace_pid: int = 0):
        self.arena = arena
        self.backend = backend
        # observability: None = off; spans record flush formation on
        # the dispatcher lane and blocking sweeps on the caller's lane
        self.tracer = tracer
        self.trace_pid = trace_pid
        self.n_clients = max(1, n_clients)
        self.max_batch = max(1, max_batch)
        self.flush_s = max(0.0, flush_us) * 1e-6
        self.query_flush_s = max(0.0, query_flush_us) * 1e-6
        self.shard = shard
        # multi-host context: when set, every flush is two-phase —
        # local partial counts over this arena's owned words, then
        # cluster.reduce_flush sums the peers' partials for the same
        # descriptors. One reduction per flush, so the collective
        # amortizes exactly like the dispatcher amortizes launches.
        self.cluster = cluster
        self.sweep_s = 0.0            # backend busy time (s)
        self._pending: List[SweepRequest] = []
        self._n_priority = 0          # priority requests in _pending
        self._cv = threading.Condition()
        self._stop = False
        self.flushes = 0
        self.requests = 0
        self.query_requests = 0       # priority (serving) requests seen
        # µs queued requests waited from submit to the start of their
        # flush, summed; and the per-kernel launch counters the
        # backend adds to (only this dispatcher's thread writes these,
        # ``sweep_s`` and, under ``_cv``, the flush gauges)
        self.queue_wait_us = 0
        self.work = dict.fromkeys(obs_schema.KERNEL_COUNTERS, 0)
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name=f"sweep-dispatcher-{shard}")
        self._thread.start()

    # ------------------------------------------------------------ client --
    def submit(self, prefix_handle: int,
               ext_handles: Sequence[int],
               segments: Optional[Sequence[int]] = None,
               priority: bool = False,
               desc: Optional[Tuple[int, ...]] = None) -> Future:
        p = (tuple(int(h) for h in prefix_handle)
             if isinstance(prefix_handle, tuple) else int(prefix_handle))
        req = SweepRequest(p, tuple(ext_handles),
                           shard=self.shard,
                           segments=(tuple(segments)
                                     if segments is not None else None),
                           priority=priority, desc=desc)
        with self._cv:
            if self._stop:
                raise RuntimeError("dispatcher is stopped")
            if priority:
                self._pending.insert(0, req)
                self._n_priority += 1
                self.query_requests += 1
            else:
                self._pending.append(req)
            self._cv.notify_all()
        return req.future

    def submit_many(self, sweeps: Sequence[Tuple],
                    segments: Optional[Sequence[int]] = None,
                    priority: bool = False) -> List[Future]:
        """Enqueue a burst of ``(prefix, ext_handles)`` sweeps under one
        lock acquisition / one wakeup — the streaming delta path's
        coalescing entry point (per-candidate ``submit`` calls would
        trickle in and flush at occupancy ~1). ``prefix`` may be a
        handle or a tuple of handles (AND-reduced in the backend).
        ``priority=True`` marks the burst as query-class: it goes to
        the FRONT of the pending queue (order preserved within the
        burst) and shortens the straggler wait to ``query_flush_us``."""
        segs = tuple(segments) if segments is not None else None
        reqs = [SweepRequest(
                    (tuple(int(h) for h in p) if isinstance(p, tuple)
                     else int(p)),
                    tuple(e), shard=self.shard, segments=segs,
                    priority=priority)
                for p, e in sweeps]
        with self._cv:
            if self._stop:
                raise RuntimeError("dispatcher is stopped")
            if priority:
                self._pending[:0] = reqs
                self._n_priority += len(reqs)
                self.query_requests += len(reqs)
            else:
                self._pending.extend(reqs)
            self._cv.notify_all()
        return [r.future for r in reqs]

    def sweep(self, prefix_handle: int,
              ext_handles: Sequence[int],
              segments: Optional[Sequence[int]] = None,
              desc: Optional[Tuple[int, ...]] = None) -> np.ndarray:
        """Blocking convenience: enqueue and wait for the counts.
        ``segments`` restricts the join to a segment subset (a
        streaming delta sweep)."""
        # caller-side wait: nests inside the worker's task span
        with region(self.tracer, "sweep", cat="sweep") as args:
            if args is not None:
                args["ext"] = len(ext_handles)
            return self.submit(prefix_handle, ext_handles,
                               segments=segments, desc=desc).result()

    @property
    def batch_occupancy(self) -> float:
        return self.requests / self.flushes if self.flushes else 0.0

    def stats(self) -> Dict[str, float]:
        """This dispatcher's gauges — the per-device rows of
        ``MiningMetrics.per_device``, on the ``repro.obs.schema``
        device schema (arena-global h2d/d2d gauges live on the arena,
        not here)."""
        return obs_schema.device_stats(
            {"device": self.shard, "flushes": self.flushes,
             "sweep_requests": self.requests,
             "query_requests": self.query_requests,
             "queue_wait_us": self.queue_wait_us,
             **self.work,
             "sweep_s": self.sweep_s})

    def _launches(self) -> int:
        w = self.work
        return w["bitmap_join_launches"] + w["gather_intersect_launches"]

    def _flush_args(self, batch: Sequence[SweepRequest],
                    parts: int) -> Dict[str, float]:
        """Span payload for one flush: requests, rows, the dense/sparse
        representation split and ``parts``, the kernel launches it made
        (0 on a host backend). Only runs when a tracer is attached."""
        arena = self.arena
        rows = sum(len(r.prefix_handles) + len(r.ext_handles)
                   for r in batch)
        sparse = sum(1 for r in batch if r.is_sparse(arena))
        return {"requests": len(batch), "rows": rows,
                "sparse": sparse, "dense": len(batch) - sparse,
                "queries": sum(1 for r in batch if r.priority),
                "parts": parts}

    # -------------------------------------------------------------- loop --
    def _loop(self):
        tr = self.tracer
        if tr is not None:
            tr.set_lane(f"dispatcher-{self.shard}",
                        sort_index=1000 + self.shard,
                        pid=self.trace_pid)
        _dispatch_thread.work = self.work
        _dispatch_thread.tracer = tr
        full = min(self.max_batch, self.n_clients)
        while True:
            with self._cv:
                if not self._pending and not self._stop:
                    with region(tr, "dispatch.idle", cat="idle"):
                        while not self._pending and not self._stop:
                            self._cv.wait()
                if not self._pending and self._stop:
                    return
                if len(self._pending) < full and not self._stop:
                    with region(tr, "dispatch.form", cat="idle"):
                        self._wait_stragglers(full)
                batch = self._pending[:self.max_batch]
                del self._pending[:self.max_batch]
                self._n_priority -= sum(1 for r in batch if r.priority)
                self.flushes += 1
                self.requests += len(batch)
                t_start = time.perf_counter()
                self.queue_wait_us += int(1e6 * sum(
                    t_start - r.t_submit for r in batch))
            try:
                with region(tr, "flush", cat="flush") as args:
                    launches = self._launches()
                    t0 = time.perf_counter()
                    results = self.backend.sweep_many(self.arena, batch)
                    self.sweep_s += time.perf_counter() - t0
                    if self.cluster is not None:
                        # the cross-host reduction tail of this flush
                        with region(tr, "net-flush", cat="net") as a:
                            if a is not None:
                                a["requests"] = len(batch)
                            results = self.cluster.reduce_flush(
                                batch, results)
                    if args is not None:
                        args.update(self._flush_args(
                            batch, self._launches() - launches))
            except BaseException as e:  # noqa: BLE001 - resolve futures:
                for r in batch:         # a swallowed error would deadlock
                    r.future.set_exception(e)   # every blocked worker
            else:
                for r, counts in zip(batch, results):
                    r.future.set_result(counts)

    def _wait_stragglers(self, full: int) -> None:
        """Hold the forming flush (caller holds ``_cv``) until ``full``
        requests are pending, ``flush_us`` has passed, or a pending
        query's shorter cap has."""
        deadline = time.monotonic() + self.flush_s
        while len(self._pending) < full and not self._stop:
            # a pending query caps the straggler wait: the cap
            # re-applies on every pass so a query that ARRIVES
            # mid-wait also shortens the window
            if self._n_priority:
                deadline = min(deadline,
                               time.monotonic() + self.query_flush_s)
            left = deadline - time.monotonic()
            if left <= 0:
                break
            self._cv.wait(timeout=left)

    def stop(self):
        """Drain pending requests, then join the dispatcher thread."""
        with self._cv:
            self._stop = True
            self._cv.notify_all()
        self._thread.join(timeout=10)
        with self._cv:                  # only non-empty if the thread died
            leftover, self._pending = self._pending, []
        for r in leftover:              # pragma: no cover - crash path
            r.future.set_exception(RuntimeError("dispatcher stopped"))
