"""Apriori/Eclat FPM on the task scheduler — the paper's application.

Three task granularities (the paper's key knob, cf. "Redesigning pattern
mining algorithms for supercomputers"):

  granularity="candidate"    one task per candidate k-itemset (paper §2).
      The task's prefix comes from a per-worker-thread LRU cache of
      *prefix intersections*: tasks that share a (k-1)-prefix hit the
      cache iff they run back-to-back on the same worker — exactly the
      locality the clustered policy creates and the Cilk-style policy
      destroys. Its one-extension join is a dispatcher request.
  granularity="bucket"       one task per (k-1)-prefix bucket (default).
      The task resolves its prefix intersection ONCE (to an arena
      handle) and enqueues one handle-based SweepRequest on the sweep
      dispatcher, which coalesces many workers' buckets into batched
      multi-prefix kernel launches (repro.core.join_backend).
      Level-synchronous: a driver barrier separates level k from k+1.
  granularity="depth-first"  barrier-free equivalence-class recursion.
      Each task owns one class (prefix P, sibling extensions E): it
      sweeps E through the dispatcher, records the frequent extensions,
      forms the child classes P+(e,) × {siblings > e} Eclat-style (no
      global candidate generation), materializes each child's
      ``prefix ∧ ext`` bitmap exactly once *into the arena* and hands
      the child task the handle — so no child ever recomputes or
      cache-probes a prefix intersection. Children spawn onto the
      spawning worker's queue (steals move whole subtrees); the deepest
      class drains first, bounding retained handoff bitmaps; one
      terminal ``wait_all`` replaces every inter-level barrier.

Every bitmap lives in one ``BitmapArena`` (repro.core.tidlist): item
bitmaps are loaded once (handle == item id), prefix intersections and
child handoffs are refcounted arena rows, and on the Pallas path the
arena's device mirror is synced incrementally — repeated sweeps cost
~one initial upload (``MiningMetrics.h2d_bytes``) instead of one
upload per sweep. Every join of every granularity, on every backend,
is a ``SweepRequest`` flushed by a dispatcher thread.

``mine(mesh=...)`` runs the SAME engine — every granularity, every
policy — across a device mesh: the arena shards one mirror per device
(item rows replicated, materialized rows owned by the creating shard),
one dispatcher per device flushes batched joins on its own shard,
workers carry a device affinity so clustered bucket placement is device
placement, and a cross-device bucket steal migrates the bucket's
retained handoff bitmaps explicitly. ``repro.core.distributed_fpm`` is
now only a compatibility shim over this path.

All granularities return identical supports under every policy (and
under every mesh shape). The cache hit-rate (candidate),
rows-touched/bytes-swept counters (shared cost model in
repro.core.buckets), batch-occupancy/flush gauges (per-device
dispatchers), peak-retained-bitmap gauge (arena), and cross-device
``d2d_bytes``/``migrations`` gauges are this reproduction's analogue
of the paper's dTLB/IPC counters.
"""
from __future__ import annotations

import collections
import threading
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from repro.core import tidlist
from repro.core.buckets import (REPRESENTATIONS, DensityModel,
                                class_rows_touched, rows_to_bytes)
from repro.core.itemsets import (Bucket, Itemset, gen_buckets,
                                 gen_candidates, itemset_hash)
from repro.core.join_backend import (FLUSH_US, MAX_BATCH, SweepDispatcher,
                                     resolve_backend)
from repro.core.scheduler import TaskScheduler, make_policy
from repro.core.tidlist import BitmapArena
from repro.obs import MetricsRegistry, region
from repro.obs import schema as obs_schema

GRANULARITIES = ("bucket", "candidate", "depth-first", "auto")


@dataclass
class MiningMetrics:
    wall_s: float = 0.0
    levels: int = 0
    candidates: int = 0
    buckets: int = 0
    frequent: int = 0
    cache_hits: int = 0
    cache_misses: int = 0
    cache_partial_hits: int = 0
    rows_touched: int = 0        # bitmap rows actually read (measured)
    bytes_swept: int = 0         # rows_touched * W * 4
    # arena gauges: how many non-base rows (cached prefix intersections
    # + depth-first handoff bitmaps) were alive at once — the engines'
    # memory bound — and the bitmap payload uploaded host→device
    peak_retained_bitmaps: int = 0
    peak_bytes_retained: int = 0
    h2d_bytes: int = 0
    # dispatcher gauges: batched launches and their mean occupancy
    # (sweep requests per flush; >1 means coalescing actually happened)
    flushes: int = 0
    batch_occupancy: float = 0.0
    # mesh gauges: shards in the run, modeled cross-device row traffic
    # (on-demand foreign fetches + explicit steal migrations), rows
    # re-owned by migration, and one stats dict per device dispatcher
    # (flushes / batch_occupancy / sweep_requests per shard)
    n_devices: int = 1
    d2d_bytes: int = 0
    migrations: int = 0
    per_device: List[Dict[str, float]] = field(default_factory=list)
    scheduler: Dict[str, float] = field(default_factory=dict)
    # multi-host gauges (cluster runs only): hosts in the run, bytes
    # that crossed the interconnect (descriptor flushes + count
    # replies + level exchanges + steal migrations), the steal share
    # of them, cross-host bucket migrations, and one per-host row
    # (bytes_swept / sweep_s / eval_s / eval_bytes) for capacity math
    n_hosts: int = 1
    net_bytes: int = 0
    steal_net: int = 0
    cross_steals: int = 0
    per_host: List[Dict[str, float]] = field(default_factory=list)
    # hybrid-representation gauges: sweeps split by the prefix row's
    # representation, the byte share of bytes_swept that went through
    # the sparse (gather-intersect) path, sparse rows pushed, both
    # conversion directions (ops + bytes billed by the arena), and the
    # density model's per-child representation decisions
    representation: str = "bitmap"
    dense_sweeps: int = 0
    sparse_sweeps: int = 0
    sparse_bytes_swept: int = 0
    sparse_rows: int = 0
    densify_ops: int = 0
    densify_bytes: int = 0
    sparsify_ops: int = 0
    sparsify_bytes: int = 0
    rep_picks: Dict[str, int] = field(default_factory=dict)

    @property
    def cache_hit_rate(self) -> float:
        t = self.cache_hits + self.cache_misses
        return self.cache_hits / t if t else 0.0


class _PrefixCache:
    """LRU of prefix -> arena handle of the intersected bitmap (one
    instance per worker).

    *Hierarchical*: a miss on ABC first checks AB — if present, only one
    extra AND is needed. With the nearest-neighbour policy (the paper's
    §6 future work) neighbouring buckets share sub-prefixes, so partial
    reuse crosses bucket boundaries.

    ``get`` also returns the number of bitmap rows it read to build the
    intersection (0 on a full hit) — the measured locality traffic.

    Ownership contract: the cache owns one arena reference per entry
    (``push`` grants it; eviction releases), and ``get`` retains a
    SECOND reference on the caller's behalf before returning — the
    caller must release it when done. This keeps a handle live across
    the async dispatcher flight even if the entry is evicted meanwhile,
    and makes ``cache_size=0`` a valid "no cache" A/B knob (the entry
    is evicted immediately, but the caller's reference keeps the row
    alive until its release).

    The depth-first engine never touches this cache: the parent→child
    handle handoff makes it vestigial on that path (cache_misses == 0
    structurally)."""

    def __init__(self, arena: BitmapArena, maxsize: int = 32,
                 shard: int = 0, upto: Optional[int] = None,
                 model: Optional[DensityModel] = None):
        self.arena = arena
        self.maxsize = maxsize
        self.model = model        # density model: sparse-worthy prefix
                                  # intersections are pushed as
                                  # tid-lists instead of word-columns
        self.shard = shard        # rows this cache pushes are owned by
                                  # the caching worker's device shard
        self.upto = upto          # segment boundary: builds read (and
                                  # pushed rows cover) only the first
                                  # ``upto`` segments, so an ingest
                                  # landing mid-refresh cannot change a
                                  # row's width between two reads
        self.d: "collections.OrderedDict[Itemset, int]" = \
            collections.OrderedDict()
        self.hits = 0
        self.misses = 0
        self.partial_hits = 0

    def _row(self, h: int) -> np.ndarray:
        if self.upto is None:
            return self.arena.row(h)
        return self.arena.row_upto(h, self.upto)

    def _put(self, prefix: Itemset, handle: int):
        self.d[prefix] = handle
        if len(self.d) > self.maxsize:
            _, old = self.d.popitem(last=False)
            self.arena.release(old)

    def get(self, prefix: Itemset) -> Tuple[int, int]:
        """(caller-retained arena handle, bitmap rows read to build
        it). The caller must ``release`` the handle when done."""
        d = self.d
        arena = self.arena
        if prefix in d:
            d.move_to_end(prefix)
            self.hits += 1
            h = d[prefix]
            arena.retain(h)
            return h, 0
        self.misses += 1
        # hierarchical fallback: longest cached ancestor prefix
        for cut in range(len(prefix) - 1, 1, -1):
            parent = prefix[:cut]
            if parent in d:
                d.move_to_end(parent)
                self.partial_hits += 1
                bm = self._row(d[parent])
                for item in prefix[cut:]:
                    bm = bm & self._row(item)
                rows_read = len(prefix) - cut
                break
        else:
            bm = self._row(prefix[0]).copy()
            for item in prefix[1:]:
                bm &= self._row(item)
            rows_read = len(prefix)
        if (self.model is not None and self.model.pick_rep(
                int(tidlist.popcount32(bm).sum())) != "bitmap"):
            h = arena.sparsify_push(bm, shard=self.shard,
                                    cover=self.upto)
        else:
            h = arena.push(bm, shard=self.shard, cover=self.upto)
        arena.retain(h)           # the caller's reference, BEFORE _put:
        self._put(prefix, h)      # maxsize=0 evicts-and-releases at once
        return h, rows_read

    def drain(self) -> None:
        """Release every cached handle. A one-shot ``mine`` discards
        the arena with the run, but a streaming arena persists across
        refreshes — rows a dead cache pins would never recycle, and
        worse, they would survive a later ``ingest`` WITHOUT the new
        segment's words, so the runtime drains caches at close."""
        while self.d:
            _, h = self.d.popitem(last=False)
            self.arena.release(h)


def _raise_task_errors(tasks) -> None:
    """Surface the first task-body exception on the driver thread (the
    scheduler records it instead of letting the worker die, which would
    deadlock wait_all)."""
    for t in tasks:
        if t.error is not None:
            raise t.error


def _level1(bitmaps: np.ndarray, min_support: int, counts=None
            ) -> Tuple[Dict[Itemset, int], List[Itemset]]:
    """Level 1, shared by every engine: dense popcount, no tasks.
    ``counts`` short-circuits the popcount with per-item ones counts a
    caller already has (``pack_database(..., return_counts=True)``
    produces them in the packing pass)."""
    supports = (np.asarray(counts) if counts is not None
                else tidlist.popcount32(bitmaps).sum(axis=1))
    result: Dict[Itemset, int] = {
        (i,): int(supports[i]) for i in range(bitmaps.shape[0])
        if supports[i] >= min_support}
    return result, sorted(result)


def _cluster_fn(granularity: str, policy: str):
    """Task attr -> queue-bucket key. attr = (prefix_hash, itemset-or-
    prefix): the hash is the paper's XOR'd prefix hash, precomputed once
    so queue ops stay O(1). The nearest-neighbour policy keys buckets by
    the prefix tuple itself (it needs item overlap between bucket keys).
    """
    if granularity == "candidate":
        return ((lambda a: a[1][:-1]) if policy == "nn"
                else (lambda a: a[0]))
    return ((lambda a: a[1]) if policy == "nn"
            else (lambda a: a[0]))


def _resolve_mesh(mesh) -> Tuple[int, Optional[list]]:
    """``mesh=`` accepts None (shared-memory run), an int (N logical
    shards — ownership/affinity/d2d accounting without jax devices, so
    the CPU tier exercises the mesh path), or a ``jax.sharding.Mesh``
    (one shard per mesh device, mirrors placed on those devices).
    Returns (n_shards, devices-or-None)."""
    if mesh is None:
        return 1, None
    if isinstance(mesh, int):
        if mesh < 1:
            raise ValueError(f"mesh must be >= 1 shards, got {mesh}")
        return mesh, None
    devs = list(np.asarray(mesh.devices).reshape(-1))
    return len(devs), devs


def mesh_over_devices(n: int):
    """CLI ``--mesh N`` semantics, shared by the launcher, quickstart,
    and benchmarks: a jax ``Mesh`` over the first N devices when the
    host exposes at least N, else N logical shards (the int form of
    ``mine``'s ``mesh=``). Returns None for ``n <= 1`` — a plain
    shared-memory run."""
    if n <= 1:
        return None
    import jax
    from jax.sharding import Mesh
    devs = jax.devices()
    if len(devs) >= n:
        return Mesh(np.array(devs[:n]), ("data",))
    return n


@dataclass
class DeltaPlan:
    """Incremental re-mine instructions threaded through the engine
    cores by ``StreamingMiner.refresh`` (None on a batch ``mine``).

    ``known`` maps every candidate ever swept (frequent AND negative
    border) to its exact support over the segments refreshed so far —
    the engines update it in place (under ``lock`` on the depth-first
    path, where class tasks merge concurrently). ``dirty_items`` are
    the items occurring in the pending segments: a candidate's support
    may have changed iff EVERY item of it is dirty. ``segments`` are
    the pending segment ids a dirty candidate's delta sweep reads;
    ``base_segments`` are the segments a FULL (fresh-candidate) sweep
    reads — the refresh generation boundary, so an ingest landing
    mid-refresh never leaks into this generation's supports.
    ``priority_of(prefix)`` (optional) is the staleness-hotness carried
    on spawned tasks — the clustered policies drain stale-hot buckets
    first; None skips priority stamping entirely (an all-fresh first
    generation would otherwise pay the priority-drain scan for
    nothing). ``tenant`` tags every spawned task for the scheduler's
    weighted-fair drain (multi-tenant serving; None on single-tenant
    runs). Clean known candidates are never swept at all: that is
    the whole point."""
    known: Dict[Itemset, int]
    dirty_items: frozenset
    segments: Tuple[int, ...]
    base_segments: Tuple[int, ...]
    priority_of: Optional[Callable[[Itemset], float]] = None
    tenant: object = None
    lock: threading.Lock = field(default_factory=threading.Lock)
    # refresh-side counters (how much re-mining the plan avoided)
    swept_full: int = 0
    swept_delta: int = 0
    reused: int = 0

    def is_dirty(self, c: Itemset) -> bool:
        d = self.dirty_items
        return all(i in d for i in c)

    def classify_buckets(self, plan: List[Bucket]
                         ) -> Tuple[List[Tuple[Itemset, int]],
                                    List[Bucket], List[Bucket]]:
        """Split a level's prefix buckets into (clean ``(c, support)``
        pairs, dirty sub-buckets, fresh sub-buckets) in one pass over
        the already-grouped plan. The prefix's dirtiness is probed
        ONCE per bucket — the per-candidate hot loop is one
        ``known.get`` plus one set probe for the extension item, and
        dirty and fresh extensions stay bucketed so the delta path
        never re-groups them."""
        known, ditems = self.known, self.dirty_items
        clean: List[Tuple[Itemset, int]] = []
        dirty: List[Bucket] = []
        fresh: List[Bucket] = []
        for b in plan:
            p = b.prefix
            p_dirty = all(i in ditems for i in p)
            d_exts: List[int] = []
            f_exts: List[int] = []
            for e in b.exts:
                c = p + (e,)
                ks = known.get(c)
                if ks is None:
                    f_exts.append(e)
                elif p_dirty and e in ditems:
                    d_exts.append(e)
                else:
                    clean.append((c, ks))
            if d_exts:
                dirty.append(Bucket(b.key, p, tuple(d_exts)))
            if f_exts:
                fresh.append(Bucket(b.key, p, tuple(f_exts)))
        return clean, dirty, fresh


class EngineRuntime:
    """The persistent engine substrate: one scheduler with
    device-affine workers plus one sweep dispatcher per arena shard.

    Batch ``mine`` spins one up per call and tears it down with the
    run; the streaming/serving layer owns ONE across its whole life and
    lends it to every refresh's :class:`MiningRun` — so query sweeps
    submitted between (and during) refreshes land on the SAME
    dispatchers as candidate sweeps and coalesce into the same
    flushes. Idle cost is zero: dispatcher threads park untimed on
    their condition variable and so do scheduler workers once nothing
    is outstanding."""

    def __init__(self, store: BitmapArena, *, policy: str = "clustered",
                 n_workers: int = 8, granularity: str = "bucket",
                 backend: str = "auto", max_batch: int = MAX_BATCH,
                 flush_us: float = FLUSH_US, cluster=None, tracer=None):
        backend_obj = resolve_backend(backend)
        n_shards = store.n_shards
        if n_shards > 1:
            n_workers = max(n_workers, n_shards)  # ≥1 worker per shard
        self.store = store
        self.n_workers = n_workers
        self.backend = backend_obj
        # multi-host context (repro.core.cluster): the dispatchers
        # reduce every flush across hosts through it, and the engine
        # cores partition work / exchange level results through it
        self.cluster = cluster
        # observability (repro.obs): one tracer threaded through every
        # layer this runtime owns — scheduler workers, dispatcher
        # threads and the arena all record into its per-thread rings.
        # None (the default) keeps every instrumented site on the
        # one-branch disabled fast path. In cluster mode the host rank
        # becomes the Chrome-trace pid, one lane group per host.
        self.tracer = tracer
        trace_pid = cluster.host_id if cluster is not None else 0
        self.trace_pid = trace_pid
        if tracer is not None:
            store.tracer = tracer
        self.device_of = [i % n_shards for i in range(n_workers)]
        self.dispatchers = [
            SweepDispatcher(store, backend_obj,
                            n_clients=self.device_of.count(s),
                            max_batch=max_batch, flush_us=flush_us,
                            shard=s, cluster=cluster, tracer=tracer,
                            trace_pid=trace_pid)
            for s in range(n_shards)]
        self.sched = TaskScheduler(
            n_workers,
            make_policy(policy, n_workers,
                        _cluster_fn(granularity, policy)),
            device_of=self.device_of,
            migrate_cb=lambda hs, src, dst: store.migrate(hs, dst),
            tracer=tracer, trace_pid=trace_pid)
        # pull-based snapshot API: live gauges, readable any time
        self.registry = MetricsRegistry()
        self.registry.register("scheduler", self.sched.merged_stats)
        # the gauges close over locals, never ``self``: a cycle through
        # the runtime would keep a finished mine's arena, and its device
        # mirror, alive until the cyclic collector happened to run
        dispatchers = self.dispatchers
        self.registry.register(
            "per_device", lambda: [d.stats() for d in dispatchers])
        self.registry.register(
            "arena", lambda: {"h2d_bytes": store.h2d_bytes,
                              "d2d_bytes": store.d2d_bytes,
                              "migrations": store.migrations,
                              "compactions": store.compactions,
                              "compaction_bytes": store.compaction_bytes,
                              "live_extra": store.live_extra})

    def shutdown(self) -> None:
        self.sched.shutdown()
        for dispatcher in self.dispatchers:
            dispatcher.stop()


class MiningRun:
    """The engine runtime shared by batch ``mine`` and streaming
    ``refresh``: one scheduler with device-affine workers, one sweep
    dispatcher per arena shard, per-worker prefix caches, and the
    metrics plumbing — built around an arena the caller owns (a batch
    run discards it; a streaming run keeps it across refreshes).

    ``runtime`` lends a persistent :class:`EngineRuntime` instead of
    building one: the run then reports scheduler/dispatcher gauges as
    DELTAS against construction-time baselines (the shared runtime's
    counters accumulate across refreshes and query traffic), and
    ``close`` drains this run's caches but leaves the runtime alive."""

    def __init__(self, store: BitmapArena, *, policy: str,
                 n_workers: int, granularity: str, cache_size: int,
                 backend: str = "auto", max_batch: int = MAX_BATCH,
                 flush_us: float = FLUSH_US,
                 representation: str = "auto", item_counts=None,
                 runtime: Optional[EngineRuntime] = None,
                 tracer=None):
        if granularity not in GRANULARITIES:
            raise ValueError(
                f"granularity must be one of {GRANULARITIES}, "
                f"got {granularity!r}")
        if representation not in REPRESENTATIONS:
            raise ValueError(
                f"representation must be one of {REPRESENTATIONS}, "
                f"got {representation!r}")
        if runtime is None:
            runtime = EngineRuntime(
                store, policy=policy, n_workers=n_workers,
                granularity=granularity, backend=backend,
                max_batch=max_batch, flush_us=flush_us, tracer=tracer)
            self._owns_runtime = True
        else:
            if runtime.store is not store:
                raise ValueError(
                    "runtime was built over a different arena")
            self._owns_runtime = False
        self.runtime = runtime
        self.store = store
        self.granularity = granularity
        self.cache_size = cache_size
        self.representation = representation
        # "bitmap" keeps the model out entirely — the seed engine's
        # exact code paths; "auto"/"sparse" seed the density model from
        # per-item ones counts (pack_database's one-pass byproduct, or
        # the level-1 popcount the caller ran anyway)
        self.model = (None if representation == "bitmap"
                      else DensityModel.from_counts(
                          store.n_words, item_counts,
                          force=(None if representation == "auto"
                                 else "sparse")))
        self.device_of = runtime.device_of
        self.dispatchers = runtime.dispatchers
        self.sched = runtime.sched
        self.metrics = MiningMetrics(n_devices=store.n_shards)
        self.caches: Dict[int, _PrefixCache] = {}   # thread ident -> cache
        # gauge baselines: zero for an owned runtime, the accumulated
        # counters for a borrowed one — finalize() reports deltas
        self._disp0 = [d.stats() for d in self.dispatchers]
        self._sched0 = self.sched.merged_stats()

    def close(self) -> None:
        if self._owns_runtime:
            self.runtime.shutdown()
        for cache in self.caches.values():
            cache.drain()

    def _disp_stats(self, d, base) -> Dict[str, float]:
        now = d.stats()
        return obs_schema.device_stats(
            {**obs_schema.delta_counters(now, base,
                                         obs_schema.DEVICE_COUNTERS),
             "device": d.shard,
             "sweep_s": now["sweep_s"] - base["sweep_s"]})

    def finalize(self, t0: float) -> MiningMetrics:
        """Fill the metrics from scheduler/dispatcher/arena gauges.
        Scheduler and dispatcher gauges are deltas against this run's
        construction (identical to totals for an owned runtime). Arena
        gauges are cumulative over the arena's life — ``mine`` owns a
        fresh arena so they equal the run; ``refresh`` snapshots them
        before/after to report per-refresh deltas."""
        metrics, store = self.metrics, self.store
        # perf_counter epoch (matches the caller's t0): time.time() is
        # not monotonic — an NTP step mid-run corrupted wall_s
        metrics.wall_s = time.perf_counter() - t0
        # delta the COUNTERS only, then rebuild the derived ratio —
        # the obs schema is the one place the key set lives
        metrics.scheduler = obs_schema.scheduler_stats(
            obs_schema.delta_counters(self.sched.merged_stats(),
                                      self._sched0,
                                      obs_schema.SCHEDULER_COUNTERS))
        metrics.rows_touched = int(metrics.scheduler["rows_touched"])
        metrics.bytes_swept = int(metrics.scheduler["bytes_swept"])
        metrics.cache_hits = sum(c.hits for c in self.caches.values())
        metrics.cache_misses = sum(c.misses
                                   for c in self.caches.values())
        metrics.cache_partial_hits = sum(c.partial_hits
                                         for c in self.caches.values())
        metrics.per_device = [self._disp_stats(d, b)
                              for d, b in zip(self.dispatchers,
                                              self._disp0)]
        metrics.flushes = sum(int(row["flushes"])
                              for row in metrics.per_device)
        total_requests = sum(int(row["sweep_requests"])
                             for row in metrics.per_device)
        metrics.batch_occupancy = (total_requests / metrics.flushes
                                   if metrics.flushes else 0.0)
        metrics.h2d_bytes = store.h2d_bytes
        metrics.d2d_bytes = store.d2d_bytes
        metrics.migrations = store.migrations
        metrics.peak_retained_bitmaps = store.peak_live_extra
        metrics.peak_bytes_retained = store.peak_bytes_extra
        metrics.representation = self.representation
        metrics.dense_sweeps = int(metrics.scheduler["dense_sweeps"])
        metrics.sparse_sweeps = int(metrics.scheduler["sparse_sweeps"])
        metrics.sparse_bytes_swept = int(
            metrics.scheduler["sparse_bytes_swept"])
        metrics.sparse_rows = store.sparse_pushed
        metrics.densify_ops = store.densify_ops
        metrics.densify_bytes = store.densify_bytes
        metrics.sparsify_ops = store.sparsify_ops
        metrics.sparsify_bytes = store.sparsify_bytes
        if self.model is not None:
            metrics.rep_picks = {"bitmap": self.model.bitmap_picks,
                                 "tidlist": self.model.tidlist_picks,
                                 "diffset": self.model.diffset_picks}
        return metrics


def mine(bitmaps: np.ndarray, min_support: int, *,
         policy: str = "clustered", n_workers: int = 8,
         max_k: int = 8, cache_size: int = 32,
         granularity: str = "bucket", backend: str = "auto",
         arena: str = "auto", max_batch: int = MAX_BATCH,
         flush_us: float = FLUSH_US, mesh=None,
         representation: str = "auto", item_counts=None, hosts: int = 1,
         trace=None,
         ) -> Tuple[Dict[Itemset, int], MiningMetrics]:
    """bitmaps: [n_items, W] uint32 packed TID bitmaps.

    ``granularity`` selects the unit of scheduler task: "bucket" (one
    task per (k-1)-prefix, batched extension sweep), "candidate"
    (one single-extension sweep per candidate, the paper's task),
    "depth-first" (barrier-free equivalence-class recursion with
    parent→child handle handoff), or "auto" (levelwise driver that
    detaches subtrees to depth-first class tasks when the density
    model predicts sparse/deep mining wins there).
    ``representation`` selects the row representation the engines hand
    around: "bitmap" (word-columns only — the pre-hybrid engine),
    "sparse" (force tid-list/diffset rows wherever structurally legal),
    or "auto" (per-subtree density-driven choice; the default).
    ``item_counts`` passes per-item ones counts a caller already has
    (``pack_database(..., return_counts=True)``) so level 1 and the
    density-model seed skip their popcount pass.
    ``backend`` names the sweep executor ("auto", "numpy",
    "pallas-interpret", "pallas-jit"; see repro.core.join_backend).
    ``arena`` picks the bitmap store's device residency ("auto": lazy
    device mirror; "jax": eager upload; "numpy": host-only — Pallas
    backends then re-upload per batch, the old transfer-bound
    behaviour). ``max_batch``/``flush_us`` tune the sweep dispatcher's
    coalescing (requests per launch / straggler wait).
    ``mesh`` makes the SAME engine multi-device: a ``jax.sharding.Mesh``
    (or an int for logical shards) shards the arena one mirror per
    device, splits the dispatcher one-per-device, and pins workers to
    shards — every granularity and policy then runs distributed through
    this one code path, with cross-shard traffic in
    ``MiningMetrics.d2d_bytes`` and per-device dispatcher gauges in
    ``MiningMetrics.per_device``.
    ``hosts`` > 1 runs the multi-HOST decomposition instead (see
    repro.core.cluster): the transaction axis word-partitions over N
    logical hosts in this process — each with its own arena slice,
    scheduler and dispatchers — with two-phase support counting and
    cross-host steal-as-migration. Bit-identical results; cluster
    traffic lands in ``MiningMetrics.net_bytes``/``steal_net``.
    ``trace`` attaches a :class:`repro.obs.Tracer`: workers,
    dispatchers and the arena record span timelines into it (export
    with ``repro.obs.write_chrome_trace``; None = tracing off). The
    calling thread's ``driver`` lane tiles the call with ``mine.arena``,
    ``mine.level1``, ``mine.start`` (the runtime), per level its
    ``level.candidates`` and, when it has any, its ``level-k``, then
    ``mine.close`` and ``mine.finalize``.
    """
    if hosts > 1:
        if mesh is not None:
            raise ValueError("hosts= and mesh= are mutually exclusive "
                             "(a host owns its whole slice)")
        from repro.core.cluster import mine_cluster
        return mine_cluster(bitmaps, min_support, hosts=hosts,
                            policy=policy, n_workers=n_workers,
                            max_k=max_k, cache_size=cache_size,
                            granularity=granularity, backend=backend,
                            max_batch=max_batch, flush_us=flush_us,
                            item_counts=item_counts, tracer=trace)
    n_shards, devices = _resolve_mesh(mesh)
    if trace is not None:
        trace.set_lane("driver", sort_index=0)
    with region(trace, "mine.arena", cat="level"):
        # the host copy; the device mirror uploads on the first flush
        # (eagerly here under arena="jax")
        store = BitmapArena.from_bitmaps(bitmaps, backing=arena,
                                         n_shards=n_shards,
                                         devices=devices)
    t0 = time.perf_counter()
    # level 1 before the runtime spins up worker/dispatcher threads:
    # if it raises there is nothing to tear down
    with region(trace, "mine.level1", cat="level"):
        if item_counts is None:
            item_counts = tidlist.popcount32(bitmaps).sum(axis=1)
        result, frequent = _level1(bitmaps, min_support,
                                   counts=item_counts)
    with region(trace, "mine.start", cat="level"):
        run = MiningRun(store, policy=policy, n_workers=n_workers,
                        granularity=granularity, cache_size=cache_size,
                        backend=backend, max_batch=max_batch,
                        flush_us=flush_us,
                        representation=representation,
                        item_counts=item_counts, tracer=trace)
    run.metrics.frequent += len(frequent)
    try:
        mine_more(run, min_support, max_k, result, frequent)
    finally:
        with region(trace, "mine.close", cat="level"):
            run.close()
    with region(trace, "mine.finalize", cat="level"):
        return result, run.finalize(t0)


def mine_more(run: MiningRun, min_support: int, max_k: int,
              result: Dict[Itemset, int], frequent: List[Itemset],
              delta: Optional[DeltaPlan] = None) -> None:
    """Mine levels ≥ 2 on an existing runtime, starting from the
    level-1 ``frequent`` itemsets — the shared entry point under
    ``mine`` (delta=None: sweep everything) and the streaming refresh
    (delta: reuse known supports, delta-sweep dirty candidates over the
    pending segments only, carry staleness priorities)."""
    cluster = run.runtime.cluster
    tr = run.sched.tracer
    if tr is not None:
        # whichever thread drives this run gets the "driver" lane (one
        # per host in cluster mode — drivers are distinct threads)
        tr.set_lane("driver", sort_index=0, pid=run.runtime.trace_pid)
    if run.granularity == "depth-first":
        _mine_depth_first(run.store, run.dispatchers, min_support,
                          max_k, run.sched, run.metrics, result,
                          frequent, delta=delta, model=run.model,
                          cluster=cluster)
    else:
        _mine_levelwise(run.store, run.dispatchers, min_support, max_k,
                        run.sched, run.metrics, result, frequent,
                        run.granularity, run.cache_size, run.caches,
                        delta=delta, model=run.model, cluster=cluster)


def _mine_levelwise(store, dispatchers, min_support, max_k, sched,
                    metrics, result, frequent, granularity, cache_size,
                    caches, delta=None, model=None, cluster=None):
    """Level-synchronous engines: plan level k, spawn, barrier, plan
    level k+1 (the paper's §2 shape, at candidate or bucket grain).
    The planner is ``gen_buckets``: a level stays in prefix-bucket form
    from candidate generation to thresholding (candidate grain
    flattens it only to spawn), and a plain mine's collect pairs up
    only the frequent extensions, so no per-candidate tuple is built
    on the driver's serial path. A candidate task is a bucket task
    whose bucket holds one extension, so both grains sweep through the
    worker's device-affine dispatcher.

    With a ``delta`` plan the level's candidates split three ways:
    *clean known* (support unchanged — zero rows touched), *dirty
    known* (delta-swept over only the pending segments, support
    accumulated into ``delta.known``), and *fresh* (never swept —
    full sweep over the generation-boundary segments). Dirty buckets
    are CHUNKED: one scheduler task carries ~hundreds of buckets and
    submits them as a burst of tuple-prefix sweeps — the backend
    AND-reduces each prefix's base rows over only the pending
    segments, so the delta path never builds a full-width prefix
    intersection and its launches fill like the full path's. Tasks
    carry ``delta.priority_of`` (when set) so the clustered policies
    drain stale-hot prefixes first.

    ``granularity="auto"`` runs this driver with a per-bucket escape
    hatch: when the density model predicts a prefix's subtree is
    sparse (or thin enough that level barriers dominate), the whole
    bucket detaches into a depth-first class task — the subtree mines
    barrier-free in the model-picked representation and its itemsets
    never re-enter the level frontier (``gen_buckets`` gets the
    full known-frequent set so cross-prefix pruning stays exact).
    Under a delta plan auto stays level-synchronous: the classify
    clean/dirty/fresh split already skips clean work, and diffset
    handoffs are structurally disabled mid-refresh anyway."""
    n_w = store.n_words
    # cached prefix rows must COVER every segment the plan sweeps;
    # max+1 (not len) because a multi-tenant plan's segment set is a
    # non-contiguous subset of the arena's segments (identical for the
    # single-tenant prefix case, where base_segments is range(n))
    upto = ((max(delta.base_segments) + 1)
            if delta is not None and delta.base_segments else None)
    lock = threading.Lock()
    tr = sched.tracer
    df_miner = None
    detached_tasks: List = []
    if granularity == "auto" and model is not None and delta is None:
        df_miner = _ClassMiner(store, dispatchers, min_support, max_k,
                               sched, metrics, result, model=model)

    def _thread_cache() -> _PrefixCache:
        tid = threading.get_ident()
        c = caches.get(tid)
        if c is None:
            with lock:
                c = caches.setdefault(
                    tid, _PrefixCache(store, cache_size,
                                      shard=sched.worker_device(),
                                      upto=upto, model=model))
        return c

    def _prefix_handle(cache: _PrefixCache, prefix: Itemset
                       ) -> Tuple[int, int]:
        """Caller-retained handle (release when done; a no-op for the
        pinned base rows at k=2) + rows read to build it."""
        if len(prefix) == 1:
            return prefix[0], 1                 # base row; no reuse at k=2
        return cache.get(prefix)

    def _seg_w(segments) -> int:
        """Words per row a sweep actually reads: the full width, or
        only the pending segments' words on a delta sweep."""
        if segments is None:
            return n_w
        return sum(store.seg_words(g) for g in segments)

    def _account(prows: int, erows: int, segments) -> None:
        """prows prefix-build rows are read full-width; erows extension
        rows only over the swept segments."""
        st = sched.worker_stats()
        st.rows_touched += prows + erows
        st.bytes_swept += (rows_to_bytes(prows, n_w)
                           + rows_to_bytes(erows, _seg_w(segments)))

    def sweep_task(bucket: Bucket, segments=None) -> np.ndarray:
        """Bucket-granularity body: resolve the prefix handle once,
        then one handle-based request on the worker's device-affine
        dispatcher (which batches it with other workers' buckets on
        the same shard). ``segments`` restricts a delta sweep to the
        pending segments. Returns [E] counts."""
        cache = _thread_cache()
        ph, prows = _prefix_handle(cache, bucket.prefix)
        try:
            _account(prows, len(bucket.exts), segments)
            st = sched.worker_stats()
            st.sweeps_submitted += 1
            if store.rep_of(ph) != tidlist.REP_BITMAP:
                st.sparse_sweeps += 1
                st.sparse_bytes_swept += (len(store.tids_of(ph)) * 4
                                          * len(bucket.exts))
            else:
                st.dense_sweeps += 1
            disp = dispatchers[sched.worker_device()]
            return disp.sweep(ph, bucket.exts, segments=segments,
                              desc=bucket.prefix)
        finally:
            store.release(ph)

    def detach_task(bucket: Bucket, own_support: int,
                    psup: Tuple[int, ...]) -> None:
        """granularity="auto" handoff: resolve the bucket's prefix
        handle like a sweep task would, then run the depth-first class
        body inline — its children spawn barrier-free class tasks, and
        this whole subtree leaves the level frontier."""
        cache = _thread_cache()
        ph, prows = _prefix_handle(cache, bucket.prefix)
        _account(prows, 0, None)
        df_miner.class_task(bucket.prefix, ph, bucket.exts, psup,
                            own_support, True)

    def _spawn_buckets(plan: List[Bucket], segments):
        with region(tr, "level.plan", cat="level"):
            detach = []
            if df_miner is not None:
                keep = []
                for b in plan:
                    ps = result.get(b.prefix)
                    if (ps is not None and model.pick_granularity(ps)
                            == "depth-first"):
                        # the class task re-counts its own candidates
                        metrics.candidates -= len(b.exts)
                        # parent-level sibling supports (for dEclat
                        # children): support of prefix[:-1] + (e,),
                        # frequent by the Apriori prune so present in
                        # ``result``
                        psup = tuple(result[b.prefix[:-1] + (e,)]
                                     for e in b.exts)
                        detach.append((b, ps, psup))
                    else:
                        keep.append(b)
                plan = keep
        with region(tr, "level.spawn", cat="level"):
            detached_tasks.extend(
                sched.spawn(detach_task, b, ps, psup,
                            attr=(b.key, b.prefix))
                for b, ps, psup in detach)
            metrics.buckets += len(plan)
            prio = delta.priority_of if delta is not None else None
            tenant = delta.tenant if delta is not None else None
            tasks = [sched.spawn(sweep_task, b, segments,
                                 attr=(b.key, b.prefix),
                                 priority=prio(b.prefix) if prio else 0.0,
                                 tenant=tenant)
                     for b in plan]
        return plan, tasks

    def _spawn_candidates(plan: List[Bucket], segments):
        """One task per candidate: a sweep of its prefix's bucket cut
        down to its one extension."""
        prio = delta.priority_of if delta is not None else None
        tenant = delta.tenant if delta is not None else None
        cands, tasks = [], []
        for b in plan:
            priority = prio(b.prefix) if prio else 0.0
            for e in b.exts:
                c = b.prefix + (e,)
                cands.append(c)
                tasks.append(sched.spawn(sweep_task,
                                         Bucket(b.key, b.prefix, (e,)),
                                         segments, attr=(b.key, c),
                                         priority=priority,
                                         tenant=tenant))
        return cands, tasks

    def delta_chunk_task(chunk: List[Bucket]
                         ) -> List[Tuple[Itemset, int]]:
        """Coalesced dirty-candidate burst: each bucket in the chunk
        becomes ONE tuple-prefix sweep over the pending segments, and
        the whole chunk is enqueued at once, so it coalesces into wide
        dispatcher flushes. No prefix bitmap is ever built host-side."""
        st = sched.worker_stats()
        disp = dispatchers[sched.worker_device()]
        futures = disp.submit_many(
            [((b.prefix if len(b.prefix) > 1 else b.prefix[0]),
              b.exts) for b in chunk],
            segments=delta.segments)
        counts_per_bucket = [f.result() for f in futures]
        st.sweeps_submitted += len(chunk)
        out: List[Tuple[Itemset, int]] = []
        rows = 0
        for b, counts in zip(chunk, counts_per_bucket):
            rows += len(b.prefix) + len(b.exts)
            out.extend((b.prefix + (e,), int(s))
                       for e, s in zip(b.exts, counts))
        st.rows_touched += rows
        st.bytes_swept += rows_to_bytes(rows, _seg_w(delta.segments))
        return out

    def _spawn_delta_chunks(plan: List[Bucket]) -> Callable[
            [], List[Tuple[Itemset, int]]]:
        """Spawn a handful of chunk tasks (≈4 per worker) over the
        already-classified dirty buckets instead of one task per
        bucket — per-task scheduler and future overhead is what made
        the delta path slower than the full path it was supposed to
        beat."""
        if not plan:
            return lambda: []
        metrics.buckets += len(plan)
        n_chunks = max(1, 4 * sched.n)
        size = max(1, -(-len(plan) // n_chunks))
        tasks = [sched.spawn(delta_chunk_task, plan[i:i + size],
                             attr=(plan[i].key, plan[i].prefix),
                             tenant=delta.tenant)
                 for i in range(0, len(plan), size)]

        def collect():
            _raise_task_errors(tasks)
            return [pair for t in tasks for pair in t.result]
        return collect

    def _spawn_sweeps(plan: List[Bucket], segments) -> Callable[
            [], List[Tuple[Itemset, int]]]:
        """Spawn sweeps for ``plan`` (bucket- or candidate-grained)
        and return a collector to call AFTER ``wait_all`` — fresh and
        dirty sweep sets share one level barrier. The collected counts
        cover ``segments`` only when restricted (the caller adds them
        to the known supports). A plain mine (no delta, no cluster)
        thresholds each bucket's counts here and pairs up only the
        frequent extensions; a refresh needs every swept support for
        ``delta.known`` and a cluster every pair for its exchange."""
        if cluster is not None:
            # task partition: every host plans the SAME global frontier
            # but sweeps only its owned prefixes; the level exchange
            # merges the counted pairs back so thresholds stay global
            plan = [b for b in plan if cluster.owns(b.prefix)]
        if not plan:
            return lambda: []
        if granularity in ("bucket", "auto"):
            plan, tasks = _spawn_buckets(plan, segments)

            def collect():
                _raise_task_errors(tasks)
                if delta is not None or cluster is not None:
                    return [(b.prefix + (e,), int(s))
                            for b, t in zip(plan, tasks)
                            for e, s in zip(b.exts, t.result)]
                out = []
                for b, t in zip(plan, tasks):
                    counts = t.result
                    for i in np.flatnonzero(
                            counts >= min_support).tolist():
                        out.append((b.prefix + (b.exts[i],),
                                    int(counts[i])))
                return out
        else:
            with region(tr, "level.spawn", cat="level"):
                cands, tasks = _spawn_candidates(plan, segments)

            def collect():
                _raise_task_errors(tasks)
                return [(c, int(t.result[0]))
                        for c, t in zip(cands, tasks)]
        return collect

    def _keep_frequent(level: List[Tuple[Itemset, int]]
                       ) -> List[Itemset]:
        """Threshold one level's counted candidates (or, on a plain
        mine, its already-thresholded pairs) into ``result``; the
        level's frequent itemsets, sorted."""
        frequent = []
        for c, s in level:
            if s >= min_support:
                result[c] = s
                frequent.append(c)
        frequent.sort()
        metrics.frequent += len(frequent)
        return frequent

    def _level_wait() -> None:
        with region(tr, "level.barrier", cat="idle"):
            if cluster is None:
                sched.wait_all()
            else:
                cluster.level_wait(sched)

    # driver-lane spans: each level's level.candidates, then, for a
    # level with candidates, level-k tiled into plan, spawn, barrier
    # and collect (the last thresholds and sorts)
    k = 2
    while frequent and k <= max_k:
        with region(tr, "level.candidates", cat="level"):
            # detached subtrees' itemsets never rejoin ``frequent``, so
            # the Apriori prune needs the full known-frequent membership
            # (the result dict is complete here: the level barrier
            # below also waited on every detached class task)
            plan = (gen_buckets(frequent, known_frequent=result)
                    if df_miner is not None else gen_buckets(frequent))
        if not plan:
            break
        with region(tr, f"level-{k}", cat="level") as level_args:
            n_cands = sum(len(b.exts) for b in plan)
            buckets_before = metrics.buckets
            metrics.levels += 1
            metrics.candidates += n_cands
            level: List[Tuple[Itemset, int]] = []
            if delta is None:
                collect = _spawn_sweeps(plan, None)
                _level_wait()
                with region(tr, "level.collect", cat="level"):
                    if df_miner is not None:
                        _raise_task_errors(detached_tasks)
                        df_miner.raise_errors()
                    level = collect()
                    if cluster is not None:
                        level = cluster.exchange(level)
                    frequent = _keep_frequent(level)
            else:
                with region(tr, "level.plan", cat="level"):
                    clean, dirty, fresh = delta.classify_buckets(plan)
                    level.extend(clean)         # clean: zero rows read
                    if cluster is None or cluster.host_id == 0:
                        # a loopback cluster SHARES the plan: bill its
                        # avoided-work counters once, not once per host
                        delta.reused += len(clean)
                        delta.swept_full += sum(len(b.exts)
                                                for b in fresh)
                        delta.swept_delta += sum(len(b.exts)
                                                 for b in dirty)
                    if cluster is not None:
                        dirty = [b for b in dirty
                                 if cluster.owns(b.prefix)]
                collect_fresh = _spawn_sweeps(fresh, delta.base_segments)
                with region(tr, "level.spawn", cat="level"):
                    collect_dirty = _spawn_delta_chunks(dirty)
                _level_wait()
                with region(tr, "level.collect", cat="level"):
                    if cluster is None:
                        for c, s in collect_fresh():
                            delta.known[c] = s
                            level.append((c, s))
                        for c, d in collect_dirty():
                            # delta over pending segs
                            s = delta.known[c] + d
                            delta.known[c] = s
                            level.append((c, s))
                    else:
                        mined = ([(c, s, True)
                                  for c, s in collect_fresh()]
                                 + [(c, d, False)
                                    for c, d in collect_dirty()])

                        def _apply(merged):
                            # runs ONCE per known-store (host 0 under
                            # loopback, where hosts share the plan):
                            # fold fresh supports and dirty deltas into
                            # ``known``, return the globally-
                            # thresholdable (itemset, support) pairs
                            out = []
                            for c, v, is_fresh in merged:
                                s = (v if is_fresh
                                     else delta.known[c] + v)
                                delta.known[c] = s
                                out.append((c, s))
                            return out

                        level.extend(cluster.exchange(mined,
                                                      update=_apply))
                    frequent = _keep_frequent(level)
            if level_args is not None:
                # materialized: (itemset, support) pairs the level
                # built — the frequent ones alone on a plain mine
                level_args.update(candidates=n_cands,
                                  buckets=metrics.buckets - buckets_before,
                                  materialized=len(level),
                                  frequent=len(frequent))
        k += 1


class _ClassMiner:
    """Barrier-free equivalence-class machinery: tasks spawn child
    classes. Shared by ``granularity="depth-first"`` (every root item
    is a class) and ``granularity="auto"`` (the levelwise driver
    detaches model-chosen prefix buckets into class tasks mid-run).

    A task = one equivalence class (P, E) owning an arena handle for
    P's row: it sweeps the |E| extensions through the dispatcher,
    records frequent extensions, then for each frequent sibling e
    (except the last) materializes the child row ONCE into the arena
    and spawns the child class (P+(e,), {frequent siblings > e}) with
    the new handle. The child never recomputes a prefix intersection —
    the handoff replaces the LRU cache entirely. Eclat shape: no global
    candidate generation, no Apriori cross-class prune (supports are
    identical; a few extra infrequent candidates get swept).

    Hybrid representation (``model`` set): the handed row's
    representation is chosen per child by the density cost model —
    dense word-column (``materialize``), sorted tid-list
    (``push_tids``), or dEclat diffset anchored on P
    (``push_diffset``). A sparse P is swept by the gather-intersect
    path, which returns |payload ∩ e| — for a tid-list that IS the
    support, for a diffset the class converts it with the
    parent-sibling supports handed down at spawn
    (``support = psup[e] - |diff ∩ e|``). Sparse children of a sparse
    parent are carved out of P's explicit tid set (``resolve_tids``,
    reconstructed once per class), so no dense intermediate is built.

    Memory bound: a handed row is live from materialize until the
    child task's ``finally`` releases it (including on task error — an
    error may NOT leak the refcount, or the arena slot never recycles).
    With depth-first drain order (scheduler) and spawn-onto-own-worker
    placement, each worker holds O(depth × branching) live rows instead
    of a whole level's worth; the peak is measured by the arena and
    reported as ``metrics.peak_retained_bitmaps`` /
    ``peak_bytes_retained``.

    With a ``delta`` plan each class splits its extensions into clean
    known (support looked up, zero rows), dirty known (delta sweep over
    the pending segments only) and fresh (full sweep), and a child
    subtree is recursed into ONLY when some candidate in it is fresh or
    dirty — a clean subtree's results are already exact in
    ``delta.known``, so whole equivalence classes are skipped without
    touching a row (the invalidated-classes-only re-mine). Diffset
    children are disabled under delta (``allow_diffset=False``): a
    dirty diffset sweep would need |parent ∩ e ∩ pending|, which the
    delta path doesn't carry — tid-list children delta-sweep fine (the
    backend searchsorts the payload into the pending segments' tid
    windows)."""

    def __init__(self, store, dispatchers, min_support, max_k, sched,
                 metrics, result, delta=None, model=None, cluster=None):
        self.store = store
        self.dispatchers = dispatchers
        self.min_support = min_support
        self.max_k = max_k
        self.sched = sched
        self.metrics = metrics
        self.result = result
        self.delta = delta
        self.model = model
        self.cluster = cluster    # multi-host: root classes partition
                                  # by owner, sweeps reduce per flush
        self.n_w = store.n_words
        self.lock = threading.Lock()
        self.all_tasks: List = []
        self._obs = 0     # observe() sampling counter (racy is fine)

    def needs_visit(self, cprefix: Itemset, csibs) -> bool:
        """A class subtree can contain changed or never-swept itemsets
        only if one of ITS OWN candidates is fresh or dirty: deeper
        dirt implies a dirty candidate here (X ⊆ dirty-items ⇒ every
        sub-candidate too), and deeper freshness implies a frequency
        status change here (supports only change where dirt is)."""
        delta = self.delta
        for e in csibs:
            c = cprefix + (e,)
            if delta.known.get(c) is None or delta.is_dirty(c):
                return True
        return False

    def _make_child(self, ph, e, csup, crep, shard, ptids, bits):
        """One child handoff row in the model-picked representation.
        Returns (handle, handoff-bytes-read, is-sparse). ``ptids`` is
        P's explicit tid set and ``bits`` its membership row in ext e —
        both resolved/gathered ONCE per class by the caller; only the
        dense-parent materialize path runs without them."""
        store = self.store
        if crep == "bitmap" and store.rep_of(ph) == tidlist.REP_BITMAP:
            return (store.materialize(ph, e, shard=shard),
                    self.n_w * 4, False)
        cov = min(store.cover_of(ph), store.cover_of(e))
        read = len(ptids) * 4 * 2      # bits gather + payload carve
        if crep == "bitmap":
            # force="bitmap" never lands here; under "auto" a dense
            # child of a sparse parent can't win the cost model
            # (child support ≤ parent support), so this is the forced
            # densify corner only
            ch = store.push(tidlist.tids_to_bitmap(ptids[bits],
                                                   self.n_w),
                            shard=shard, cover=cov)
            return ch, read + self.n_w * 4, False
        if crep == "tidlist":
            ch = store.push_tids(ptids[bits], shard=shard, cover=cov)
        else:
            ch = store.push_diffset(ptids[~bits], anchor=ph,
                                    support=csup, shard=shard,
                                    cover=cov)
        return ch, read, True

    def class_task(self, prefix: Itemset, ph: int,
                   exts: Tuple[int, ...], psup: Tuple[int, ...],
                   own_support: int, owned: bool,
                   ptids_hint=None) -> None:
        store, sched, delta = self.store, self.sched, self.delta
        min_support, model = self.min_support, self.model
        children: List[Tuple[Itemset, int, Tuple[int, ...],
                             Tuple[int, ...], int, object]] = []
        try:
            k = len(prefix) + 1                 # size of swept itemsets
            shard = sched.worker_device()
            st = sched.worker_stats()
            disp = self.dispatchers[shard]
            rep = store.rep_of(ph)
            sparse = rep != tidlist.REP_BITMAP
            payload = len(store.tids_of(ph)) if sparse else 0
            is_diff = rep == tidlist.REP_DIFFSET
            supports: List[Tuple[int, int]] = []     # (ext, support)
            if delta is None:
                st.sweeps_submitted += 1
                counts = disp.sweep(ph, exts, desc=prefix)
                if is_diff:
                    # dEclat arithmetic: the backend counted
                    # |diff ∩ e|; the parent's sibling supports
                    # handed down at spawn turn it into support
                    supports = [(e, psup[j] - int(s)) for j, (e, s)
                                in enumerate(zip(exts, counts))]
                else:
                    supports = [(e, int(s))
                                for e, s in zip(exts, counts)]
                swept = len(exts)
                fresh_e: List[int] = []
                dirty_e: List[int] = []
            else:
                fresh_e, dirty_e = [], []
                for e in exts:
                    c = prefix + (e,)
                    ks = delta.known.get(c)
                    if ks is None:
                        fresh_e.append(e)
                    elif delta.is_dirty(c):
                        dirty_e.append(e)
                    else:
                        supports.append((e, ks))    # clean: zero rows
                n_clean = len(supports)
                # both sweeps go out before either result is awaited,
                # so they share a dispatcher flush; fresh sweeps read
                # the generation-boundary segments, never ones an
                # overlapped ingest appended mid-refresh
                ffut = (disp.submit(ph, tuple(fresh_e),
                                    segments=delta.base_segments,
                                    desc=prefix)
                        if fresh_e else None)
                dfut = (disp.submit(ph, tuple(dirty_e),
                                    segments=delta.segments,
                                    desc=prefix)
                        if dirty_e else None)
                updates: Dict[Itemset, int] = {}
                if ffut is not None:
                    st.sweeps_submitted += 1
                    for e, s in zip(fresh_e, ffut.result()):
                        updates[prefix + (e,)] = int(s)
                        supports.append((e, int(s)))
                if dfut is not None:
                    st.sweeps_submitted += 1
                    for e, d in zip(dirty_e, dfut.result()):
                        c = prefix + (e,)
                        s = delta.known[c] + int(d)
                        updates[c] = s
                        supports.append((e, s))
                with delta.lock:
                    delta.known.update(updates)
                    delta.swept_full += len(fresh_e)
                    delta.swept_delta += len(dirty_e)
                    delta.reused += n_clean
                supports.sort()       # merged lists back to ext order
                swept = len(fresh_e) + len(dirty_e)
            if model is not None and supports:
                # sampled EWMA: the gauge steers granularity detach
                # decisions, not per-child picks — every 4th class is
                # plenty of signal and trims the per-class Python floor
                self._obs += 1
                if (self._obs & 3) == 0:
                    model.observe([s for _, s in supports])
            freq = [(e, s) for e, s in supports if s >= min_support]
            sibs = [e for e, _ in freq]         # ascending (exts sorted)
            child_bytes = 0
            child_sparse_bytes = 0
            if k < self.max_k and len(freq) > 1:
                # pick every child's representation first, so the carve
                # work (P's explicit tid set + its membership bits in
                # each child ext) resolves and gathers ONCE per class
                plan = []             # (sibling idx, ext, csup, crep)
                for i, (e, csup) in enumerate(freq[:-1]):
                    if delta is not None and not self.needs_visit(
                            prefix + (e,), tuple(sibs[i + 1:])):
                        continue      # clean subtree: known is exact
                    plan.append((i, e, csup,
                                 "bitmap" if model is None
                                 else model.pick_child_rep(
                                     own_support, csup,
                                     allow_diffset=delta is None)))
                ptids = None  # P's tid set, resolved at most once
                bits: Dict[int, np.ndarray] = {}  # ext -> P's bits in it
                carve = [e for _, e, _, crep in plan
                         if crep != "bitmap" or sparse]
                if carve:
                    if is_diff:
                        # dEclat chain: the spawner handed P's parent
                        # tid set down, so resolution is ONE sorted
                        # difference, not a chain walk
                        diff = store.tids_of(ph)
                        ptids = (tidlist.sorted_difference(
                                     ptids_hint, diff)
                                 if ptids_hint is not None
                                 else store.resolve_tids(ph))
                    elif sparse:
                        ptids = store.tids_of(ph)
                    else:
                        ptids = store.resolve_tids(ph)  # billed
                    bits = dict(zip(carve,
                                    store.gather_bits_rows(ptids, carve)))
                for i, e, csup, crep in plan:
                    ch, read, ch_sparse = self._make_child(
                        ph, e, csup, crep, shard, ptids, bits.get(e))
                    child_bytes += read
                    if ch_sparse:
                        child_sparse_bytes += read
                    children.append((prefix + (e,), ch,
                                     tuple(sibs[i + 1:]),
                                     tuple(s for _, s in freq[i + 1:]),
                                     csup,
                                     ptids if crep == "diffset"
                                     else None))
            if delta is None:
                rows = class_rows_touched(len(exts), len(children))
                st.rows_touched += rows
                if sparse:
                    # gather-intersect passes: the payload once per
                    # extension (plus once for itself), never W words —
                    # plus the measured child-handoff reads
                    sb = payload * 4 * (1 + len(exts))
                    st.bytes_swept += sb + child_bytes
                    st.sparse_bytes_swept += sb + child_sparse_bytes
                else:
                    st.bytes_swept += rows_to_bytes(rows, self.n_w)
                    st.sparse_bytes_swept += child_sparse_bytes
            else:
                # only what was actually read: the parent-handed prefix
                # row (when any sweep ran), swept extension rows (dirty
                # ones only over the pending segments' words), and
                # materialized child handoffs
                seg_w = sum(store.seg_words(g) for g in delta.segments)
                full_rows = ((1 if swept else 0) + len(fresh_e)
                             + len(children))
                st.rows_touched += full_rows + len(dirty_e)
                if sparse:
                    sb = (payload * 4 * (1 + len(fresh_e)
                                         + len(dirty_e)) + child_bytes)
                    st.bytes_swept += sb
                    st.sparse_bytes_swept += sb
                else:
                    st.bytes_swept += (rows_to_bytes(full_rows,
                                                     self.n_w)
                                       + rows_to_bytes(len(dirty_e),
                                                       seg_w))
                    st.sparse_bytes_swept += child_sparse_bytes
            if swept or delta is None:
                if sparse:
                    st.sparse_sweeps += 1
                else:
                    st.dense_sweeps += 1
            with self.lock:
                metrics = self.metrics
                metrics.buckets += 1
                metrics.candidates += len(exts)
                metrics.levels = max(metrics.levels, k - 1)
                metrics.frequent += len(freq)
                for e, s in freq:
                    self.result[prefix + (e,)] = s
            spawned = []
            while children:
                cprefix, ch, csibs, cpsup, csup, chint = children[0]
                spawned.append(self.spawn(cprefix, ch, csibs, cpsup,
                                          csup, True, chint))
                children.pop(0)       # ownership moved to the child task
            if spawned:
                with self.lock:
                    self.all_tasks.extend(spawned)
        except BaseException:
            # refcount hygiene on error: materialized handles whose
            # child tasks never spawned must release here or the rows
            # leak for the rest of the run
            for _, ch, _, _, _, _ in children:
                store.release(ch)
            raise
        finally:
            if owned:
                store.release(ph)

    def spawn(self, prefix: Itemset, ph: int, exts, psup,
              own_support: int, owned: bool, ptids_hint=None):
        delta = self.delta
        return self.sched.spawn(
            self.class_task, prefix, ph, exts, psup, own_support, owned,
            ptids_hint,
            attr=(itemset_hash(prefix), prefix), depth=len(prefix),
            priority=(delta.priority_of(prefix)
                      if delta is not None and delta.priority_of
                      else 0.0),
            tenant=delta.tenant if delta is not None else None,
            handles=(ph,) if owned else ())

    def spawn_roots(self, frequent, result) -> None:
        """One class per root item (the depth-first driver). Root
        classes hand the pinned base row's handle (== item id —
        nothing materialized, nothing retained); their sibling
        supports are the level-1 supports."""
        if self.max_k < 2 or len(frequent) < 2:
            return
        items = [p[0] for p in frequent]        # sorted singleton items
        sup = {p[0]: result[p] for p in frequent}
        for i, it in enumerate(items[:-1]):
            sibs = tuple(items[i + 1:])
            if (self.cluster is not None
                    and not self.cluster.owns((it,))):
                continue              # a peer host mines this subtree
            if self.delta is not None and not self.needs_visit((it,),
                                                               sibs):
                continue              # clean root class: skip entirely
            t = self.spawn((it,), it, sibs,
                           tuple(sup[e] for e in sibs), sup[it], False)
            with self.lock:   # already-running roots append concurrently
                self.all_tasks.append(t)

    def raise_errors(self) -> None:
        with self.lock:
            tasks = list(self.all_tasks)
        _raise_task_errors(tasks)


def _mine_depth_first(store, dispatchers, min_support, max_k, sched,
                      metrics, result, frequent, delta=None,
                      model=None, cluster=None):
    """Barrier-free engine driver: see :class:`_ClassMiner`. Under a
    cluster the root classes partition by owner host (global counts
    from the per-flush reduction make every subtree decision
    host-independent) and ONE terminal exchange replicates the mined
    itemsets — barrier-free within the whole subtree forest, exactly
    one collective at the end."""
    miner = _ClassMiner(store, dispatchers, min_support, max_k, sched,
                        metrics, result, delta=delta, model=model,
                        cluster=cluster)
    miner.spawn_roots(frequent, result)
    if cluster is None:
        sched.wait_all()                        # the ONLY wait
        miner.raise_errors()
    else:
        cluster.level_wait(sched)
        miner.raise_errors()
        mined = [(c, s) for c, s in result.items() if len(c) > 1]
        for c, s in cluster.exchange(mined):
            result[c] = s


def mine_serial(bitmaps: np.ndarray, min_support: int, max_k: int = 8
                ) -> Dict[Itemset, int]:
    """Single-threaded reference (no scheduler)."""
    result, frequent = _level1(bitmaps, min_support)
    k = 2
    while frequent and k <= max_k:
        cands = gen_candidates(frequent)
        frequent = []
        for c in cands:
            s = tidlist.support_of(bitmaps[list(c)])
            if s >= min_support:
                result[c] = s
                frequent.append(c)
        frequent.sort()
        k += 1
    return result
