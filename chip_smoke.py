#!/usr/bin/env python3
"""Bring-up smoke run of the whole main path on one TPU chip.

Drives the system once through the entry points a user calls, in one
process, and checks every answer exactly:

  1. device check   -- jax must report a TPU; nothing falls back to CPU
  2. kernel parity  -- bitmap_join_many / gather_intersect_many compiled
                       ("pallas-jit") vs the numpy references, and the
                       compiled program holds the Mosaic kernel
  3. batch mine, sparse data  -- t10i4 x5 (T10I4D100K shape), bucket
  4. batch mine, dense data   -- mushroom x16, depth-first
  5. sparse kernel in flushes -- retail x8, representation="sparse"
  6. stream and serve -- StreamingMiner ingest/refresh + PatternServer
                         support / support_many / device top-k

Every mine is compared with ``mine_serial``, every query with brute
force over the packed bitmaps. A failed check raises; the last line,
printed only when every phase passed, is one JSON object naming the
device. Per-phase counts and times on earlier lines are orientation,
not measurements.

    python chip_smoke.py                 # one chip (the default)
    python chip_smoke.py --four-chips    # only the 4-chip mesh phase
    JAX_PLATFORMS=cpu python chip_smoke.py --rehearse
                                         # same phases, toy sizes, Pallas
                                         # interpreter; prints no ok line
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))


# ------------------------------------------------------------------ sizes --
# (profile, scale) per phase, the batch mines' depth cap (None: mined to
# completion), the streaming miner's configured depth (its queries are
# itemsets longer than that), and the kernel parity shape (B, E, W, S);
# --rehearse swaps in toy sizes
FULL = {"sparse": ("t10i4", 5), "dense": ("mushroom", 16),
        "sparse_rep": ("retail", 8), "stream": ("retail", 8),
        "mesh": ("retail", 8), "mine_cap": None, "stream_max_k": 8,
        "kernel": (32, 128, 4096, 1024)}
TOY = {"sparse": ("t10i4", 1), "dense": ("mushroom", 1),
       "sparse_rep": ("retail", 1), "stream": ("retail", 1),
       "mesh": ("retail", 1), "mine_cap": 3, "stream_max_k": 3,
       "kernel": (4, 8, 64, 128)}


class Compiles:
    """Counts, through jax.monitoring, the XLA programs a run builds and
    their seconds (``n``/``secs``: compiled, or loaded from the
    persistent cache) and the persistent-cache loads among them
    (``hits``); ``n - hits`` programs were compiled afresh."""

    def __init__(self):
        import jax
        self.n = 0
        self.secs = 0.0
        self.hits = 0

        def on_duration(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.n += 1
                self.secs += duration

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def snap(self):
        return (self.n, self.secs, self.hits)


def report(name, t0, compiles, c0, **kv):
    n, secs, hits = compiles.snap()
    fields = " ".join(f"{k}={v}" for k, v in kv.items())
    print(f"[{name}] wall_s={time.perf_counter() - t0} "
          f"compiles={n - c0[0]} compile_s={secs - c0[1]} "
          f"cache_hits={hits - c0[2]} {fields}", flush=True)


def check(cond, what):
    if not cond:
        raise AssertionError(what)


def load_db(name, scale, seed):
    from repro.core.tidlist import pack_database
    from repro.data.transactions import load, min_support_count
    db, prof = load(name, seed=seed, scale=scale)
    n_items = prof.n_dense_items if prof.kind == "dense" else prof.n_items
    bm, counts = pack_database(db, n_items, return_counts=True)
    return db, prof, n_items, bm, counts, min_support_count(prof, db)


# ----------------------------------------------------------------- phases --
def phase_kernels(sizes, mode, seed, rehearse, compiles):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.tidlist import popcount32
    from repro.kernels.bitmap_join.ops import bitmap_join_many
    from repro.kernels.gather_intersect.ops import gather_intersect_many
    from repro.kernels.gather_intersect.ref import gather_intersect_many_np

    t0, c0 = time.perf_counter(), compiles.snap()
    b, e, w, s = sizes
    rng = np.random.default_rng(seed)
    prefixes = rng.integers(0, 2 ** 32, (b, w), dtype=np.uint32)
    exts = rng.integers(0, 2 ** 32, (b, e, w), dtype=np.uint32)
    tids = np.full((b, s), -1, np.int32)
    for i in range(b):                       # ragged sorted tid rows
        n = int(rng.integers(0, s + 1))
        tids[i, :n] = np.sort(rng.choice(32 * w, n, replace=False))
    want_dense = popcount32(exts & prefixes[:, None, :]).sum(axis=2)
    want_sparse = gather_intersect_many_np(tids, exts)
    dense = jax.jit(lambda p, x: bitmap_join_many(p, x, mode=mode))
    sparse = jax.jit(lambda t, x: gather_intersect_many(t, x, mode=mode))
    p_d, x_d, t_d = (jnp.asarray(prefixes), jnp.asarray(exts),
                     jnp.asarray(tids))
    got_dense = np.asarray(dense(p_d, x_d))
    got_sparse = np.asarray(sparse(t_d, x_d))
    check(np.array_equal(got_dense, want_dense),
          "bitmap_join_many differs from the numpy reference")
    check(np.array_equal(got_sparse, want_sparse),
          "gather_intersect_many differs from the numpy reference")
    if not rehearse:
        for fn, args in ((dense, (p_d, x_d)), (sparse, (t_d, x_d))):
            text = fn.lower(*args).compile().as_text()
            check("tpu_custom_call" in text,
                  "compiled sweep holds no tpu_custom_call: the kernel "
                  "did not run")
    report("kernels", t0, compiles, c0, shape=f"B{b}xE{e}xW{w}xS{s}",
           mode=mode, exact=True)


def phase_mine(tag, sizes, kw, backend, seed, compiles, cap):
    from repro.core.fpm import mine, mine_serial

    t0, c0 = time.perf_counter(), compiles.snap()
    name, scale = sizes
    db, prof, n_items, bm, counts, ms = load_db(name, scale, seed)
    max_k = n_items if cap is None else cap   # n_items: no itemset longer
    t_data = time.perf_counter() - t0
    t1 = time.perf_counter()
    ref = mine_serial(bm, ms, max_k=max_k)
    t_serial = time.perf_counter() - t1
    res, met = mine(bm, ms, backend=backend, arena="jax", max_k=max_k,
                    item_counts=counts, **kw)
    check(res == ref, f"{tag}: mine() differs from mine_serial")
    report(tag, t0, compiles, c0, dataset=f"{name}x{scale}",
           transactions=len(db), items=n_items, min_support=ms,
           max_k_cut=cap, longest=max(map(len, res)), frequent=len(res),
           setup_s=t_data,
           serial_s=t_serial, mine_s=met.wall_s, flushes=met.flushes,
           batch_occupancy=met.batch_occupancy, h2d_bytes=met.h2d_bytes,
           dense_sweeps=met.dense_sweeps, sparse_sweeps=met.sparse_sweeps)
    return met


def brute_support(bm, x):
    from repro.core import tidlist
    return int(tidlist.support_of(bm[list(x)]))


def reference_top_k(supports, prefix, k):
    plen = len(prefix)
    rows = [(x, s) for x, s in supports.items()
            if len(x) > plen and x[:plen] == prefix]
    rows.sort(key=lambda r: (-r[1], r[0]))
    return rows[:k]


def phase_stream(sizes, backend, seed, rehearse, compiles, max_k):
    import numpy as np

    from repro.core import streaming
    from repro.core.fpm import mine_serial
    from repro.core.streaming import PatternServer, StreamingMiner

    t0, c0 = time.perf_counter(), compiles.snap()
    name, scale = sizes
    db, prof, n_items, bm, _, ms = load_db(name, scale, seed)
    t_data = time.perf_counter() - t0
    ref = mine_serial(bm, ms, max_k=max_k)
    cut = int(0.9 * len(db))
    init, tail = db[:cut], db[cut:]
    per = -(-len(tail) // 3)
    sm = StreamingMiner(n_items, prof.support, initial_db=init,
                        backend=backend, arena="jax", max_k=max_k)
    try:
        walls = [sm.refresh().wall_s]
        h2d = 0
        for i in range(3):
            ing = sm.ingest(tail[i * per:(i + 1) * per])
            rep = sm.refresh()
            walls.append(rep.wall_s)
            h2d += ing.h2d_bytes + rep.h2d_bytes
        snap = sm.snapshot
        check(snap.n_transactions == len(db) and snap.min_support == ms,
              "stream: final generation does not cover the database")
        check(dict(snap.supports) == ref,
              "stream: final snapshot differs from mine_serial")
        t_mined, c_mined = time.perf_counter(), compiles.snap()

        srv = PatternServer(sm)
        supports = dict(snap.supports)
        if rehearse:                          # toy snapshot: force the
            streaming.TOPK_DEVICE_MIN = 0     # device top-k path
        check(len(supports) >= streaming.TOPK_DEVICE_MIN,
              f"stream: {len(supports)} itemsets < TOPK_DEVICE_MIN "
              f"({streaming.TOPK_DEVICE_MIN}); device top-k would not run")
        # known itemsets answer from the snapshot
        for x in sorted(supports, key=len)[-8:]:
            check(srv.support(x) == supports[x] == brute_support(bm, x),
                  f"support{x} wrong")
        # itemsets longer than max_k were never counted: they sweep
        rng = np.random.default_rng(seed)
        freq1 = [x[0] for x in supports if len(x) == 1]
        longest = max(supports, key=lambda x: (len(x), supports[x]))
        queries = []
        for j in range(24):
            extra = rng.choice(freq1, size=min(len(freq1), 2 + j % 3),
                               replace=False)
            queries.append(tuple(sorted(set(longest) | set(map(int,
                                                               extra)))))
        queries += [tuple(sorted(map(int, rng.choice(
            n_items, size=max_k + 1, replace=False)))) for _ in range(8)]
        queries = [q for q in queries if len(q) > max_k]
        check(queries, "stream: no query longer than max_k")
        q0 = sm.query_sweeps
        got = srv.support_many(queries)
        want = [brute_support(bm, q) for q in queries]
        check(got == want, "support_many differs from brute force")
        check(sm.query_sweeps - q0 == len(set(queries)),
              "support_many did not sweep its unknown itemsets")
        # device top-k (snapshot >= TOPK_DEVICE_MIN itemsets)
        top1 = sorted(supports, key=lambda x: (-supports[x], x))[0]
        for prefix, k in (((), 10), ((), 100), ((top1[0],), 20)):
            check(srv.top_k(prefix, k)
                  == reference_top_k(supports, prefix, k),
                  f"top_k{prefix, k} differs from the host ranking")
        report("stream", t0, compiles, c0, dataset=f"{name}x{scale}",
               transactions=len(db), min_support=ms, max_k=max_k,
               frequent=len(supports), setup_s=t_data,
               refresh_s=[round(x, 6) for x in walls],
               serve_s=time.perf_counter() - t_mined,
               serve_compiles=compiles.snap()[0] - c_mined[0],
               serve_compile_s=compiles.snap()[1] - c_mined[1],
               h2d_bytes=h2d,
               queries_swept=len(queries), query_sweeps=sm.query_sweeps)
    finally:
        sm.close()


def phase_mesh(sizes, backend, seed, compiles, cap):
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from repro.core.fpm import mine, mine_serial
    from repro.core.streaming import StreamingMiner

    t0, c0 = time.perf_counter(), compiles.snap()
    devs = jax.devices()[:4]
    check(len(devs) == 4, f"--four-chips needs 4 devices, found "
                          f"{len(jax.devices())}")
    mesh = Mesh(np.array(devs), ("data",))
    name, scale = sizes
    db, prof, n_items, bm, counts, ms = load_db(name, scale, seed)
    max_k = n_items if cap is None else cap
    ref = mine_serial(bm, ms, max_k=max_k)
    kw = dict(backend=backend, arena="jax", max_k=max_k,
              granularity="bucket", policy="clustered", item_counts=counts)
    one, met1 = mine(bm, ms, **kw)
    res, met = mine(bm, ms, mesh=mesh, **kw)
    check(one == ref, "mesh phase: one-chip mine differs from mine_serial")
    check(res == ref, "mesh phase: 4-chip mine differs from mine_serial")
    check(met.n_devices == 4, "mesh phase: run did not span 4 shards")
    # the arena mirror of each shard lives on that shard's own chip
    sm = StreamingMiner(n_items, ms, initial_db=db, backend=backend,
                        arena="jax", max_k=max_k, mesh=mesh)
    try:
        sm.refresh()
        check(dict(sm.snapshot.supports) == ref,
              "mesh phase: 4-chip streaming refresh differs")
        placed = []
        for s, d in enumerate(devs):
            mirror = sm.arena.device_rows(s)
            check(mirror.devices() == {d},
                  f"shard {s} mirror on {mirror.devices()}, not {d}")
            placed.append(str(d.id))
    finally:
        sm.close()
    occ = "/".join(f"{r['batch_occupancy']:.3f}" for r in met.per_device)
    report("mesh4", t0, compiles, c0, dataset=f"{name}x{scale}",
           transactions=len(db), max_k_cut=cap, frequent=len(res),
           mine1_s=met1.wall_s, mine4_s=met.wall_s, flushes=met.flushes,
           d2d_bytes=met.d2d_bytes, migrations=met.migrations,
           per_device_occupancy=occ, h2d_bytes=met.h2d_bytes,
           mirrors_on_devices="/".join(placed))


# ------------------------------------------------------------------- main --
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-chip mesh phase and its "
                         "one-chip and serial comparisons")
    ap.add_argument("--rehearse", action="store_true",
                    help="toy sizes with the Pallas interpreter on any "
                         "backend; never prints the ok line")
    args = ap.parse_args(argv)

    from repro.compile_cache import enable_compile_cache
    cache = enable_compile_cache()

    import jax
    devs = jax.devices()
    d0 = devs[0]
    print(f"device: platform={d0.platform} kind={d0.device_kind} "
          f"count={len(devs)} compile_cache={cache}", flush=True)
    if d0.platform != "tpu" and not args.rehearse:
        print(f"chip_smoke: no TPU: jax reports platform "
              f"{d0.platform!r} ({d0.device_kind}); nothing falls back",
              file=sys.stderr)
        return 1
    sizes = TOY if args.rehearse else FULL
    backend = "pallas-interpret" if args.rehearse else "pallas-jit"
    cap = sizes["mine_cap"]
    compiles = Compiles()
    t_all = time.perf_counter()

    if args.four_chips:
        phase_mesh(sizes["mesh"], backend, args.seed, compiles, cap)
    else:
        phase_kernels(sizes["kernel"], backend, args.seed, args.rehearse,
                      compiles)
        mets = [
            phase_mine("mine_sparse", sizes["sparse"],
                       dict(granularity="bucket", policy="clustered"),
                       backend, args.seed, compiles, cap),
            phase_mine("mine_dense", sizes["dense"],
                       dict(granularity="depth-first"),
                       backend, args.seed, compiles, cap),
            phase_mine("mine_sparse_rep", sizes["sparse_rep"],
                       dict(granularity="depth-first",
                            representation="sparse"),
                       backend, args.seed, compiles, cap),
        ]
        dense = sum(m.dense_sweeps for m in mets)
        sparse = sum(m.sparse_sweeps for m in mets)
        check(dense > 0 and sparse > 0,
              f"kernel sweeps dense={dense} sparse={sparse}: both "
              f"kernels must run inside real flushes")
        phase_stream(sizes["stream"], backend, args.seed, args.rehearse,
                     compiles, sizes["stream_max_k"])
    n, secs, hits = compiles.snap()
    print(f"[total] wall_s={time.perf_counter() - t_all} compiles={n} "
          f"compile_s={secs} cache_hits={hits}", flush=True)
    if args.rehearse:
        print("rehearsal passed (no ok line: not a chip run)")
        return 0
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
