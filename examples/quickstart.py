"""Quickstart: the paper's system in 60 seconds.

1. Generate a transaction database (FIMI-profile synthetic).
2. Mine it with the Cilk-style policy, then the clustered policy, at
   candidate granularity (one single-extension sweep per task — the
   paper's §2 setting) and show the locality metrics that explain the difference
   (the Fig. 1 + Table 1 story).
3. Re-mine at bucket granularity: one task per (k-1)-prefix, the prefix
   intersection computed once, all extensions swept in one vectorized
   call through the join backend — the same locality, made structural.
4. Re-mine depth-first: barrier-free equivalence-class recursion where
   each task spawns its child classes and hands each child its already-
   intersected prefix bitmap — no barriers, no prefix recomputation,
   the LRU cache vestigial (zero misses).

Run:  PYTHONPATH=src python examples/quickstart.py
      (optionally: --backend pallas-interpret --arena jax
       --max-batch 16 --flush-us 500)
"""
import argparse
import sys
sys.path.insert(0, "src")

from repro.compile_cache import enable_compile_cache
from repro.core.fpm import mesh_over_devices, mine, mine_serial
from repro.core.tidlist import pack_database
from repro.data.transactions import load


def main():
    ap = argparse.ArgumentParser(description="FPM quickstart")
    ap.add_argument("--backend", default="auto",
                    help="join backend: auto|numpy|pallas-interpret|"
                         "pallas-jit")
    ap.add_argument("--arena", default="auto",
                    choices=["auto", "numpy", "jax"],
                    help="bitmap arena backing (device residency)")
    ap.add_argument("--max-batch", type=int, default=32,
                    help="sweep dispatcher: max requests per launch")
    ap.add_argument("--flush-us", type=float, default=200.0,
                    help="sweep dispatcher: straggler wait before a "
                         "partial flush")
    ap.add_argument("--mesh", type=int, default=0,
                    help="shard the run over N devices (logical shards "
                         "on a 1-device host); 0 = shared-memory")
    args = ap.parse_args()
    knobs = dict(backend=args.backend, arena=args.arena,
                 max_batch=args.max_batch, flush_us=args.flush_us,
                 mesh=mesh_over_devices(args.mesh))

    db, prof = load("chess", seed=0)
    bitmaps = pack_database(db, prof.n_dense_items)
    min_support = int(prof.support * len(db))
    print(f"synthetic 'chess' profile: {len(db)} transactions, "
          f"{prof.n_dense_items} items, min_support={min_support}")

    ref = mine_serial(bitmaps, min_support, max_k=4)
    print(f"serial Apriori: {len(ref)} frequent itemsets\n")

    for policy in ("cilk", "clustered"):
        res, met = mine(bitmaps, min_support, policy=policy,
                        n_workers=4, max_k=4, granularity="candidate",
                        **knobs)
        assert res == ref
        s = met.scheduler
        print(f"[{policy:9s}] wall={met.wall_s:6.2f}s  "
              f"prefix-cache hit rate={met.cache_hit_rate:6.1%}  "
              f"steals={int(s['steals']):5d}  "
              f"tasks/steal={s['tasks_per_steal']:.2f}")

    print("\nThe clustered policy runs tasks that share a (k-1)-prefix "
          "back-to-back\non one worker, so the prefix intersection is "
          "computed once and reused —\nthe paper's dTLB/IPC win, "
          "observable here as the cache-hit-rate gap.\n")

    for gran in ("candidate", "bucket", "depth-first"):
        res, met = mine(bitmaps, min_support, policy="clustered",
                        n_workers=4, max_k=4, granularity=gran, **knobs)
        assert res == ref
        print(f"[granularity={gran:11s}] wall={met.wall_s:6.2f}s  "
              f"tasks={int(met.scheduler['tasks_run']):6d}  "
              f"rows touched={met.rows_touched:8d}  "
              f"cache misses={met.cache_misses:6d}  "
              f"batch occupancy={met.batch_occupancy:5.2f}  "
              f"h2d={met.h2d_bytes:8d}B  "
              f"peak retained bitmaps={met.peak_retained_bitmaps}")

    print("\nBucket granularity makes the bucket the unit of task "
          "execution: the\nprefix intersection happens once per bucket "
          "and the extensions are swept\nthrough one handle-based "
          "request on the sweep dispatcher, which coalesces\nmany "
          "workers' buckets into one batched multi-prefix kernel "
          "launch (numpy\nufuncs here; the Pallas bitmap_join_many "
          "kernel on TPU) — fewer rows\ntouched, fewer tasks, same "
          "supports. Every bitmap lives in one\nrefcounted arena, so "
          "the device sees ~one initial upload (h2d above)\ninstead "
          "of per-sweep transfers.\n\n"
          "Depth-first granularity goes barrier-free: each class task "
          "spawns its\nchild equivalence classes onto its own worker "
          "and hands each child the\nalready-intersected prefix∧ext "
          "arena handle, so no prefix is ever\nrecomputed (cache "
          "misses: zero) and only one terminal wait remains. The\n"
          "price is the retained-bitmap peak printed above — bounded "
          "by depth-first\ndrain order, and measured.")


if __name__ == "__main__":
    enable_compile_cache()
    main()
