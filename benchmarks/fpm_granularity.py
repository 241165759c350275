"""A/B/C: task granularity — one task per candidate vs one task per
prefix-bucket (level-synchronous vectorized sweep) vs barrier-free
depth-first equivalence-class recursion with parent→child bitmap
handoff. Same policies, same supports; the contrast is wall-clock,
measured locality traffic (rows-touched / bytes-swept), prefix-cache
misses (the handoff makes the LRU cache vestigial: depth-first shows
cache_misses == 0), and the depth-first engine's retained-bitmap peak.

This is the shared-memory engine's version of the clustered-vs-round-
robin placement contrast in benchmarks/fpm_distributed.py: the bucket
engine turns the clustered policy's incidental cache locality into
structure, and the depth-first engine removes the remaining inter-level
barriers plus every prefix recomputation.

Also measures the arena/dispatcher plumbing: per-run batch occupancy
(sweep requests per flush — asserted > 1 under --smoke so the
dispatcher cannot silently degrade to one-bucket launches) and a
repeated-sweep H2D contrast (device-resident arena: ~one initial
upload; host-only arena: the old per-sweep transfer bill). The
``mesh_granularity`` rows run the same engine over ``--mesh`` device
shards and record per-device dispatcher occupancy plus the
cross-device gauges (``d2d_bytes``, ``migrations``); --smoke asserts
depth-first keeps ``cache_misses == 0`` on the mesh.

The hybrid-representation rows contrast the depth-first engine under
``representation`` bitmap / sparse / auto on every dataset (plus each
dataset's measured ones-per-word density and the auto runs'
dense/sparse sweep split): sparse retail subtrees are where the
gather-intersect path wins, mushroom/chess stay all-bitmap, and
--smoke asserts auto never loses to the best single representation by
more than 10% (plus retail ``df_speedup > 1.0`` and mushroom staying
all-bitmap with no regression).

Emits ``BENCH_granularity.json`` so the perf trajectory is recorded.
Run ``--smoke`` for the CI-sized variant (~2 min).
"""
from __future__ import annotations

import argparse
import json
import os
from typing import Dict, List

from repro.compile_cache import enable_compile_cache
from repro.core.fpm import mesh_over_devices, mine
from repro.core.join_backend import SweepDispatcher, get_backend
from repro.core.tidlist import BitmapArena, pack_database
from repro.data.transactions import load

#                 scale  support
SETUP = {
    "mushroom": (8, 0.15),
    "chess":    (64, 0.68),
    "retail":   (2, 0.012),
}
SMOKE_SETUP = {
    "mushroom": (2, 0.15),
    "chess":    (4, 0.72),
    "retail":   (1, 0.012),
}


def run(datasets: List[str], *, n_workers: int = 4, max_k: int = 5,
        policies=("clustered", "cilk"), backend: str = "auto",
        arena: str = "auto", max_batch: int = 32,
        flush_us: float = 200.0, smoke: bool = False,
        repeats: int = 1) -> List[Dict]:
    setup = SMOKE_SETUP if smoke else SETUP
    repeats = max(1, repeats)
    rows = []
    for name in datasets:
        scale, frac = setup[name]
        db, prof = load(name, seed=0, scale=scale)
        n_items = (prof.n_dense_items if prof.kind == "dense"
                   else prof.n_items)
        bm, item_counts = pack_database(db, n_items,
                                        return_counts=True)
        ms = max(1, int(frac * len(db)))
        density = (float(item_counts.sum())
                   / max(bm.shape[0] * bm.shape[1], 1))
        for policy in policies:
            rec: Dict = {"dataset": f"synth:{name}", "policy": policy,
                         "support": frac, "n_workers": n_workers,
                         "max_k": max_k, "backend": backend,
                         "arena": arena, "max_batch": max_batch,
                         "flush_us": flush_us,
                         "density_ones_per_word": density}
            counts = {}
            for gran in ("candidate", "bucket", "depth-first"):
                # the depth-first rows carry the representation
                # contrast: auto (the primary row) vs forced bitmap
                # vs forced sparse — that's where diffset handoffs
                # change the engine's traffic
                reps = (("auto", "bitmap", "sparse")
                        if gran == "depth-first" else ("auto",))
                # interleaved min-of-N for every row under a timing
                # assertion (bucket + all depth-first reps):
                # single-shot wall-clocks drift ±30% on a busy box,
                # and back-to-back repeats of ONE config share any
                # slow phase — round-robin over the representations
                # spreads drift evenly so the auto-vs-forced contrast
                # is unbiased. Candidate is the slow reference row,
                # never asserted against — one shot is enough.
                rounds = (repeats if gran == "candidate"
                          else max(repeats, 2))
                timing = {rep: (float("inf"), None, None)
                          for rep in reps}
                for _ in range(rounds):
                    for rep in reps:
                        res, m = mine(bm, ms, policy=policy,
                                      n_workers=n_workers, max_k=max_k,
                                      granularity=gran, backend=backend,
                                      arena=arena, max_batch=max_batch,
                                      flush_us=flush_us,
                                      representation=rep,
                                      item_counts=item_counts)
                        if m.wall_s < timing[rep][0]:
                            # counters travel with the run that set the
                            # best wall-clock, never mixed across
                            # repeats
                            timing[rep] = (m.wall_s, m, res)
                for rep in reps:      # "auto" first: it seeds counts
                    key = gran.replace("-", "_") + (
                        "" if rep == "auto" else f"_{rep}")
                    best, met, res = timing[rep]
                    if rep != "auto":
                        assert res == counts["depth-first"], \
                            f"representation mismatch on {name}/{rep}"
                        rec[f"{key}_s"] = best
                        rec[f"{key}_sparse_sweeps"] = met.sparse_sweeps
                        continue
                    counts[gran] = res
                    rec[f"{key}_s"] = best
                    rec[f"{key}_rows_touched"] = met.rows_touched
                    rec[f"{key}_bytes_swept"] = met.bytes_swept
                    rec[f"{key}_tasks"] = int(
                        met.scheduler["tasks_run"])
                    rec[f"{key}_cache_misses"] = met.cache_misses
                    rec[f"{key}_flushes"] = met.flushes
                    rec[f"{key}_batch_occupancy"] = met.batch_occupancy
                    rec[f"{key}_h2d_bytes"] = met.h2d_bytes
                    rec[f"{key}_sparse_sweeps"] = met.sparse_sweeps
                    rec[f"{key}_dense_sweeps"] = met.dense_sweeps
                    rec[f"{key}_sparse_bytes_swept"] = \
                        met.sparse_bytes_swept
                    rec["frequent"] = met.frequent
                    if gran == "depth-first":
                        rec["depth_first_peak_retained_bitmaps"] = \
                            met.peak_retained_bitmaps
                        rec["depth_first_peak_bytes_retained"] = \
                            met.peak_bytes_retained
                        rec["depth_first_rep_picks"] = met.rep_picks
                        rec["depth_first_sparse_rows"] = met.sparse_rows
            assert (counts["candidate"] == counts["bucket"]
                    == counts["depth-first"]), \
                f"granularity mismatch on {name}/{policy}"
            rec["speedup"] = rec["candidate_s"] / max(rec["bucket_s"],
                                                      1e-9)
            rec["df_speedup"] = rec["bucket_s"] / max(
                rec["depth_first_s"], 1e-9)
            # auto vs the best single forced representation
            rec["rep_speedup"] = rec["depth_first_bitmap_s"] / max(
                rec["depth_first_s"], 1e-9)
            rows.append(rec)
    return rows


def mesh_granularity(n_shards: int = 2, *, n_workers: int = 4,
                     max_k: int = 4, smoke: bool = False) -> List[Dict]:
    """The unified engine on a mesh: every granularity distributed over
    ``n_shards`` device shards (real jax devices when the host exposes
    enough — e.g. under --xla_force_host_platform_device_count —
    logical shards otherwise). Emits per-device dispatcher occupancy
    and the cross-device traffic gauges (``d2d_bytes``,
    ``migrations``) so the trajectory records the mesh path, and shows
    depth-first keeping its structural ``cache_misses == 0`` on the
    mesh."""
    mesh = mesh_over_devices(n_shards) or n_shards
    mesh_kind = "logical" if isinstance(mesh, int) else "jax"
    db, prof = load("mushroom", seed=0, scale=1 if smoke else 4)
    bm = pack_database(db, prof.n_dense_items)
    ms = max(1, int(0.18 * len(db)))
    out = []
    for gran in ("bucket", "candidate", "depth-first"):
        # on a real jax mesh, run the batched sweeps through the
        # interpreted kernel so the per-shard DEVICE mirrors (and their
        # d2d fetch path) are actually exercised — numpy would reduce
        # the row to logical-shard bookkeeping. Candidate stays on
        # numpy: per-candidate interpreted launches cost minutes and
        # the dispatcher routing under test is identical.
        backend = ("pallas-interpret"
                   if mesh_kind == "jax" and gran != "candidate"
                   else "numpy")
        res, met = mine(bm, ms, policy="clustered", n_workers=n_workers,
                        max_k=max_k, granularity=gran, mesh=mesh,
                        backend=backend)
        out.append({
            "bench": "mesh_granularity", "granularity": gran,
            "mesh_kind": mesh_kind, "backend": backend,
            "n_devices": met.n_devices,
            "wall_s": met.wall_s, "frequent": met.frequent,
            "rows_touched": met.rows_touched,
            "cache_misses": met.cache_misses,
            "d2d_bytes": met.d2d_bytes,
            "migrations": met.migrations,
            "batch_occupancy": met.batch_occupancy,
            "per_device": met.per_device,
        })
    return out


def repeat_sweep_h2d(repeats: int = 5, n_txn: int = 400,
                     n_buckets: int = 24, n_exts: int = 16) -> List[Dict]:
    """Repeated-sweep H2D contrast, the tentpole's whole point.

    The same ``n_buckets`` sweeps are submitted ``repeats`` times
    through one pallas-interpret dispatcher. With a device-resident
    arena ("jax") the bitmaps cross host→device exactly once — the
    initial arena upload — no matter how often they are swept; with a
    host-only arena ("numpy", the old path's behaviour) every batch
    re-uploads its gathered payload. Both rows land in the JSON so the
    trajectory records the drop."""
    db, prof = load("mushroom", seed=0)
    bm = pack_database(db[:n_txn], prof.n_dense_items)
    n_items = bm.shape[0]
    out = []
    for backing in ("jax", "numpy"):
        arena = BitmapArena.from_bitmaps(bm, backing=backing)
        disp = SweepDispatcher(arena, get_backend("pallas-interpret"),
                               n_clients=n_buckets)
        sweep_rows = 0
        try:
            for _ in range(repeats):
                futs = [disp.submit(p, tuple(range(p + 1,
                                                   p + 1 + n_exts)))
                        for p in range(n_buckets)]
                for f in futs:
                    f.result()
                sweep_rows += n_buckets * (1 + n_exts)
        finally:
            disp.stop()
        naive = sweep_rows * bm.shape[1] * 4    # old path: re-upload all
        out.append({"bench": "repeat_sweep_h2d", "arena": backing,
                    "repeats": repeats, "n_buckets": n_buckets,
                    "n_exts": n_exts, "n_items": n_items,
                    "arena_bytes": arena.nbytes_base,
                    "h2d_bytes": arena.h2d_bytes,
                    "naive_h2d_bytes": naive,
                    "batch_occupancy": disp.batch_occupancy})
    return out


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized datasets (~2 min)")
    ap.add_argument("--datasets", nargs="*",
                    default=["mushroom", "chess", "retail"])
    ap.add_argument("--policies", nargs="*", default=["clustered", "cilk"])
    ap.add_argument("--backend", default="auto")
    ap.add_argument("--arena", default="auto",
                    choices=["auto", "numpy", "jax"])
    ap.add_argument("--max-batch", type=int, default=32)
    ap.add_argument("--flush-us", type=float, default=200.0)
    ap.add_argument("--n-workers", type=int, default=4)
    ap.add_argument("--max-k", type=int, default=5)
    ap.add_argument("--repeats", type=int, default=1,
                    help="best-of-N wall-clock per granularity")
    ap.add_argument("--mesh", type=int, default=2,
                    help="device shards for the mesh_granularity rows "
                         "(real jax devices when available, logical "
                         "shards otherwise)")
    ap.add_argument("--out", default="BENCH_granularity.json")
    args = ap.parse_args(argv)

    rows = run(args.datasets, n_workers=args.n_workers, max_k=args.max_k,
               policies=tuple(args.policies), backend=args.backend,
               arena=args.arena, max_batch=args.max_batch,
               flush_us=args.flush_us, smoke=args.smoke,
               repeats=args.repeats)
    h2d_rows = repeat_sweep_h2d()
    # --mesh 0/1 follows the launcher/quickstart convention: no mesh
    # rows, shared-memory results only
    mesh_rows = (mesh_granularity(args.mesh, n_workers=args.n_workers,
                                  smoke=args.smoke)
                 if args.mesh > 1 else [])
    payload = {
        "bench": "fpm_granularity",
        "smoke": args.smoke,
        "backend": args.backend,
        "arena": args.arena,
        "results": rows,
        "repeat_sweep_h2d": h2d_rows,
        "mesh_granularity": mesh_rows,
    }
    with open(args.out, "w") as f:
        json.dump(payload, f, indent=2)
    print("bench,us_per_call,derived")
    for r in rows:
        print(f"granularity_{r['dataset']}_{r['policy']},"
              f"{r['bucket_s'] * 1e6:.0f},"
              f"speedup={r['speedup']:.2f}x;"
              f"df_speedup={r['df_speedup']:.2f}x;"
              f"df_cache_misses={r['depth_first_cache_misses']};"
              f"batch_occ={r['bucket_batch_occupancy']:.2f};"
              f"rows={r['bucket_rows_touched']}vs"
              f"{r['candidate_rows_touched']};"
              f"density={r['density_ones_per_word']:.2f};"
              f"df_rep=auto:{r['depth_first_s']:.2f}s/"
              f"bm:{r['depth_first_bitmap_s']:.2f}s/"
              f"sp:{r['depth_first_sparse_s']:.2f}s;"
              f"df_sparse_sweeps={r['depth_first_sparse_sweeps']}")
    for h in h2d_rows:
        print(f"repeat_sweep_h2d_arena={h['arena']},,"
              f"h2d={h['h2d_bytes']}B;naive={h['naive_h2d_bytes']}B;"
              f"arena={h['arena_bytes']}B;"
              f"occ={h['batch_occupancy']:.2f}")
    for m in mesh_rows:
        occ = "/".join(f"{d['batch_occupancy']:.2f}"
                       for d in m["per_device"])
        print(f"mesh_{m['granularity']}_{m['n_devices']}dev"
              f"({m['mesh_kind']}),{m['wall_s'] * 1e6:.0f},"
              f"d2d={m['d2d_bytes']}B;migrations={m['migrations']};"
              f"dev_occ={occ};cache_misses={m['cache_misses']}")
    if args.smoke:
        # the dispatcher must actually coalesce: mean occupancy of the
        # batched granularities stays above one request per launch
        occs = [r[f"{g}_batch_occupancy"] for r in rows
                for g in ("bucket", "depth_first")]
        mean_occ = sum(occs) / len(occs)
        assert mean_occ > 1.0, (
            f"dispatcher degraded to one-bucket batches: mean "
            f"batch_occupancy {mean_occ:.2f} (per-run: {occs})")
        print(f"# smoke occupancy check passed: mean={mean_occ:.2f}")
        # device-resident arena: repeated sweeps cost ~one initial
        # upload (indices excluded from the gauge), not one per sweep
        dev = next(h for h in h2d_rows if h["arena"] == "jax")
        assert dev["h2d_bytes"] <= 1.05 * dev["arena_bytes"], dev
        assert dev["h2d_bytes"] < 0.1 * dev["naive_h2d_bytes"], dev
        print("# smoke h2d check passed: "
              f"{dev['h2d_bytes']}B ~= one arena upload "
              f"({dev['arena_bytes']}B) vs naive {dev['naive_h2d_bytes']}B")
        # hybrid representation: auto must track the best single
        # representation (≤10% + scheduling jitter slack) everywhere,
        # beat bucket on sparse retail, and keep dense mushroom
        # all-bitmap with no regression against forced-bitmap
        slack = 0.15
        for r in rows:
            best_single = min(r["depth_first_bitmap_s"],
                              r["depth_first_sparse_s"])
            assert r["depth_first_s"] <= 1.10 * best_single + slack, (
                f"auto representation lost >10% to the best single "
                f"representation on {r['dataset']}/{r['policy']}: "
                f"auto={r['depth_first_s']:.3f}s vs "
                f"best={best_single:.3f}s")
        retail = [r for r in rows if r["dataset"] == "synth:retail"]
        if retail:
            best_df = max(r["df_speedup"] for r in retail)
            assert best_df > 1.0, (
                f"retail depth-first (hybrid) no longer beats bucket: "
                f"df_speedup={best_df:.2f}")
            assert all(r["depth_first_sparse_sweeps"] > 0
                       for r in retail), "retail never went sparse"
            print(f"# smoke retail check passed: df_speedup="
                  f"{best_df:.2f} (sparse sweeps="
                  f"{retail[0]['depth_first_sparse_sweeps']})")
        shroom = [r for r in rows if r["dataset"] == "synth:mushroom"]
        for r in shroom:
            assert r["depth_first_sparse_sweeps"] == 0, (
                f"mushroom went sparse under auto: "
                f"{r['depth_first_sparse_sweeps']} sparse sweeps")
            assert r["depth_first_s"] <= (1.05 * r["depth_first_bitmap_s"]
                                          + slack), (
                f"mushroom auto regressed vs forced bitmap: "
                f"{r['depth_first_s']:.3f}s vs "
                f"{r['depth_first_bitmap_s']:.3f}s")
        if shroom:
            print("# smoke mushroom check passed: all-bitmap, "
                  f"auto={shroom[0]['depth_first_s']:.2f}s vs "
                  f"bitmap={shroom[0]['depth_first_bitmap_s']:.2f}s")
        if mesh_rows:
            # the mesh path keeps depth-first's structural invariant:
            # the handoff replaces the prefix cache even across shards
            df = next(m for m in mesh_rows
                      if m["granularity"] == "depth-first")
            assert df["cache_misses"] == 0, df
            assert len(df["per_device"]) == df["n_devices"] >= 2, df
            print(f"# smoke mesh check passed: depth-first on "
                  f"{df['n_devices']} shards, cache_misses=0, "
                  f"d2d={df['d2d_bytes']}B")
    print(f"# wrote {os.path.abspath(args.out)}")


if __name__ == "__main__":
    enable_compile_cache()
    main()
