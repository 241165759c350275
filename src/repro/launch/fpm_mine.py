"""FPM mining launcher — the paper's application end-to-end.

Example (Fig. 1 reproduction on one dataset):
    PYTHONPATH=src python -m repro.launch.fpm_mine --dataset chess \
        --workers 8 --policies cilk clustered
"""
from __future__ import annotations

import argparse
import time

from repro.compile_cache import enable_compile_cache
from repro.core.buckets import REPRESENTATIONS
from repro.core.fpm import (GRANULARITIES, mesh_over_devices, mine,
                            mine_serial)
from repro.core.tidlist import pack_database
from repro.data.transactions import PROFILES, load, min_support_count
from repro.obs import Tracer, summary_table, write_chrome_trace
from repro.obs import schema as obs_schema


def _dispatch_summary(per_device) -> str:
    """The dispatchers' queue wait per sweep request, and each
    launched kernel's fill: logical over padded work, %."""
    c = obs_schema.merge_counters(per_device, obs_schema.DEVICE_COUNTERS)
    out = []
    if c["sweep_requests"]:
        out.append(f"queue_wait="
                   f"{c['queue_wait_us'] / c['sweep_requests'] / 1e3:.3f}ms")
    for kernel, unit in (("bitmap_join", "words"),
                         ("gather_intersect", "probes")):
        padded = c[f"{kernel}_padded_{unit}"]
        if padded:
            out.append(f"{kernel}_fill="
                       f"{100 * c[f'{kernel}_{unit}'] / padded:.1f}%")
    return " ".join(out)


def _finish_trace(args, tracer, wall_s: float) -> None:
    """Flush the run's tracer: Chrome-trace JSON for ``--trace`` (one
    lane per worker/dispatcher, loadable at https://ui.perfetto.dev)
    and the terminal time-in-state table for ``--trace-summary``."""
    if tracer is None:
        return
    if args.trace:
        write_chrome_trace(tracer, args.trace)
        print(f"trace: wrote {args.trace} "
              f"({len(tracer.events())} events) — open in "
              f"https://ui.perfetto.dev")
    if args.trace_summary:
        print(summary_table(tracer, wall_s))


def _spawn_hosts(args) -> None:
    """Parent of a ``--hosts N`` run: pick a coordinator port, spawn
    one rank subprocess per host with ``JAX_PLATFORMS=cpu`` (the ranks
    emulate a cluster on this host's CPU; per-flush reductions travel
    the coordination service's KV store), forward rank 0's report, and
    propagate the first failing exit code."""
    import os
    import socket
    import subprocess
    import sys

    if args.stream:
        raise SystemExit("--hosts and --stream are mutually exclusive "
                         "(use StreamingMiner(hosts=N) for multi-host "
                         "streaming)")
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        coord = f"127.0.0.1:{s.getsockname()[1]}"
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    base = [sys.executable, "-m", "repro.launch.fpm_mine",
            "--dataset", args.dataset,
            "--workers", str(args.workers),
            "--policies", args.policies[0],
            "--granularity", args.granularity,
            "--max-k", str(args.max_k),
            "--seed", str(args.seed),
            "--_coordinator", coord,
            "--_nprocs", str(args.hosts)]
    if args.support is not None:
        base += ["--support", str(args.support)]
    print(f"hosts: spawning {args.hosts} ranks @ {coord} "
          f"(JAX_PLATFORMS=cpu)")
    procs = [subprocess.Popen(
        base + ["--_rank", str(r)], env=env,
        stdout=None if r == 0 else subprocess.DEVNULL)
        for r in range(args.hosts)]
    codes = [p.wait() for p in procs]
    for r, c in enumerate(codes):
        if c:
            raise SystemExit(f"rank {r} exited with {c}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="chess", choices=list(PROFILES))
    ap.add_argument("--workers", type=int, default=8)
    ap.add_argument("--policies", nargs="+",
                    default=["cilk", "clustered"])
    ap.add_argument("--granularity", default="bucket",
                    choices=list(GRANULARITIES),
                    help="task grain: bucket (level-sync sweep), "
                         "candidate (one-extension sweeps), or "
                         "depth-first (barrier-free class recursion)")
    ap.add_argument("--representation", default="auto",
                    choices=list(REPRESENTATIONS),
                    help="row representation: bitmap (word-columns "
                         "only), sparse (force tid-list/diffset rows), "
                         "auto (density-driven per-subtree choice)")
    ap.add_argument("--backend", default="auto",
                    help="join backend: auto|numpy|pallas-interpret|"
                         "pallas-jit")
    ap.add_argument("--arena", default="auto",
                    choices=["auto", "numpy", "jax"],
                    help="bitmap arena backing: auto (lazy device "
                         "mirror), jax (eager upload), numpy "
                         "(host-only; Pallas backends re-upload per "
                         "batch — the transfer-bound baseline)")
    ap.add_argument("--max-batch", type=int, default=32,
                    help="sweep dispatcher: max requests per batched "
                         "kernel launch")
    ap.add_argument("--flush-us", type=float, default=200.0,
                    help="sweep dispatcher: µs to wait for straggler "
                         "requests before flushing a partial batch")
    ap.add_argument("--mesh", type=int, default=0,
                    help="run the engine mesh-aware over N device "
                         "shards (sharded arena, one dispatcher per "
                         "device, device-affine workers). Uses the "
                         "first N jax devices when available, logical "
                         "shards otherwise; 0 = shared-memory run")
    ap.add_argument("--hosts", type=int, default=0, metavar="N",
                    help="multi-host mode: spawn N worker processes "
                         "forming a jax.distributed CPU cluster; each "
                         "owns a word-slice of the transaction axis "
                         "and support counting is two-phase (local "
                         "partial counts + per-flush cross-host "
                         "reduction). The ranks run on the CPU "
                         "(JAX_PLATFORMS=cpu), never on an "
                         "accelerator. 0 = single process")
    # child-rank plumbing for --hosts (set by the parent, not by hand)
    ap.add_argument("--_rank", type=int, default=None,
                    help=argparse.SUPPRESS)
    ap.add_argument("--_nprocs", type=int, default=0,
                    help=argparse.SUPPRESS)
    ap.add_argument("--_coordinator", default=None,
                    help=argparse.SUPPRESS)
    ap.add_argument("--support", type=float, default=None,
                    help="override the profile's min-support fraction")
    ap.add_argument("--max-k", type=int, default=6)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--stream", type=int, default=0, metavar="N",
                    help="streaming mode: hold back the tail of the "
                         "dataset and replay it as N ingest+refresh "
                         "rounds through a StreamingMiner (prints "
                         "per-round border/reuse stats; the final "
                         "generation is verified against the serial "
                         "batch miner)")
    ap.add_argument("--stream-frac", type=float, default=0.1,
                    help="fraction of the dataset replayed as the "
                         "ingest stream (with --stream)")
    ap.add_argument("--trace", default=None, metavar="OUT.json",
                    help="record a time-resolved trace of the run "
                         "(task/flush/steal spans, one lane per "
                         "worker) and write Chrome trace-event JSON "
                         "loadable in Perfetto")
    ap.add_argument("--trace-summary", action="store_true",
                    help="print the per-worker time-in-state table "
                         "(sweep/eval/idle/steal) after the run; "
                         "implies tracing even without --trace")
    ap.add_argument("--serve", type=int, default=0, metavar="N",
                    help="after the stream replay, serve N queries of "
                         "each kind (known-hit, batched unknown-itemset "
                         "sweep, top-k) through the PatternServer and "
                         "print per-kind p50/p95/p99 (with --stream)")
    args = ap.parse_args()
    enable_compile_cache()

    if args.hosts >= 2 and args._rank is None:
        return _spawn_hosts(args)

    db, prof = load(args.dataset, args.seed)
    n_items = (prof.n_dense_items if prof.kind == "dense"
               else prof.n_items)
    bitmaps, item_counts = pack_database(db, n_items,
                                         return_counts=True)
    frac = args.support if args.support is not None else prof.support
    ms = max(1, int(frac * len(db)))
    print(f"dataset=synth:{args.dataset} |D|={len(db)} items={n_items} "
          f"min_support={ms} ({frac:.4f})")

    if args._rank is not None:
        # one rank of a --hosts cluster: every process packed the same
        # database above, keeps only its word-slice, and mines with
        # the KV-store reduction transport
        from repro.core.cluster import mine_distributed_process
        res, met = mine_distributed_process(
            bitmaps, ms, rank=args._rank, n_procs=args._nprocs,
            coordinator=args._coordinator, policy=args.policies[0],
            n_workers=args.workers, max_k=args.max_k,
            granularity=args.granularity)
        if args._rank == 0:
            s = met.scheduler
            print(f"{args.policies[0]:10s} hosts={met.n_hosts} "
                  f"wall={met.wall_s:6.2f}s "
                  f"frequent={len(res)} "
                  f"steals={int(s.get('steals', 0)):6d} "
                  f"net={met.net_bytes}B steal_net={met.steal_net}B")
        return

    mesh = mesh_over_devices(args.mesh)
    if mesh is not None:
        print(f"mesh: {args.mesh} device shards "
              f"({'logical' if isinstance(mesh, int) else 'jax devices'})")

    t0 = time.perf_counter()
    ref = mine_serial(bitmaps, ms, max_k=args.max_k)
    t_serial = time.perf_counter() - t0
    print(f"serial: {len(ref)} frequent itemsets in {t_serial:.2f}s")

    tracer = (Tracer() if (args.trace or args.trace_summary)
              else None)

    if args.stream:
        from repro.core.streaming import PatternServer, StreamingMiner
        n_stream = max(args.stream, int(args.stream_frac * len(db)))
        init, tail = db[:-n_stream], db[-n_stream:]
        per = max(1, len(tail) // args.stream)
        sm = StreamingMiner(n_items, ms, initial_db=init,
                            policy=args.policies[0],
                            n_workers=args.workers, max_k=args.max_k,
                            granularity=args.granularity,
                            backend=args.backend, arena=args.arena,
                            max_batch=args.max_batch,
                            flush_us=args.flush_us, mesh=mesh,
                            representation=args.representation,
                            tracer=tracer)
        t_stream0 = time.perf_counter()
        rep = sm.refresh()
        print(f"stream gen1: |D|={rep.n_transactions} "
              f"frequent={rep.frequent} wall={rep.wall_s:.2f}s "
              f"rows={rep.rows_touched}")
        for r in range(args.stream):
            batch = tail[r * per:] if r == args.stream - 1 \
                else tail[r * per:(r + 1) * per]
            if not batch:
                break
            ing = sm.ingest(batch)
            rep = sm.refresh()
            print(f"stream gen{rep.generation}: +{ing.n_transactions}tx "
                  f"(seg {ing.segment}, {ing.payload_bytes}B) "
                  f"wall={rep.wall_s:.2f}s rows={rep.rows_touched} "
                  f"reused={rep.reused} delta={rep.swept_delta} "
                  f"full={rep.swept_full} born={rep.born} "
                  f"died={rep.died}")
        assert dict(sm.snapshot.supports) == ref, "stream mismatch!"
        srv = PatternServer(sm)
        top = srv.top_k((), 5)
        print(f"stream final == serial ✓; top-5: {top}")
        if args.serve:
            import itertools

            hot = [x for x, _ in top] or [(0,)]
            fresh = itertools.chain.from_iterable(
                itertools.combinations(range(n_items), k)
                for k in range(args.max_k + 1, n_items + 1))
            lat = {"hit": [], "sweep": [], "top_k": []}
            for i in range(args.serve):
                x = hot[i % len(hot)]
                t0 = time.perf_counter_ns()
                srv.support(x)
                lat["hit"].append((time.perf_counter_ns() - t0) / 1e3)
                t0 = time.perf_counter_ns()
                srv.top_k(x[:1], 5)
                lat["top_k"].append((time.perf_counter_ns() - t0) / 1e3)
            batch = 8
            for _ in range(args.serve):
                xs = list(itertools.islice(fresh, batch))
                t0 = time.perf_counter_ns()
                srv.support_many(xs)
                lat["sweep"].append(
                    (time.perf_counter_ns() - t0) / 1e3 / len(xs))
            import numpy as np
            for kind, us in lat.items():
                a = np.asarray(us)
                print(f"serve {kind:6s}: n={len(us):4d} "
                      f"p50={np.percentile(a, 50):8.1f}us "
                      f"p95={np.percentile(a, 95):8.1f}us "
                      f"p99={np.percentile(a, 99):8.1f}us")
            print(f"serve stats: {srv.merged_stats()} "
                  f"query_sweeps={sm.query_sweeps} "
                  f"query_sweep_bytes={sm.query_sweep_bytes}")
            print(f"serve recorder: {srv.latency_percentiles()}")
        _finish_trace(args, tracer,
                      time.perf_counter() - t_stream0)
        sm.close()
        return

    traced_wall = 0.0
    for policy in args.policies:
        res, met = mine(bitmaps, ms, policy=policy,
                        n_workers=args.workers, max_k=args.max_k,
                        granularity=args.granularity,
                        backend=args.backend, arena=args.arena,
                        max_batch=args.max_batch, flush_us=args.flush_us,
                        mesh=mesh, representation=args.representation,
                        item_counts=item_counts, trace=tracer)
        traced_wall += met.wall_s
        assert res == ref, f"{policy} result mismatch!"
        s = met.scheduler
        line = (f"{policy:10s} wall={met.wall_s:6.2f}s "
                f"speedup={t_serial / met.wall_s:5.2f}x "
                f"cache_hit={met.cache_hit_rate:5.1%} "
                f"steals={int(s['steals']):6d} "
                f"tasks/steal={s['tasks_per_steal']:5.2f} "
                f"bucket_switches={int(s['bucket_switches']):5d}")
        if met.flushes:
            line += (f" batch_occ={met.batch_occupancy:4.2f} "
                     f"flushes={met.flushes} h2d={met.h2d_bytes}B "
                     f"{_dispatch_summary(met.per_device)}")
        if met.n_devices > 1:
            occ = "/".join(f"{d['batch_occupancy']:.2f}"
                           for d in met.per_device)
            line += (f" d2d={met.d2d_bytes}B "
                     f"migrations={met.migrations} "
                     f"dev_occ={occ}")
        if met.n_hosts > 1:
            line += (f" hosts={met.n_hosts} net={met.net_bytes}B "
                     f"steal_net={met.steal_net}B")
        if args.granularity == "depth-first":
            line += (f" peak_retained={met.peak_retained_bitmaps}"
                     f" ({met.peak_bytes_retained} B)")
        if met.sparse_sweeps or met.sparse_rows:
            line += (f"\n{'':10s} rep[{met.representation}]: "
                     f"sweeps dense={met.dense_sweeps} "
                     f"sparse={met.sparse_sweeps} "
                     f"sparse_bytes={met.sparse_bytes_swept}B "
                     f"rows={met.sparse_rows} "
                     f"picks={met.rep_picks} "
                     f"densify={met.densify_ops}"
                     f"/{met.densify_bytes}B "
                     f"sparsify={met.sparsify_ops}"
                     f"/{met.sparsify_bytes}B")
        print(line)
    _finish_trace(args, tracer, traced_wall)


if __name__ == "__main__":
    main()
