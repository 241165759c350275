"""Tracing overhead + traced-run artifacts.

Two questions, one bench:

1. **Overhead** — the tracer's record path is one ``perf_counter``
   read, one tuple build, one ring-slot store, and the disabled path is
   a ``tracer is None`` identity test at every site. The contrast runs
   the same mushroom mine traced and untraced, interleaved best-of-N
   (single-shot wall-clocks drift ±30% on a busy box; round-robin
   spreads the drift evenly), and ``--smoke`` asserts the traced best
   stays within 5% of the untraced best (plus a small absolute slack so
   a sub-second run can't fail on scheduler jitter alone).

2. **Artifacts** — the traced batch run and a traced streaming
   ingest→refresh→serve round each write a Chrome trace-event JSON
   (``mine.trace.json`` / ``stream.trace.json``, loadable at
   https://ui.perfetto.dev) whose well-formedness (per-lane span
   nesting, one lane per worker with task spans) is asserted, so CI
   uploads a trace a human can actually open.

Emits ``BENCH_trace.json`` so the overhead trajectory is recorded.
Run ``--smoke`` for the CI-sized variant (~1 min).
"""
from __future__ import annotations

import argparse
import json
import os
from typing import Dict, List

from repro.compile_cache import enable_compile_cache
from repro.core.fpm import mine
from repro.core.streaming import PatternServer, StreamingMiner
from repro.core.tidlist import pack_database
from repro.data.transactions import load
from repro.obs import (Tracer, check_nesting, time_in_state,
                       write_chrome_trace)


def overhead(*, scale: int, support: float, n_workers: int,
             max_k: int, rounds: int, trace_dir: str) -> Dict:
    db, prof = load("mushroom", seed=0, scale=scale)
    bm, counts = pack_database(db, prof.n_dense_items,
                               return_counts=True)
    ms = max(1, int(support * len(db)))
    kw = dict(policy="clustered", n_workers=n_workers, max_k=max_k,
              granularity="bucket", item_counts=counts)
    # warm-up: backend selection + any jit compile happen once, off
    # the clock for both arms
    ref, _ = mine(bm, ms, **kw)
    best = {"untraced": float("inf"), "traced": float("inf")}
    last_tracer = None
    for _ in range(max(2, rounds)):
        res, m = mine(bm, ms, **kw)
        assert res == ref
        best["untraced"] = min(best["untraced"], m.wall_s)
        tr = Tracer()
        res, m = mine(bm, ms, **kw, trace=tr)
        assert res == ref, "tracing changed the mining result"
        if m.wall_s < best["traced"]:
            best["traced"] = m.wall_s
            last_tracer = tr
    path = os.path.join(trace_dir, "mine.trace.json")
    write_chrome_trace(last_tracer, path)
    _assert_trace_shape(last_tracer, n_workers)
    return {
        "bench": "trace_overhead", "dataset": "synth:mushroom",
        "scale": scale, "support": support, "n_workers": n_workers,
        "max_k": max_k, "rounds": rounds,
        "untraced_s": best["untraced"], "traced_s": best["traced"],
        "overhead": best["traced"] / max(best["untraced"], 1e-9) - 1.0,
        "events": len(last_tracer.events()),
        "dropped": last_tracer.dropped(),
        "trace_path": os.path.abspath(path),
    }


def _assert_trace_shape(tr: Tracer, n_workers: int) -> None:
    """The artifact must be worth opening: every worker has its own
    lane with task spans, sweeps/flushes were traced, and per-lane
    nesting is well formed."""
    bad = check_nesting(tr.events())
    assert not bad, f"malformed span nesting: {bad[:3]}"
    task_lanes = {e.lane for e in tr.events()
                  if e.ph == "X" and e.cat == "task"}
    workers = {n for n in tr.lane_names() if n.startswith("worker-")}
    assert len(workers) == n_workers, tr.lane_names()
    assert task_lanes >= workers, (task_lanes, workers)
    cats = {e.cat for e in tr.events() if e.ph == "X"}
    assert {"flush", "sweep", "level"} <= cats, cats
    for row in time_in_state(tr).values():
        if row["lane"].startswith("worker-"):
            # spans tile the worker loop: total within 5% of extent
            assert row["total"] >= 0.95 * row["extent"] - 0.002, row


def streaming_round(*, scale: int, n_workers: int, max_k: int,
                    trace_dir: str) -> Dict:
    db, prof = load("mushroom", seed=0, scale=scale)
    ms = max(1, int(0.2 * len(db)))
    cut = max(1, int(0.9 * len(db)))
    tr = Tracer()
    sm = StreamingMiner(prof.n_dense_items, ms, initial_db=db[:cut],
                        n_workers=n_workers, max_k=max_k, tracer=tr)
    try:
        sm.refresh()
        sm.ingest(db[cut:])
        lag_pending = sm.refresh_lag
        rep = sm.refresh()
        srv = PatternServer(sm)
        top = srv.top_k((), 5)
        srv.support_many([x for x, _ in top])
        lat = srv.latency_percentiles()
    finally:
        sm.close()
    path = os.path.join(trace_dir, "stream.trace.json")
    write_chrome_trace(tr, path)
    names = {e.name for e in tr.events() if e.ph == "X"}
    assert {"ingest", "refresh", "publish"} <= names, names
    assert not check_nesting(tr.events())
    assert lag_pending > 0.0 and sm.refresh_lag == 0.0
    return {
        "bench": "trace_streaming", "dataset": "synth:mushroom",
        "scale": scale, "generation": rep.generation,
        "refresh_wall_s": rep.wall_s,
        "lag_before_refresh_s": lag_pending,
        "events": len(tr.events()),
        "latency": lat,
        "trace_path": os.path.abspath(path),
    }


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized run (~1 min) + overhead assertion")
    ap.add_argument("--rounds", type=int, default=5,
                    help="interleaved best-of-N rounds per arm")
    ap.add_argument("--n-workers", type=int, default=4)
    ap.add_argument("--max-k", type=int, default=5)
    ap.add_argument("--scale", type=int, default=0,
                    help="dataset scale (0 = 2 smoke / 8 full)")
    ap.add_argument("--trace-dir", default=".",
                    help="where the .trace.json artifacts land")
    ap.add_argument("--out", default="BENCH_trace.json")
    args = ap.parse_args(argv)

    scale = args.scale or (2 if args.smoke else 8)
    os.makedirs(args.trace_dir, exist_ok=True)
    rows: List[Dict] = [
        overhead(scale=scale, support=0.15, n_workers=args.n_workers,
                 max_k=args.max_k, rounds=args.rounds,
                 trace_dir=args.trace_dir),
        streaming_round(scale=max(1, scale // 2),
                        n_workers=args.n_workers, max_k=args.max_k,
                        trace_dir=args.trace_dir),
    ]
    with open(args.out, "w") as f:
        json.dump({"bench": "fpm_trace", "smoke": args.smoke,
                   "results": rows}, f, indent=2)
    ov = rows[0]
    print("bench,us_per_call,derived")
    print(f"trace_overhead,{ov['traced_s'] * 1e6:.0f},"
          f"untraced={ov['untraced_s']:.3f}s;"
          f"overhead={ov['overhead']:+.1%};"
          f"events={ov['events']};dropped={ov['dropped']}")
    st = rows[1]
    print(f"trace_streaming,{st['refresh_wall_s'] * 1e6:.0f},"
          f"gen={st['generation']};events={st['events']};"
          f"lag_before_refresh={st['lag_before_refresh_s'] * 1e3:.1f}ms")
    if args.smoke:
        # the gate the tentpole promises: tracing costs < 5% (+0.05s
        # absolute slack so sub-second runs can't fail on scheduler
        # jitter alone)
        assert ov["traced_s"] <= 1.05 * ov["untraced_s"] + 0.05, (
            f"tracing overhead above budget: traced={ov['traced_s']:.3f}s "
            f"vs untraced={ov['untraced_s']:.3f}s "
            f"({ov['overhead']:+.1%})")
        print(f"# smoke overhead check passed: {ov['overhead']:+.1%} "
              f"(budget 5%)")
    print(f"# wrote {os.path.abspath(args.out)}")


if __name__ == "__main__":
    enable_compile_cache()
    main()
