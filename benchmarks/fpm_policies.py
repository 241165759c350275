"""Fig. 1 reproduction: normalized runtimes, Cilk-style vs Clustered.

The paper runs 8 threads on 16 cores; this container has 1 core, so the
wall-time contrast here comes from the *work reduction* the clustered
policy's locality buys (prefix-intersection reuse), not thread scaling —
the same mechanism the paper measures via dTLB misses/IPC. Runtimes are
averaged over repeats and normalized Cilk=1.0, like Fig. 1.
"""
from __future__ import annotations

import time
from typing import Dict, List

from repro.compile_cache import enable_compile_cache
from repro.core.fpm import mine
from repro.core.tidlist import pack_database
from repro.data.transactions import PROFILES, load

DATASETS = ["chess", "connect", "mushroom", "pumsb", "accidents",
            "t10i4", "t40i10", "kosarak"]

# The paper's datasets have 10^5..10^6 transactions, putting the per-task
# TID-join well above scheduling overhead. The profiles are scaled into
# that regime here (supports tuned so each dataset mines in ~5-60 s on
# this single-core container); EXPERIMENTS.md §Paper documents this.
BENCH_SETUP = {
    #            scale  support
    "chess":      (128, 0.68),
    "connect":    (128, 0.85),
    "mushroom":   (128, 0.15),
    "pumsb":      (64,  0.90),
    "accidents":  (64,  0.35),
    "t10i4":      (32,  0.005),
    "t40i10":     (16,  0.04),
    "kosarak":    (32,  0.006),
}


def run(datasets: List[str] = DATASETS, n_workers: int = 4,
        repeats: int = 1, max_k: int = 5,
        granularity: str = "candidate") -> List[Dict]:
    """``granularity="candidate"`` reproduces the paper's per-itemset
    tasks (Fig. 1's setting — the cache hit-rate gap is the story);
    ``"bucket"`` runs the same policy contrast on the vectorized
    bucket-sweep engine."""
    rows = []
    for name in datasets:
        scale, frac = BENCH_SETUP[name]
        db, prof = load(name, seed=0, scale=scale)
        n_items = (prof.n_dense_items if prof.kind == "dense"
                   else prof.n_items)
        bm = pack_database(db, n_items)
        ms = max(1, int(frac * len(db)))
        times = {}
        metrics = {}
        for policy in ("cilk", "clustered"):
            best = []
            for r in range(repeats):
                res, met = mine(bm, ms, policy=policy,
                                n_workers=n_workers, max_k=max_k,
                                granularity=granularity)
                best.append(met.wall_s)
                metrics[policy] = met
            times[policy] = sum(best) / len(best)
        rows.append({
            "dataset": f"synth:{name}",
            "support": frac,
            "granularity": granularity,
            "cilk_s": times["cilk"],
            "clustered_s": times["clustered"],
            "normalized_clustered": times["clustered"] / times["cilk"],
            "speedup": times["cilk"] / times["clustered"],
            "itemsets": metrics["clustered"].frequent,
            "rows_touched": metrics["clustered"].rows_touched,
        })
    return rows


def main():
    print("bench,us_per_call,derived")
    for r in run():
        print(f"fig1_{r['dataset']},{r['clustered_s'] * 1e6:.0f},"
              f"norm={r['normalized_clustered']:.3f};"
              f"speedup={r['speedup']:.2f}x")


if __name__ == "__main__":
    enable_compile_cache()
    main()
