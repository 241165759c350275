"""Worker-count scaling of the FPM engine (paper ran 8 threads/16 cores;
single-core container => measures scheduling overhead + work effects)."""
from __future__ import annotations

from typing import Dict, List

from repro.compile_cache import enable_compile_cache
from repro.core.fpm import mine, mine_serial
from repro.core.tidlist import pack_database
from repro.data.transactions import load
import time


def run(dataset: str = "mushroom", workers=(1, 2, 4, 8),
        max_k: int = 4) -> List[Dict]:
    db, prof = load(dataset, seed=0)
    n_items = (prof.n_dense_items if prof.kind == "dense"
               else prof.n_items)
    bm = pack_database(db, n_items)
    ms = max(1, int(prof.support * len(db)))
    t0 = time.time()
    mine_serial(bm, ms, max_k=max_k)
    serial_s = time.time() - t0
    rows = []
    for n in workers:
        # candidate granularity: efficiency is measured against the
        # per-candidate serial join, so the engine must do the same
        # per-task work
        _, met = mine(bm, ms, policy="clustered", n_workers=n,
                      max_k=max_k, granularity="candidate")
        rows.append({"workers": n, "wall_s": met.wall_s,
                     "serial_s": serial_s,
                     "efficiency": serial_s / (met.wall_s * 1)})
    return rows


def main():
    print("bench,us_per_call,derived")
    for r in run():
        print(f"scaling_w{r['workers']},{r['wall_s'] * 1e6:.0f},"
              f"serial={r['serial_s']:.2f}s;eff={r['efficiency']:.2f}")


if __name__ == "__main__":
    enable_compile_cache()
    main()
