"""Benchmark harness — one module per paper table/figure. Prints
``name,us_per_call,derived`` CSV rows.

  fpm_policies     Fig. 1  (normalized runtimes, Cilk vs Clustered)
  fpm_granularity  bucket-sweep vs per-candidate tasks (smoke sizes)
  fpm_locality     Table 1 (locality metrics)
  fpm_scaling      worker scaling
  fpm_multihost    multi-host capacity, steal migration, 8-dev mesh rows
  fpm_streaming    ingest / incremental-refresh / serving latencies
  kernels_bench    kernel micro-benches + analytic TPU bounds
"""
from __future__ import annotations

import sys
import traceback

from benchmarks import (fpm_granularity, fpm_locality, fpm_multihost,
                        fpm_policies, fpm_scaling, fpm_streaming,
                        kernels_bench)
from repro.compile_cache import enable_compile_cache

ALL = [
    ("fpm_policies", fpm_policies.main),
    ("fpm_granularity", lambda: fpm_granularity.main(["--smoke"])),
    ("fpm_locality", fpm_locality.main),
    ("fpm_scaling", fpm_scaling.main),
    ("fpm_multihost", lambda: fpm_multihost.main(["--smoke"])),
    ("fpm_streaming", lambda: fpm_streaming.main(["--smoke"])),
    ("kernels_bench", kernels_bench.main),
]


def main() -> None:
    only = sys.argv[1] if len(sys.argv) > 1 else None
    failed = []
    for name, fn in ALL:
        if only and name != only:
            continue
        print(f"# === {name} ===", flush=True)
        try:
            fn()
        except Exception as e:  # noqa: BLE001
            failed.append(name)
            print(f"{name},0,ERROR={type(e).__name__}:{e}")
            traceback.print_exc()
    if failed:
        print(f"# failures: {failed}")
        sys.exit(1)


if __name__ == "__main__":
    enable_compile_cache()
    main()
