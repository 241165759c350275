"""Kernel micro-benchmarks on the accelerator: the batched dense join
(``bitmap_join_many``) and the sparse gather-intersect sweep
(``gather_intersect_many``), compiled, at dispatcher shapes.

Each row names the device it ran on and its HBM-bound floor from the
device's published peak. A device missing from ``PEAKS`` is an error:
there is no default peak, and no CPU fallback.

    PYTHONPATH=src python benchmarks/kernels_bench.py
"""
from __future__ import annotations

import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.kernels.bitmap_join.ops import bitmap_join_many
from repro.kernels.gather_intersect.ops import gather_intersect_many

# Per-chip HBM bandwidth (bytes/s) keyed by ``device_kind``: the sweeps
# are AND+popcount over streamed words, so HBM bandwidth is their
# roofline. Source: Google Cloud documentation, "TPU v5e" (16 GB HBM at
# 819 GB/s per chip).
PEAKS: Dict[str, float] = {
    "TPU v5 lite": 819e9,
}


def hbm_peak(dev) -> float:
    if dev.device_kind not in PEAKS:
        raise KeyError(f"no published HBM bandwidth for device kind "
                       f"{dev.device_kind!r} ({dev.platform}); add it "
                       f"to PEAKS with its source")
    return PEAKS[dev.device_kind]


def timeit(fn, *args, repeats: int = 10) -> float:
    """Host-clock seconds per call, after one warm-up call that
    compiles; ``block_until_ready`` ends the window on the device."""
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(repeats):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / repeats


def run() -> List[Dict]:
    dev = jax.devices()[0]
    hbm = hbm_peak(dev)
    rng = np.random.default_rng(0)
    b, e, w, s = 32, 128, 4096, 1024
    prefixes = jnp.asarray(rng.integers(0, 2 ** 32, (b, w), dtype=np.uint32))
    exts = jnp.asarray(rng.integers(0, 2 ** 32, (b, e, w), dtype=np.uint32))
    tids = jnp.asarray(np.sort(rng.choice(32 * w, (b, s)), axis=1)
                       .astype(np.int32))
    rows = []
    for name, fn, args, nbytes in (
            (f"bitmap_join_many_b{b}_e{e}_w{w}",
             lambda p, x: bitmap_join_many(p, x, mode="pallas-jit"),
             (prefixes, exts), exts.nbytes + prefixes.nbytes),
            (f"gather_intersect_many_b{b}_e{e}_w{w}_s{s}",
             lambda t, x: gather_intersect_many(t, x, mode="pallas-jit"),
             (tids, exts), exts.nbytes + tids.nbytes)):
        dt = timeit(fn, *args)
        rows.append({"name": name, "wall_s": dt,
                     "hbm_bound_s": nbytes / hbm})
    return rows


def main():
    dev = jax.devices()[0]
    print(f"# device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(jax.devices())}")
    print("bench,us_per_call,derived")
    for r in run():
        extra = {k: v for k, v in r.items() if k not in ("name", "wall_s")}
        ds = ";".join(f"{k}={v:.3e}" for k, v in extra.items())
        print(f"{r['name']},{r['wall_s'] * 1e6:.0f},{ds}")


if __name__ == "__main__":
    enable_compile_cache()
    main()
