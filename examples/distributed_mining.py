"""Multi-device FPM through the unified task engine: `fpm.mine(mesh=)`
runs every granularity on a device mesh — sharded bitmap arena (one
mirror per device), one sweep dispatcher per device, device-affine
workers whose cross-device bucket steals migrate the bucket's retained
bitmaps (spawns an 8-device subprocess).

Run:  PYTHONPATH=src python examples/distributed_mining.py
"""
import subprocess
import sys
import textwrap

CODE = """
import sys; sys.path.insert(0, "src")
import time
import jax, numpy as np
from jax.sharding import Mesh
from repro.compile_cache import enable_compile_cache
from repro.data.transactions import load
from repro.core.tidlist import pack_database
from repro.core.fpm import mine, mine_serial
from repro.core.distributed_fpm import mine_distributed

enable_compile_cache()
db, p = load('mushroom', seed=0)
db = db[:2500]
bm = pack_database(db, p.n_dense_items)
ms = int(0.22 * len(db))
print(f"{len(db)} transactions over 8 devices, min_support={ms}")
ref = mine_serial(bm, ms, max_k=4)
mesh = Mesh(np.array(jax.devices()).reshape(8), ('data',))

# the unified engine: every granularity runs distributed
for gran in ['bucket', 'depth-first']:
    t0 = time.time()
    res, met = mine(bm, ms, mesh=mesh, granularity=gran,
                    policy='clustered', n_workers=8, max_k=4)
    assert res == ref
    occ = '/'.join(f"{d['batch_occupancy']:.1f}" for d in met.per_device)
    print(f"[{gran:11s}] wall={time.time()-t0:5.2f}s "
          f"rows_touched={met.rows_touched:7d} "
          f"d2d={met.d2d_bytes}B migrations={met.migrations} "
          f"dev_occupancy={occ} cache_misses={met.cache_misses}")

# the legacy two-policy API is a shim over the same engine
for pol in ['round_robin', 'clustered']:
    t0 = time.time()
    res, stats = mine_distributed(bm, ms, mesh, policy=pol, max_k=4)
    assert res == ref
    print(f"[{pol:11s}] wall={time.time()-t0:5.2f}s "
          f"rows_touched={stats['rows_touched']:7d} "
          f"candidates={stats['candidates']}")
print("clustered placement touches fewer bitmap rows (prefix joined "
      "once per bucket), and depth-first carries its zero-recompute "
      "handoff onto the mesh: cross-device traffic is explicit "
      "(d2d bytes = fetched rows + migrated bucket bitmaps).")
"""


def main():
    env = {"XLA_FLAGS": "--xla_force_host_platform_device_count=8",
           "JAX_PLATFORMS": "cpu",   # skip TPU probing in the child
           "PYTHONPATH": "src", "PATH": "/usr/bin:/bin:/usr/local/bin"}
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(CODE)],
                       env=env, text=True)
    sys.exit(r.returncode)


if __name__ == "__main__":
    main()
