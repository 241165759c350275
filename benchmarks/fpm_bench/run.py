#!/usr/bin/env python3
"""The chip benchmark of the frequent-pattern miner: one cell per run.

    python benchmarks/fpm_bench/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

Everything about a cell is found by name, starting from the repository
root's ``BENCHMARK.json``:

* ``workloads/<cell>.json``   the cell: its configuration, traffic mix,
                              chips, and any parameter of its own (such
                              as an offered query rate);
* ``configs/<config>.json``   the deployment: generator, support,
                              engine options and guarantees;
* ``traffic/<mix>.json``      the traffic mix's parameters, whose
                              ``kind`` names the traffic driver
                              ``traffic/<kind>.py``;
* ``layer_metrics/<metric>.py``  one reader per per-layer metric.

A run checks that JAX reports a TPU (and as many chips as the cell asks
for), turns on the persistent compile cache, hands the cell to its
traffic driver (data from ``--seed``, set-up and warm-up, a window of
``--seconds``, then the check of every answer against the plain
reference), and prints one JSON object as the last line of its standard
output. With ``--trace 0`` the metrics are the cell's end-to-end
metrics; with ``--trace 1`` the window also runs under the JAX profiler
and the program's tracer, and the metrics are its per-layer metrics.
The numbers the check compared, each with its limit, are the last lines
of standard error and the last key of the result line.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

from harness import ROOT, TRACE_DIR, Cell, Context, SpecError, peaks


def _device_check(chips: int):
    import jax
    devs = jax.devices()
    d0 = devs[0]
    if d0.platform != "tpu":
        raise SystemExit(f"fpm_bench: no TPU: jax reports platform "
                         f"{d0.platform!r} ({d0.device_kind}); "
                         f"nothing falls back")
    if len(devs) < chips:
        raise SystemExit(f"fpm_bench: the cell needs {chips} chips, jax "
                         f"reports {len(devs)}")
    try:
        peaks(d0.device_kind)
    except SpecError as e:
        raise SystemExit(f"fpm_bench: {e}")
    return devs


def per_layer(cell: Cell, record: dict) -> dict:
    """Each per-layer metric of the cell, from its own reader; a reader
    that finds nothing to read returns None and the metric is left
    out."""
    out = {}
    for m in cell.per_layer:
        value = cell.reader(m["name"]).read(record)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def check_lines(checks: dict) -> list:
    return [f"check {k}: {v['value']} (limit {v['limit']})"
            for k, v in checks.items()]


def result_line(cell: Cell, out: dict, device: dict,
                reduced: dict = None) -> dict:
    """The last line's object. With ``reduced`` (a traced run's trace
    reduction) the metrics are the cell's per-layer metrics; without,
    its end-to-end metrics. ``checks``, the numbers compared with their
    limits, comes last."""
    result = {"correct": bool(out["correct"]),
              "attempted": int(out["attempted"]),
              "failed": int(out["failed"])}
    if reduced is not None:
        result["metrics"] = per_layer(cell, dict(out["record"],
                                                 device=reduced))
        device = dict(device, busy_s=reduced["busy_s"],
                      window_s=reduced["window_s"])
        result["device"] = device
        result["breakdown"] = {"device_ops": reduced["top_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    else:
        result["metrics"] = {m["name"]: {"value": out["metrics"][m["name"]],
                                         "unit": m["unit"]}
                             for m in cell.end_to_end}
        result["device"] = device
    result["checks"] = out["checks"]
    return result


def main(argv=None) -> int:
    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    try:
        cell = Cell(args.workload)
    except (SpecError, FileNotFoundError, KeyError) as e:
        print(f"fpm_bench: {e}", file=sys.stderr)
        return 2
    # libtpu would log under /tmp; a run writes only inside its checkout
    # and the directories it is given
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    devs = _device_check(cell.chips)
    d0 = devs[0]
    print(f"device: platform={d0.platform} kind={d0.device_kind} "
          f"count={len(devs)} compile_cache={cache}", flush=True)
    ctx = Context(cell, args.seed, args.seconds, bool(args.trace),
                  t_start)
    out = cell.driver().run(ctx)
    built, loaded, secs = ctx.window_compiles
    print(f"compiles_in_window={built} (loaded from the persistent cache "
          f"{loaded}, compiled {built - loaded}; {secs:.3f} s tracing, "
          f"lowering and building)", flush=True)
    reduced = None
    if args.trace:
        import trace_reduce
        reduced = trace_reduce.reduce_dir(TRACE_DIR, chips=cell.chips)
    result = result_line(cell, out, {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devs), "memory_peak_bytes": ctx.memory_peak_bytes},
        reduced)
    for line in check_lines(out["checks"]):
        print(line, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
