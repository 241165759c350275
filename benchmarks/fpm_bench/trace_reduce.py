"""From a JAX profiler trace (``.xplane.pb``) to the device's numbers.

The traced run wraps its measured window in a ``window`` annotation and
each call into a layer in an annotation of its own (``mine``,
``ingest``, ``refresh``, ``query.support`` ...). This module reads the
trace with ``jax.profiler.ProfileData`` alone and gives, over the
window:

* ``busy_s``: the union of the intervals in which an operation ran on a
  device, averaged over the chips used; ``window_s``: the window's
  length;
* ``kernel_s``: device seconds of each named kernel (events whose name,
  or HLO name, holds the kernel's name), summed over the chips;
* ``top_ops``: the device operations that took most time;
* ``idle_gaps``: the longest gaps between device operations, each named
  by the innermost benchmark annotation that held the host then.
"""
from __future__ import annotations

import glob
import os
from typing import Dict, Iterable, List, Sequence, Tuple

KERNELS = ("bitmap_join_many", "gather_intersect_many")
DEVICE_PREFIX = "/device:TPU:"
# the device line whose events are single operations
OPS_LINE = "XLA Ops"
PREFIX = "fpm_bench:"         # the benchmark's annotations
WINDOW = PREFIX + "window"
TOP = 10

Interval = Tuple[int, int]


def latest_xplane(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(files, key=os.path.getmtime)


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Merged, sorted, non-overlapping intervals."""
    out: List[List[int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1][1] = b
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(a: int, b: int, lo: int, hi: int):
    a, b = max(a, lo), min(b, hi)
    return (a, b) if b > a else None


def gaps(busy: Sequence[Interval], lo: int, hi: int) -> List[Interval]:
    """The idle intervals of [lo, hi] around merged ``busy``."""
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def _stat_strings(ev) -> List[str]:
    try:
        return [str(v) for _, v in ev.stats if isinstance(v, str)]
    except Exception:                # noqa: BLE001 - stats are optional
        return []


def kernel_of(ev) -> str:
    """The named kernel an event ran, or ''."""
    for k in KERNELS:
        if k in ev.name or any(k in s for s in _stat_strings(ev)):
            return k
    return ""


def reduce_profile(pd, chips: int = 1) -> Dict:
    annotations: List[Tuple[int, int, str]] = []
    window = None
    devices = []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            devices.append(plane)
            continue
        for line in plane.lines:
            for ev in line.events:
                s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                if ev.name == WINDOW:
                    window = (int(s), int(e))
                elif ev.name.startswith(PREFIX):
                    annotations.append((int(s), int(e),
                                        ev.name[len(PREFIX):]))
    if window is None:
        raise ValueError("the trace holds no 'window' annotation")
    lo, hi = window
    devices.sort(key=lambda p: int(p.name[len(DEVICE_PREFIX):]
                                   .split()[0] or 0))
    devices = devices[:chips]
    if not devices:
        raise ValueError("the trace holds no TPU plane")
    busy_total = 0
    kernel_ns = {k: 0 for k in KERNELS}
    op_ns: Dict[str, int] = {}
    all_gaps: List[Interval] = []
    for plane in devices:
        ivs = []
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                iv = clip(int(ev.start_ns),
                          int(ev.start_ns + ev.duration_ns), lo, hi)
                if iv is None:
                    continue
                ivs.append(iv)
                d = iv[1] - iv[0]
                k = kernel_of(ev)
                if k:
                    kernel_ns[k] += d
                op_ns[k or ev.name] = op_ns.get(k or ev.name, 0) + d
        merged = union(ivs)
        busy_total += sum(b - a for a, b in merged)
        all_gaps += gaps(merged, lo, hi)
    # name each gap by the innermost annotation holding its midpoint;
    # host annotations are few, so a scan per long gap is cheap
    longest = sorted(all_gaps, key=lambda g: g[0] - g[1])[:TOP]
    named = []
    for a, b in longest:
        mid = (a + b) // 2
        holders = [x for x in annotations if x[0] <= mid < x[1]]
        name = min(holders, key=lambda x: x[1] - x[0])[2] if holders \
            else "outside any annotation"
        named.append([name, (b - a) / 1e9])
    top = sorted(op_ns.items(), key=lambda kv: -kv[1])[:TOP]
    return {"busy_s": busy_total / len(devices) / 1e9,
            "window_s": (hi - lo) / 1e9,
            "kernel_s": {k: v / 1e9 for k, v in kernel_ns.items()},
            "kernel_seen": {k: v > 0 for k, v in kernel_ns.items()},
            "top_ops": [[n, v / 1e9] for n, v in top],
            "idle_gaps": named,
            "chips": len(devices)}


def reduce_file(path: str, chips: int = 1) -> Dict:
    from jax.profiler import ProfileData
    return reduce_profile(ProfileData.from_file(path), chips)


def reduce_dir(trace_dir: str, chips: int = 1) -> Dict:
    return reduce_file(latest_xplane(trace_dir), chips)
