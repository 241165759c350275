"""The benchmark's shared pieces: the cell as ``BENCHMARK.json`` and its
files describe it, the context a traffic driver runs in, and the count
of XLA programs built."""
from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import shutil
import sys
import time
import types

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(BENCH))
# the profiler's output for a traced run: a fixed path inside the
# checkout, emptied at the start of each traced run
TRACE_DIR = os.path.join(ROOT, ".bench_trace")
# the benchmark's own profiler annotations carry this prefix, so the
# trace reduction tells them from the runtime's
ANNOTATION_PREFIX = "fpm_bench:"


class SpecError(Exception):
    """The cell, or a file it names, is missing or does not agree."""


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    """Import a benchmark file by path (its name may hold dots)."""
    if not os.path.exists(path):
        raise SpecError(f"{os.path.relpath(path, ROOT)} does not exist")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One cell of ``BENCHMARK.json`` with every file it names."""

    def __init__(self, name: str, root: str = ROOT, bench: str = BENCH):
        spec = load_json(os.path.join(root, "BENCHMARK.json"))
        by_name = {w["name"]: w for w in spec["workloads"]}
        if name not in by_name:
            raise SpecError(f"no workload {name!r} in BENCHMARK.json "
                            f"(known: {sorted(by_name)})")
        entry = by_name[name]
        configs = {c["name"]: c for c in spec["configs"]}
        self._load(name, entry, bench, spec, os.path.join(
            root, configs[entry["config"]]["file"]))

    @classmethod
    def unlisted(cls, name: str, bench: str = BENCH) -> "Cell":
        """A cell whose files are in place but which ``BENCHMARK.json``
        does not list: its workload file stands for the entry, its
        configuration is found by name, and it has no metrics. For
        rehearsals and fault runs only."""
        cell = cls.__new__(cls)
        entry = dict(load_json(os.path.join(bench, "workloads",
                                            name + ".json")), name=name)
        cell._load(name, entry, bench, {"end_to_end": [], "per_layer": []},
                   os.path.join(bench, "configs", entry["config"] + ".json"))
        return cell

    def _load(self, name: str, entry: dict, bench: str, spec: dict,
              config_file: str) -> None:
        self.name = name
        self.entry = entry
        self.bench = bench
        self.cell = load_json(os.path.join(bench, "workloads",
                                           name + ".json"))
        for key in ("config", "traffic", "chips"):
            if self.cell.get(key) != entry[key]:
                raise SpecError(
                    f"workloads/{name}.json has {key}="
                    f"{self.cell.get(key)!r}, BENCHMARK.json "
                    f"{entry[key]!r}")
        self.config = load_json(config_file)
        self.mix = load_json(os.path.join(bench, "traffic",
                                          entry["traffic"] + ".json"))
        self.params = {**self.mix, **self.cell.get("params", {})}
        self.chips = int(entry["chips"])
        self.end_to_end = [m for m in spec["end_to_end"]
                           if name in m.get("workloads", [name])]
        self.per_layer = [m for m in spec["per_layer"]
                          if name in m.get("workloads", [name])]

    def driver(self):
        kind = self.params["kind"]
        return load_module(os.path.join(self.bench, "traffic",
                                        kind + ".py"),
                           f"fpm_bench_traffic_{kind}")

    def reader(self, metric: str):
        return load_module(os.path.join(self.bench, "layer_metrics",
                                        metric + ".py"),
                           "fpm_bench_metric_" + metric.replace(".", "_"))


def peaks(device_kind: str) -> dict:
    """The published peaks of one chip, from ``peaks.json``. A device
    kind the table lacks is an error: there is no default."""
    table = load_json(os.path.join(BENCH, "peaks.json"))
    if device_kind not in table:
        raise SpecError(f"no peaks for device kind {device_kind!r} in "
                        f"peaks.json; add them with their source")
    return table[device_kind]


class Compiles:
    """XLA programs built in this process, counted through
    ``jax.monitoring``: ``n`` built (compiled, or loaded from the
    persistent cache), ``hits`` of them loaded, and ``secs`` spent
    tracing, lowering and building them (a load skips only the last)."""

    def __init__(self):
        import jax
        self.n = self.hits = 0
        self.secs = 0.0

        def on_duration(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.n += 1
            if event in ("/jax/core/compile/backend_compile_duration",
                         "/jax/core/compile/jaxpr_trace_duration",
                         "/jax/core/compile/jaxpr_to_mlir_module_duration"):
                self.secs += duration

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def snap(self):
        return (self.n, self.hits, self.secs)


class Context:
    """What a traffic driver gets: the cell, the seed, the window
    length, the tracing switch, and the hooks that mark the window.

    ``on_chip`` False is the CPU rehearsal: the numpy backend, no
    profiler, no compile count and no memory reading."""

    def __init__(self, cell, seed: int, seconds: float, trace: bool,
                 t_start: float, on_chip: bool = True):
        self.cell = cell
        self.config = cell.config
        self.params = cell.params
        self.seed = seed
        self.seconds = seconds
        self.trace = trace
        self.t_start = t_start
        self.on_chip = on_chip
        self.backend = "auto" if on_chip else "numpy"
        self.compiles = Compiles() if on_chip else None
        self.window = None            # (t0, t1), host perf_counter
        self.window_compiles = None
        self.memory_peak_bytes = None
        self._profiling = False

    @classmethod
    def rehearsal(cls, config: dict, params: dict, seed: int,
                  seconds: float, trace: bool = False) -> "Context":
        """A toy-size run's context for the CPU (see ``on_chip``)."""
        cell = types.SimpleNamespace(name="rehearsal", chips=1,
                                     config=config, params=params)
        return cls(cell, seed, seconds, trace, time.perf_counter(),
                   on_chip=False)

    def built(self) -> int:
        """XLA programs built so far (0 off the chip)."""
        return self.compiles.n if self.compiles is not None else 0

    def log(self, msg: str) -> None:
        if self.on_chip:
            print(f"[{self.cell.name}] {msg}", file=sys.stderr, flush=True)

    def annotate(self, name: str):
        """A profiler annotation around one call into a layer (traced
        runs on the chip only)."""
        if not self._profiling:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(ANNOTATION_PREFIX + name)

    def window_begin(self) -> float:
        self._c0 = self.compiles.snap() if self.compiles is not None else (0, 0, 0.0)
        if self.trace and self.on_chip:
            import jax
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.host_tracer_level = 1
            opts.python_tracer_level = 0
            jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
            self._profiling = True
            self._window_ann = jax.profiler.TraceAnnotation(
                ANNOTATION_PREFIX + "window")
            self._window_ann.__enter__()
        t0 = time.perf_counter()
        self.window = (t0, None)
        return t0

    def window_end(self) -> float:
        t1 = time.perf_counter()
        self.window = (self.window[0], t1)
        c1 = self.compiles.snap() if self.compiles is not None else (0, 0, 0.0)
        # programs built in the window: (all, loaded from the cache, s)
        self.window_compiles = tuple(b - a for a, b in zip(self._c0, c1))
        if self._profiling:
            import jax
            self._window_ann.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self._profiling = False
        return t1

    def read_memory(self) -> None:
        """Peak device memory of the fullest chip, read once the window
        has closed and before the reference runs."""
        if not self.on_chip:
            return
        import jax
        peaks = [d.memory_stats().get("peak_bytes_in_use", 0)
                 for d in jax.local_devices()[:self.cell.chips]]
        self.memory_peak_bytes = int(max(peaks))


def percentile(values, q: float) -> float:
    """The nearest-rank q-th percentile (the smallest value with at
    least q% of the values at or below it)."""
    v = sorted(values)
    if not v:
        raise ValueError("percentile of no values")
    k = max(1, -(-len(v) * q // 100))
    return float(v[int(k) - 1])
