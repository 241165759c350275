"""Faults planted under the benchmark's timed path, and its control.

Each entry patches the system under test for the length of a ``with``
block; a run made inside one must come out not correct:

* ``answer_altered``  the backend adds 1 to the first count of every
  flush, where the counts are produced;
* ``half_batch``      the backend counts only the first half of each
  flush's requests and returns zeros for the rest;
* ``refresh_unchanged``  a stream's refresh returns without publishing:
  the state is left unchanged;
* ``ingest_half``     a stream ingests only the first half of each batch;
* ``control``         the plain reference in the program's place, with
  its supports summed in bfloat16, the precision a cheaper counter
  would bring (the system states exact integer supports).

``python tests/bench/faults.py --workload <cell> --seed <n> --seconds
<s> --fault <name>`` runs one cell with one of them in place, through
the benchmark's own driver, and prints the numbers its check compared.
On a chip it runs at the cell's own size; ``--cpu`` makes it a toy-size
rehearsal with the numpy backend.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BENCH = os.path.join(ROOT, "benchmarks", "fpm_bench")
for p in (os.path.join(ROOT, "src"), BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)


@contextlib.contextmanager
def patched(obj, name, value):
    old = getattr(obj, name)
    setattr(obj, name, value)
    try:
        yield
    finally:
        setattr(obj, name, old)


def _backends():
    from repro.core import join_backend as jb
    return (jb.NumpyBackend, jb._PallasBackend)


@contextlib.contextmanager
def answer_altered():
    with contextlib.ExitStack() as stack:
        for cls in _backends():
            orig = cls.sweep_many

            def sweep_many(self, arena, requests, _orig=orig):
                out = _orig(self, arena, requests)
                for c in out:
                    if len(c):
                        c[0] += 1
                        break
                return out
            stack.enter_context(patched(cls, "sweep_many", sweep_many))
        yield


@contextlib.contextmanager
def half_batch():
    import numpy as np
    with contextlib.ExitStack() as stack:
        for cls in _backends():
            orig = cls.sweep_many

            def sweep_many(self, arena, requests, _orig=orig):
                keep = (len(requests) + 1) // 2
                out = _orig(self, arena, list(requests[:keep]))
                return out + [np.zeros(len(r.ext_handles), np.int64)
                              for r in requests[keep:]]
            stack.enter_context(patched(cls, "sweep_many", sweep_many))
        yield


@contextlib.contextmanager
def refresh_unchanged():
    from repro.core.streaming import StreamingMiner
    orig = StreamingMiner.refresh
    state = {"first": True}

    def refresh(self, before_publish=None):
        if state["first"]:          # set-up's first generation stands
            state["first"] = False
            return orig(self, before_publish)
        return None
    with patched(StreamingMiner, "refresh", refresh):
        yield


@contextlib.contextmanager
def ingest_half():
    from repro.core.streaming import StreamingMiner
    orig = StreamingMiner.ingest

    def ingest(self, batch):
        batch = list(batch)
        return orig(self, batch[:max(1, len(batch) // 2)])
    with patched(StreamingMiner, "ingest", ingest):
        yield


def _bf16():
    import ml_dtypes
    return ml_dtypes.bfloat16


def _reference_rows(bitmaps):
    """uint32 item words -> the reference's uint64 rows."""
    import numpy as np
    w32 = bitmaps.shape[1]
    if w32 % 2:
        bitmaps = np.concatenate(
            [bitmaps, np.zeros((bitmaps.shape[0], 1), np.uint32)], axis=1)
    return np.ascontiguousarray(bitmaps).view(np.uint64)


@contextlib.contextmanager
def control():
    """The reference in the program's place, counting in bfloat16."""
    import reference as ref
    from repro.core import fpm, streaming
    from repro.core.fpm import MiningMetrics
    from repro.core.tidlist import pack_database

    def lossy_mine(bitmaps, min_support, **kw):
        n = bitmaps.shape[1] * 32
        rows = _reference_rows(bitmaps)
        table = ref.mine(rows, [min_support], [n], count_dtype=_bf16())
        return ref.frequent_at(table, 0, min_support), MiningMetrics()

    orig_init = streaming.StreamingMiner.__init__
    orig_ingest = streaming.StreamingMiner.ingest
    orig_refresh = streaming.StreamingMiner.refresh

    def init(self, n_items, min_support, *, initial_db=(), **kw):
        orig_init(self, n_items, min_support, initial_db=initial_db, **kw)
        self._control_db = [list(t) for t in initial_db]

    def ingest(self, batch):
        self._control_db += [list(t) for t in batch]
        return orig_ingest(self, batch)

    def refresh(self, before_publish=None):
        rep = orig_refresh(self, before_publish)
        snap = self._snapshot
        n = len(self._control_db)
        rows = _reference_rows(pack_database(self._control_db,
                                             self.n_items))
        table = ref.mine(rows, [snap.min_support], [n],
                         count_dtype=_bf16())
        self._snapshot = streaming.PatternSnapshot(
            snap.generation, snap.n_transactions, snap.min_support,
            ref.frequent_at(table, 0, snap.min_support))
        return rep

    with contextlib.ExitStack() as stack:
        stack.enter_context(patched(fpm, "mine", lossy_mine))
        stack.enter_context(patched(streaming.StreamingMiner, "__init__",
                                    init))
        stack.enter_context(patched(streaming.StreamingMiner, "ingest",
                                    ingest))
        stack.enter_context(patched(streaming.StreamingMiner, "refresh",
                                    refresh))
        yield


FAULTS = {"answer_altered": answer_altered, "half_batch": half_batch,
          "refresh_unchanged": refresh_unchanged,
          "ingest_half": ingest_half, "control": control}


def run_with(fault: str, workload: str, seed: int, seconds: float,
             on_chip: bool) -> dict:
    """One run of ``workload`` with ``fault`` in place; the traffic driver's
    output (``correct``, ``checks`` ...)."""
    from harness import Cell, Context, SpecError
    try:
        cell = Cell(workload)
    except SpecError:             # a cell not yet in BENCHMARK.json
        cell = Cell.unlisted(workload)
    driver = cell.driver()
    with FAULTS[fault]():
        if on_chip:
            from repro.compile_cache import enable_compile_cache
            enable_compile_cache()
            # the readings need the window's answers, not a warm window
            cell.params["max_warmup"] = 1
            return driver.run(Context(cell, seed, seconds, False,
                                      time.perf_counter()))
        sizes = CONTROL_TOY[cell.params["kind"]] if fault == "control" \
            else None
        return driver.rehearse(cell.config, cell.params, seed, seconds,
                               sizes=sizes)


# bfloat16 holds every integer up to 256, so the control's rehearsal
# needs supports past that: more transactions than the traffic drivers' TOY
CONTROL_TOY = {"mine": {"n_transactions": 8000},
               "serve": {"initial_transactions": 8000,
                         "batch_transactions": 400, "rate_qps": 5.0,
                         "max_cycles": 20, "warmup_seconds": 0.5}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--fault", required=True, choices=sorted(FAULTS))
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args(argv)
    out = run_with(args.fault, args.workload, args.seed, args.seconds,
                   on_chip=not args.cpu)
    print(json.dumps({"fault": args.fault, "workload": args.workload,
                      "seed": args.seed, "correct": out["correct"],
                      "checks": out["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
