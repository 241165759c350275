"""Traffic kind ``mine``: complete batch mines of one database, back to
back.

Set-up generates the deployment's database (the configuration's
generator under its fixed ``data_seed``, items relabelled and
transactions shuffled by ``--seed``), packs it with the program's
``pack_database``, and warms up with whole mines until one builds no new
XLA program. The window then runs ``fpm.mine`` back to back from its
start; it ends with the first mine that finishes after ``--seconds``.
``mine_s`` is that span over the mines in it, each timed around the
whole call, arena upload included.

Every mine, warm-up included, is compared itemset by itemset with the
plain reference's complete result once the window has closed.
"""
from __future__ import annotations

import time
from typing import Dict, List

import reference as ref
from datagen.quest import QuestParams, generate, relabel
from harness import Context

KIND = "mine"


def make_database(config: dict, seed: int, n_transactions=None,
                  blocks=()):
    """The cell's transactions: the configuration's generator at its
    fixed data seed, relabelled and shuffled by ``seed``."""
    gen = dict(config["generator"])
    if n_transactions is not None:
        gen["D"] = n_transactions
    base = generate(QuestParams.from_config(gen), int(config["data_seed"]))
    return relabel(base, seed, blocks)


def engine_kwargs(config: dict, n_items: int, backend: str) -> dict:
    eng = dict(config["engine"])
    if eng.get("max_k") == "n_items":      # mined to completion
        eng["max_k"] = n_items
    return dict(eng, backend=backend)


def spans_of(tracer) -> list:
    """The program's spans as [name, lane, t0, t1] on the host's
    perf_counter clock."""
    epoch = tracer._epoch
    return [[ev.name, ev.lane, epoch + ev.ts, epoch + ev.ts + ev.dur]
            for ev in tracer.events() if ev.ph == "X"]


def check_results(db, ms: int, results: List[Dict]) -> dict:
    """Compare every result with the reference's complete result."""
    n = len(db)
    rows = ref.item_rows(db.tx_ids(), db.items, db.n_items, n)
    want = ref.frequent_at(ref.mine(rows, [ms], [n]), 0, ms)
    wrong_mines = missing = extra = wrong = 0
    for got in results:
        m, e, w = ref.compare(got, want)
        missing, extra, wrong = missing + m, extra + e, wrong + w
        wrong_mines += bool(m or e or w)
    return {"mines_wrong": {"value": wrong_mines, "limit": 0},
            "itemsets_missing": {"value": missing, "limit": 0},
            "itemsets_extra": {"value": extra, "limit": 0},
            "supports_wrong": {"value": wrong, "limit": 0}}


def run(ctx) -> dict:
    from repro.core.fpm import mine
    from repro.core.tidlist import pack_database
    from repro.obs import Tracer

    cfg, params = ctx.config, ctx.params
    db = make_database(cfg, ctx.seed, params.get("n_transactions"))
    ms = ref.min_support_count(float(cfg["min_support"]), len(db))
    bitmaps, counts = pack_database(db.to_lists(), db.n_items,
                                    return_counts=True)
    kw = engine_kwargs(cfg, db.n_items, ctx.backend)
    kw["item_counts"] = counts
    results: List[Dict] = []

    for i in range(int(params["max_warmup"])):
        c0 = ctx.built()
        res, _ = mine(bitmaps, ms, **kw)
        results.append(res)
        built = ctx.built() - c0
        ctx.log(f"warm-up mine {i}: {len(res)} itemsets, {built} "
                f"programs built")
        if not built:
            break

    ops: List[dict] = []
    failed = 0
    t_begin = ctx.window_begin()
    deadline = t_begin + ctx.seconds
    while True:
        tracer = Tracer(ring_size=1 << 16) if ctx.trace else None
        t0 = time.perf_counter()
        try:
            with ctx.annotate("mine"):
                res, met = mine(bitmaps, ms, trace=tracer, **kw)
        except Exception as e:     # noqa: BLE001 - counted as failed
            ctx.log(f"mine failed: {e!r}")
            failed += 1
            res = met = None
        t1 = time.perf_counter()
        op = {"t0": t0, "t1": t1}
        if met is not None:
            results.append(res)
            op.update(flushes=int(met.flushes),
                      requests=int(round(met.batch_occupancy
                                         * met.flushes)),
                      dense_sweeps=int(met.dense_sweeps),
                      sparse_sweeps=int(met.sparse_sweeps))
        if tracer is not None:
            op.update(spans=spans_of(tracer), dropped=tracer.dropped())
        ops.append(op)
        if t1 >= deadline or met is None:
            break
    t_end = ctx.window_end()
    ctx.read_memory()
    ctx.log(f"window: {len(ops)} mines in {t_end - t_begin} s, "
            f"min_support={ms}, {len(results[-1])} itemsets")

    checks = check_results(db, ms, results)
    correct = failed == 0 and all(v["value"] <= v["limit"]
                                  for v in checks.values())
    record = {"kind": KIND, "window": [t_begin, t_end], "ops": ops,
              "n_workers": int(kw["n_workers"])}
    return {"correct": correct, "attempted": len(ops), "failed": failed,
            "metrics": {"mine_s": (t_end - t_begin) / len(ops),
                        "setup_s": t_begin - ctx.t_start},
            "record": record, "checks": checks}


# the toy size of a CPU rehearsal
TOY = {"n_transactions": 2000}


def rehearse(config: dict, params: dict, seed: int, seconds: float,
             trace: bool = False, sizes: dict = None) -> dict:
    """The same driver at toy size with the numpy backend on the CPU:
    no device check, no profiler, and no result line."""
    return run(Context.rehearsal(config, dict(params, **(sizes or TOY)),
                                 seed, seconds, trace))
