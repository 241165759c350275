"""Traffic kind ``serve``: a stream kept fresh under open-loop queries.

Set-up generates the stream (the configuration's generator at its fixed
``data_seed``, items relabelled and the initial block and the rest
shuffled apart by ``--seed``), builds a ``StreamingMiner`` over the
initial transactions, publishes its first generation, and warms up with
rounds of the window's own traffic (cycles under the cell's queries)
until a round builds no new XLA program.

The window runs two things at once:

* cycles back to back from its start, each ``ingest`` of a fresh batch
  and the ``refresh`` that publishes it; the cycles end with the first
  that finishes after ``--seconds``. ``refresh_s`` is that span over
  the cycles in it: staleness, from a batch's ingest to the publication
  of the snapshot that holds it;
* Poisson arrivals of queries at the cell's fixed rate, from one
  generator thread to a pool of client threads (an open loop: a slow
  answer never delays the next arrival). Each query is timed from when
  it was due until its answer; ``query_p95_ms`` is the 95th percentile
  over every query due in the window, and one that fails or never
  answers counts as missing every limit.

The mix: ``support()`` of published itemsets chosen Zipf(0.99) over a
seeded scramble of the first generation's itemsets; ``support_many()``
of 1-8 itemsets of 3-5 items, drawn from the stream's transactions with
at least one item that is frequent at no generation (so no generation
ever counts them, and each one sweeps), never repeated; and
``top_k(prefix, 10)`` with the prefix empty or one frequent item chosen
Zipf(0.99). The arrival gaps, the kinds, their draws and the sizes of
the fresh itemsets come from the mix's fixed ``schedule_seed``;
``--seed`` only orders them, so every seed offers the same work.

Once the window has closed, every published snapshot and every answer is
checked against the plain reference. An answer is right when it equals
the reference's answer at a generation that was published while the
query ran.
"""
from __future__ import annotations

import math
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List, Tuple

import numpy as np

import reference as ref
from harness import Context, percentile
from traffic.mine import engine_kwargs, make_database, spans_of

KIND = "serve"
KINDS = ("support", "support_many", "top_k")


def zipf_ranks(rng: np.random.Generator, n: int, size: int,
               theta: float) -> np.ndarray:
    p = 1.0 / np.arange(1, n + 1) ** theta
    return rng.choice(n, size, p=p / p.sum())


def rare_items(db, threshold: int) -> np.ndarray:
    """Items whose support over the whole stream stays below the
    threshold: frequent at no generation."""
    counts = np.bincount(db.items, minlength=db.n_items)
    return np.nonzero(counts < threshold)[0]


class FreshItemsets:
    """Itemsets no generation counts, each from one transaction of the
    stream that holds a rare item: that item plus others of the same
    transaction. No itemset is handed out twice."""

    def __init__(self, db, rare: np.ndarray, rng: np.random.Generator):
        self.db = db
        self.rng = rng
        self.is_rare = np.zeros(db.n_items, bool)
        self.is_rare[rare] = True
        lens = db.lengths()
        has_rare = np.add.reduceat(
            self.is_rare[db.items].astype(np.int64),
            db.offsets[:-1]) * (lens > 0)
        self.cand = np.nonzero((has_rare > 0) & (lens >= 3))[0]
        if not len(self.cand):
            raise ValueError("no transaction of 3 or more items holds a "
                             "rare item")
        self.seen = set()

    def take(self, size: int) -> Tuple[int, ...]:
        db, rng = self.db, self.rng
        for _ in range(1000):
            t = int(self.cand[rng.integers(len(self.cand))])
            items = db.items[db.offsets[t]:db.offsets[t + 1]]
            r = items[self.is_rare[items]]
            first = int(r[rng.integers(len(r))])
            rest = items[items != first]
            k = min(int(size), len(items)) - 1
            x = tuple(sorted([first, *map(int, rng.choice(rest, k,
                                                          replace=False))]))
            if x not in self.seen:
                self.seen.add(x)
                return x
        raise ValueError("could not draw a fresh itemset")


def make_schedule(params: dict, seed: int, seconds: float, rate: float,
                  n_published: int, n_frequent_items: int) -> List[dict]:
    """The window's queries: gaps, kinds and draws from the mix's fixed
    schedule seed, put in an order drawn from ``seed``. A
    ``support_many`` draw is the list of its itemsets' sizes."""
    base = np.random.default_rng(int(params["schedule_seed"]))
    n = max(1, int(math.ceil(rate * seconds)))
    gaps = base.exponential(1.0 / rate, n)
    shares = params["mix"]
    counts = [int(round(shares[k] * n)) for k in KINDS[1:]]
    counts.insert(0, n - sum(counts))
    kinds = np.repeat(np.arange(len(KINDS)), counts)
    theta = float(params["zipf_theta"])
    lo, hi = params["fresh_size"]
    draws = {
        "support": list(zipf_ranks(base, n_published, counts[0], theta)),
        "support_many": [list(base.integers(lo, hi + 1, m)) for m in
                         base.integers(params["many_min"],
                                       params["many_max"] + 1, counts[1])],
        "top_k": [(-1 if base.random() < params["top_k_empty_share"]
                   else int(zipf_ranks(base, n_frequent_items, 1,
                                       theta)[0]))
                  for _ in range(counts[2])]}
    rng = np.random.default_rng(seed)
    gaps = gaps[rng.permutation(n)]
    kinds = kinds[rng.permutation(n)]
    for k in KINDS:
        order = rng.permutation(len(draws[k]))
        draws[k] = [draws[k][i] for i in order]
    due = np.cumsum(gaps)
    out, pos = [], {k: 0 for k in KINDS}
    for d, ki in zip(due, kinds):
        if d > seconds:
            break
        k = KINDS[ki]
        out.append({"due": float(d), "kind": k, "draw": draws[k][pos[k]]})
        pos[k] += 1
    return out


class Stream:
    """The program's stream with a record of what it published."""

    def __init__(self, sm, batches: List[List[List[int]]]):
        self.sm = sm
        self.batches = batches
        self.next = 0
        self.snapshots = []

    def publish_record(self) -> None:
        s = self.sm.snapshot
        self.snapshots.append((s.generation, s.n_transactions,
                               s.min_support, dict(s.supports)))

    def cycle(self, ctx) -> None:
        if self.next >= len(self.batches):
            raise RuntimeError("the stream ran out of transactions; "
                               "raise max_cycles")
        with ctx.annotate("ingest"):
            self.sm.ingest(self.batches[self.next])
        self.next += 1
        with ctx.annotate("refresh"):
            self.sm.refresh()
        self.publish_record()


def ask(srv, ctx, q: dict) -> dict:
    """Run one query; record its answer, time and generations."""
    g0 = srv.snapshot.generation
    try:
        with ctx.annotate("query." + q["kind"]):
            if q["kind"] == "support":
                ans = srv.support(q["itemset"])
            elif q["kind"] == "support_many":
                ans = srv.support_many(q["itemsets"])
            else:
                ans = srv.top_k(q["prefix"], q["k"])
        q["answer"] = ans
    except Exception as e:      # noqa: BLE001 - counted as failed
        q["error"] = repr(e)
    q["done"] = time.perf_counter()
    q["gens"] = (g0, srv.snapshot.generation)
    return q


class Serving:
    """A published stream, its server, and what queries draw from."""

    def __init__(self, ctx, params, db, sm, stream, ms: int, PatternServer):
        self.ctx, self.params, self.db, self.sm = ctx, params, db, sm
        self.stream = stream
        stream.sm.refresh()
        stream.publish_record()
        self.srv = PatternServer(sm)
        first = stream.snapshots[0][3]
        self.rng = np.random.default_rng(ctx.seed)
        published = sorted(first)
        self.published = [published[i]
                          for i in self.rng.permutation(len(published))]
        items = sorted(x[0] for x in first if len(x) == 1)
        self.items = [items[i] for i in self.rng.permutation(len(items))]
        self.fresh = FreshItemsets(db, rare_items(db, ms), self.rng)
        self.done: List[dict] = []       # set-up's queries, also checked

    def plan(self, rate: float, seconds: float, order: int) -> List[dict]:
        """``seconds`` of queries at ``rate``, in the order ``order``
        draws, with their arguments."""
        plan = make_schedule(self.params, order, seconds, rate,
                             len(self.published), len(self.items))
        return [self.bind(q) for q in plan]

    def bind(self, q: dict) -> dict:
        if q["kind"] == "support":
            q["itemset"] = self.published[q["draw"]]
        elif q["kind"] == "support_many":
            q["itemsets"] = [self.fresh.take(s) for s in q["draw"]]
        else:
            q["prefix"] = () if q["draw"] < 0 else (self.items[q["draw"]],)
            q["k"] = int(self.params["top_k"])
        return q

    def warm_up(self) -> int:
        """Rounds of the window's own traffic, ``warmup_seconds`` each,
        until a round builds no new XLA program: the flush shapes a
        refresh meets depend on the queries that coalesce with it.
        Returns the queries and cycles that failed."""
        ctx, params = self.ctx, self.params
        seconds = float(params["warmup_seconds"])
        failed = 0
        for i in range(int(params["max_warmup"])):
            c0 = ctx.built()
            plan = self.plan(float(params["rate_qps"]), seconds,
                             order=ctx.seed + 1 + i)
            r = self.run(plan, seconds)
            self.done += r["queries"]
            failed += r["failed_cycles"] + sum(1 for q in r["queries"]
                                               if "error" in q)
            built = ctx.built() - c0
            ctx.log(f"warm-up round {i}: {r['cycles']} cycles, generation "
                    f"{self.stream.snapshots[-1][0]}, "
                    f"{len(self.stream.snapshots[-1][3])} itemsets, "
                    f"{built} programs built")
            if not built or failed:
                break
        return failed

    def run(self, plan: List[dict], seconds: float,
            measured: bool = False) -> dict:
        """Cycles back to back and the plan's queries, open loop, for
        ``seconds``; every query waited for (up to ``answer_grace_s``
        past the cycles). ``measured`` marks the benchmark's window."""
        ctx, srv, params = self.ctx, self.srv, self.params
        late: List[float] = []
        pool = ThreadPoolExecutor(max_workers=int(params["clients"]),
                                  thread_name_prefix="bench-client")
        futures = []
        t_begin = ctx.window_begin() if measured else time.perf_counter()

        def generate():
            for q in plan:
                due = t_begin + q["due"]
                wait = due - time.perf_counter()
                if wait > 0:
                    time.sleep(wait)
                late.append(time.perf_counter() - due)
                futures.append(pool.submit(ask, srv, ctx, dict(q, due=due)))

        gen = threading.Thread(target=generate, name="bench-generator")
        gen.start()
        cycles = failed_cycles = 0
        deadline = t_begin + seconds
        while True:
            try:
                self.stream.cycle(ctx)
            except Exception as e:    # noqa: BLE001 - counted as failed
                ctx.log(f"cycle failed: {e!r}")
                failed_cycles += 1
            cycles += 1
            t_cycles = time.perf_counter()
            if t_cycles >= deadline or failed_cycles:
                break
        gen.join()
        pool.shutdown(wait=False)
        grace = time.perf_counter() + float(params["answer_grace_s"])
        answered: List[dict] = []
        for f in futures:
            try:
                answered.append(f.result(
                    timeout=max(0.0, grace - time.perf_counter())))
            except Exception:          # noqa: BLE001 - never answered
                answered.append({"error": "no answer", "kind": "none"})
        t_end = ctx.window_end() if measured else time.perf_counter()
        return {"queries": answered, "cycles": cycles,
                "failed_cycles": failed_cycles, "late": late,
                "t_begin": t_begin, "t_cycles": t_cycles, "t_end": t_end}


def latencies(queries: List[dict]) -> Tuple[List[float], Dict[str, list]]:
    """Each query's ms from due to answer (inf when it failed), all of
    them and by kind."""
    lat: Dict[str, List[float]] = {k: [] for k in KINDS}
    every = []
    for q in queries:
        t = (1e3 * (q["done"] - q["due"]) if "error" not in q
             else math.inf)
        every.append(t)
        if q["kind"] in lat:
            lat[q["kind"]].append(t)
    return every, lat


def load_of(win: dict, every: List[float]) -> dict:
    """Whether the offered load was sustained: the generator's lateness,
    and a backlog that grows shows as a later fifth slower than the
    first."""
    late = win["late"]
    fifth = max(1, len(every) // 5)
    return {"late_p95_ms": percentile(late, 95) * 1e3 if late else None,
            "late_max_ms": max(late) * 1e3 if late else None,
            "first_fifth_p50_ms": percentile(every[:fifth], 50)
            if every else None,
            "last_fifth_p50_ms": percentile(every[-fifth:], 50)
            if every else None}


def run(ctx) -> dict:
    from repro.core.streaming import PatternServer, StreamingMiner
    from repro.obs import Tracer

    cfg, params = ctx.config, ctx.params
    n_init = int(params["initial_transactions"])
    batch = int(params["batch_transactions"])
    n_pool = n_init + batch * int(params["max_cycles"])
    db = make_database(cfg, ctx.seed, n_transactions=n_pool,
                       blocks=[n_init])
    lists = db.to_lists()
    # an absolute count, held as the stream grows (see the config)
    ms = ref.min_support_count(float(cfg["min_support"]), n_init)
    eng = engine_kwargs(cfg, db.n_items, ctx.backend)
    tracer = Tracer(ring_size=1 << 18) if ctx.trace else None
    sm = StreamingMiner(db.n_items, ms, initial_db=lists[:n_init],
                        tracer=tracer, **eng)
    stream = Stream(sm, [lists[i:i + batch]
                         for i in range(n_init, len(lists), batch)])
    try:
        s = Serving(ctx, params, db, sm, stream, ms, PatternServer)
        warm_failed = s.warm_up()
        plan = s.plan(float(params["rate_qps"]), ctx.seconds, ctx.seed)
        sweeps0 = sm.query_sweeps
        win = s.run(plan, ctx.seconds, measured=True)
        ctx.read_memory()
        swept = sm.query_sweeps - sweeps0
    finally:
        sm.close()                 # the reference runs on freed state
    spans = spans_of(tracer) if tracer is not None else []
    dropped = tracer.dropped() if tracer is not None else 0
    window_q, cycles = win["queries"], win["cycles"]
    t_begin, t_cycles = win["t_begin"], win["t_cycles"]
    last = stream.snapshots[-1]
    ctx.log(f"window: {cycles} cycles in {t_cycles - t_begin} s, "
            f"{len(window_q)} queries due, {swept} itemsets swept, "
            f"generation {last[0]} with {len(last[3])} itemsets at "
            f"min_support {last[2]}")
    every, lat = latencies(window_q)
    ctx.log(f"load: {load_of(win, every)}")

    checks = check_stream(db, ms, n_init, batch, stream.snapshots,
                          s.done + window_q)
    failed = (sum(1 for q in window_q if "error" in q)
              + win["failed_cycles"])
    correct = failed == 0 and warm_failed == 0 and all(
        v["value"] <= v["limit"] for v in checks.values())
    metrics = {"refresh_s": (t_cycles - t_begin) / cycles,
               "query_p95_ms": percentile(every, 95) if every else math.inf,
               "setup_s": t_begin - ctx.t_start}
    record = {"kind": KIND, "window": [t_begin, win["t_end"]],
              "spans": spans, "dropped": dropped, "latency_ms": lat}
    return {"correct": correct, "attempted": len(window_q) + cycles,
            "failed": failed, "metrics": metrics, "record": record,
            "checks": checks}


def check_stream(db, ms: int, n_init: int, batch: int,
                 snapshots: list, queries: List[dict]) -> dict:
    """Every published snapshot and every answer against the plain
    reference."""
    last_gen = max(s[0] for s in snapshots)
    bounds = [n_init + batch * g for g in range(last_gen)]
    thresholds = [ms] * len(bounds)
    n = bounds[-1]
    cut = int(db.offsets[n])
    rows = ref.item_rows(db.tx_ids()[:cut], db.items[:cut], db.n_items, n)
    table = ref.mine(rows, thresholds, bounds)
    results: Dict[int, dict] = {}      # generation index -> its result
    tops: Dict[tuple, list] = {}

    def result(g: int) -> dict:
        if g not in results:
            results[g] = ref.frequent_at(table, g, thresholds[g])
        return results[g]

    def top(g: int, prefix, k: int) -> list:
        if (g, prefix, k) not in tops:
            tops[g, prefix, k] = ref.top_k(result(g), prefix, k)
        return tops[g, prefix, k]

    # one publication per refresh: the i-th record is generation i + 1
    stale = sum(1 for i, s in enumerate(snapshots) if s[0] != i + 1)
    snaps_wrong = missing = extra = wrong = 0
    for gen, n_tx, ms, sup in snapshots:
        g = gen - 1
        bad = not (0 <= g < len(bounds)) or n_tx != bounds[g] \
            or ms != thresholds[g]
        m, e, w = (0, 0, 0) if bad else ref.compare(sup, result(g))
        snaps_wrong += bool(bad or m or e or w)
        missing, extra, wrong = missing + m, extra + e, wrong + w

    many = [x for q in queries if q.get("kind") == "support_many"
            for x in q["itemsets"]]
    many_sup = dict(zip(many, ref.supports_of(rows, many, bounds)))
    answers_wrong = 0
    for q in queries:
        if "error" in q:
            continue
        g0, g1 = q["gens"]
        ok = False
        for gen in range(g0, g1 + 1):
            g = gen - 1
            if q["kind"] == "support":
                x = q["itemset"]
                want = (table[x] if x in table else
                        ref.supports_of(rows, [x], bounds)[0])
                ok = q["answer"] == int(want[g])
            elif q["kind"] == "support_many":
                ok = q["answer"] == [int(many_sup[x][g])
                                     for x in q["itemsets"]]
            else:
                ok = q["answer"] == top(g, q["prefix"], q["k"])
            if ok:
                break
        answers_wrong += not ok
    return {"generations_stale": {"value": stale, "limit": 0},
            "snapshots_wrong": {"value": snaps_wrong, "limit": 0},
            "itemsets_missing": {"value": missing, "limit": 0},
            "itemsets_extra": {"value": extra, "limit": 0},
            "supports_wrong": {"value": wrong, "limit": 0},
            "answers_wrong": {"value": answers_wrong, "limit": 0}}


# the toy size of a CPU rehearsal
TOY = {"initial_transactions": 1800, "batch_transactions": 100,
       "rate_qps": 20.0, "max_cycles": 60, "warmup_seconds": 0.5}


def rehearse(config: dict, params: dict, seed: int, seconds: float,
             trace: bool = False, sizes: dict = None) -> dict:
    """The same driver at toy size with the numpy backend on the CPU:
    no device check, no profiler, and no result line."""
    return run(Context.rehearsal(config, dict(params, **(sizes or TOY)),
                                 seed, seconds, trace))
