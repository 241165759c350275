"""Observability: tracer rings, exporters, schema, traced engine runs."""
import json
import threading

import pytest
from _hyp import given, settings, st

from repro.core.fpm import mine
from repro.core.tidlist import pack_database
from repro.data.transactions import load
from repro.obs import (LatencyRecorder, MetricsRegistry, Tracer,
                       check_nesting, chrome_trace, region, schema,
                       summary_table, time_in_state, write_chrome_trace)


@pytest.fixture(scope="module")
def small_db():
    db, p = load("mushroom", seed=0)
    return [t for t in db[:300]], p


def _span(tr, name, t0, dt, cat="task"):
    """Synthesize a span with exact [t0, t0+dt] extent on the calling
    thread's ring (bypasses the wall clock for deterministic tests)."""
    tr._ring().append(("X", name, cat, t0, dt, None))


# ---------------------------------------------------------------- tracer --

def test_span_records_duration_and_args():
    tr = Tracer()
    t0 = tr.now()
    tr.span("work", t0, cat="task", args={"k": 1})
    (ev,) = tr.events()
    assert ev.ph == "X" and ev.name == "work" and ev.cat == "task"
    assert ev.dur >= 0.0 and ev.args == {"k": 1}


def test_ring_overflow_drops_oldest_without_corruption():
    tr = Tracer(ring_size=8)
    for i in range(20):
        _span(tr, f"s{i}", float(i), 0.5)
    evs = tr.events()
    # last cap events survive, in append order, uncorrupted
    assert [e.name for e in evs] == [f"s{i}" for i in range(12, 20)]
    assert all(e.dur == 0.5 for e in evs)
    assert tr.dropped() == 12
    assert "dropped" in str(chrome_trace(tr).get("otherData", {}))


@pytest.mark.parametrize("n", [7, 8, 9])
def test_ring_at_and_around_capacity_keeps_the_newest(n):
    # n == ring_size leaves the write slot wrapped to 0 with nothing
    # dropped: the whole ring, oldest first
    tr = Tracer(ring_size=8)
    for i in range(n):
        _span(tr, f"s{i}", float(i), 0.5)
    assert [e.name for e in tr.events()] == \
        [f"s{i}" for i in range(max(0, n - 8), n)]
    assert tr.dropped() == max(0, n - 8)


def test_region_records_one_span_with_the_args_it_was_given():
    tr = Tracer()
    with region(tr, "work", cat="task") as args:
        args["k"] = 1
    with region(tr, "empty"):
        pass
    with pytest.raises(ValueError):
        with region(tr, "raised", cat="task"):
            raise ValueError("x")
    evs = tr.events()
    assert [(e.ph, e.name, e.cat) for e in evs] == [
        ("X", "work", "task"), ("X", "empty", "span"),
        ("X", "raised", "task")]
    assert evs[0].args == {"k": 1} and evs[1].args is None
    assert all(e.dur >= 0.0 for e in evs)


def test_region_without_a_tracer_is_one_shared_null_context():
    a, b = region(None, "x"), region(None, "y", cat="task")
    assert a is b
    with a as args:
        assert args is None


def test_tracer_writes_its_regions_into_the_xplane(small_db, tmp_path):
    import glob

    import jax
    from jax.profiler import ProfileData
    db, p = small_db
    bm, counts = pack_database(db, p.n_dense_items, return_counts=True)
    tr = Tracer()
    with jax.profiler.trace(str(tmp_path)):
        mine(bm, int(0.3 * len(db)), n_workers=2, max_k=3,
             item_counts=counts, trace=tr)
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                        recursive=True)
    names = {ev.name for plane in ProfileData.from_file(path).planes
             for line in plane.lines for ev in line.events}
    assert {"repro:flush", "repro:level.plan", "repro:level.barrier",
            "repro:mine.start", "repro:task"} <= names
    assert {e.name for e in tr.events()} >= {
        n[len("repro:"):] for n in names if n.startswith("repro:")}


def test_ring_is_per_thread_and_lane_order_is_stable():
    tr = Tracer()
    tr.set_lane("driver", sort_index=0)
    _span(tr, "main", 0.0, 1.0)

    def worker(i):
        tr.set_lane(f"worker-{i}", sort_index=10 + i)
        _span(tr, f"w{i}", 0.0, 1.0)

    ts = [threading.Thread(target=worker, args=(i,)) for i in (1, 0)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    # sort_index, not registration order, decides display order
    assert tr.lane_names() == ["driver", "worker-0", "worker-1"]


def test_disabled_fast_path_is_structural(small_db):
    # the off switch is tracer=None at every site — a plain run must
    # not build rings anywhere
    db, p = small_db
    bm, counts = pack_database(db, p.n_dense_items, return_counts=True)
    res, met = mine(bm, int(0.3 * len(db)), policy="clustered",
                    n_workers=2, max_k=4, item_counts=counts)
    assert met.wall_s > 0


# ------------------------------------------------------------- exporters --

def test_nesting_well_formed_and_violation_detected():
    tr = Tracer()
    _span(tr, "child", 1.0, 2.0)
    _span(tr, "parent", 0.0, 10.0)
    _span(tr, "after", 11.0, 1.0)
    assert check_nesting(tr.events()) == []
    _span(tr, "straddle", 11.5, 2.0)   # starts inside "after", ends past
    bad = check_nesting(tr.events())
    assert len(bad) == 1 and "straddle" in bad[0]


def test_time_in_state_bills_nested_child_to_its_own_category():
    tr = Tracer()
    tr.set_lane("worker-0", sort_index=10)
    _span(tr, "sweep", 2.0, 3.0, cat="sweep")
    _span(tr, "task", 0.0, 10.0, cat="task")
    _span(tr, "park", 10.0, 4.0, cat="idle")
    (row,) = time_in_state(tr).values()
    assert row["sweep"] == pytest.approx(3.0)
    assert row["eval"] == pytest.approx(7.0)      # 10 − nested 3
    assert row["idle"] == pytest.approx(4.0)
    assert row["total"] == pytest.approx(14.0)
    assert row["extent"] == pytest.approx(14.0)
    table = summary_table(tr, wall_s=14.0)
    assert "worker-0" in table and "100.0%" in table


def test_chrome_trace_json_round_trip(tmp_path):
    tr = Tracer()
    tr.set_lane("driver", sort_index=0, pid=3)
    _span(tr, "level-2", 0.25, 0.5, cat="level")
    tr.counter("refresh_lag", {"s": 0.125})
    path = str(tmp_path / "t.trace.json")
    write_chrome_trace(tr, path)
    with open(path) as f:
        doc = json.load(f)
    evs = doc["traceEvents"]
    names = {e["ph"]: e for e in evs}
    assert {"M", "X", "C"} <= set(names)
    x = names["X"]
    assert x["ts"] == pytest.approx(0.25e6)       # µs
    assert x["dur"] == pytest.approx(0.5e6)
    assert x["pid"] == 3 and x["tid"] >= 1
    c = names["C"]
    assert c["args"] == {"s": 0.125}
    meta = [e for e in evs if e["ph"] == "M"]
    assert {"process_name", "thread_name", "thread_sort_index"} <= {
        m["name"] for m in meta}
    assert any(m["args"].get("name") == "host-3" for m in meta)


@settings(max_examples=25, deadline=None)
@given(st.lists(st.lists(st.integers(0, 7), min_size=1, max_size=30),
                min_size=1, max_size=4))
def test_merged_timeline_preserves_per_lane_order(lanes):
    """Property: events() merges rings lane by lane, and within every
    lane the collected order IS the append order — even across ring
    overflow (a small cap keeps only the newest suffix, still in
    order)."""
    tr = Tracer(ring_size=8)

    def emit(i, seq):
        tr.set_lane(f"lane-{i}", sort_index=i)
        for j, _ in enumerate(seq):
            _span(tr, f"{i}:{j}", float(j), 0.5)

    threads = [threading.Thread(target=emit, args=(i, seq))
               for i, seq in enumerate(lanes)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    by_lane = {}
    for ev in tr.events():
        by_lane.setdefault(ev.lane, []).append(ev.name)
    assert len(by_lane) == len(lanes)
    for i, seq in enumerate(lanes):
        got = [int(n.split(":")[1]) for n in by_lane[f"lane-{i}"]]
        want = list(range(len(seq)))[-8:]          # drop-oldest suffix
        assert got == want


# ---------------------------------------------------------------- schema --

def test_schema_builders_fill_defaults_and_validate():
    s = schema.scheduler_stats({"tasks_run": 5, "steals": 2,
                                "tasks_stolen": 4})
    schema.validate("scheduler", s)
    assert s["tasks_per_steal"] == pytest.approx(2.0)
    q = schema.query_stats({"hit": 1, "sweep": 2})
    schema.validate("query", q)
    assert q["queries"] == 3 and q["top_k"] == 0
    d = schema.device_stats({"device": 1, "flushes": 4,
                             "sweep_requests": 10, "host": 2})
    schema.validate("device", d)
    assert d["batch_occupancy"] == pytest.approx(2.5)
    schema.validate("host", schema.host_stats({"host": 1}))


def test_schema_validate_rejects_drift():
    with pytest.raises(ValueError, match="missing"):
        schema.validate("scheduler", {"tasks_run": 1})
    bad = schema.scheduler_stats({})
    bad["made_up"] = 7
    with pytest.raises(ValueError, match="off-schema"):
        schema.validate("scheduler", bad)
    bad2 = schema.query_stats({})
    bad2["hit"] = 1.5
    with pytest.raises(ValueError, match="must be int"):
        schema.validate("query", bad2)


def test_schema_merge_and_delta_recompose():
    a = schema.scheduler_stats({"tasks_run": 10, "steals": 2,
                                "tasks_stolen": 6})
    b = schema.scheduler_stats({"tasks_run": 4, "steals": 2,
                                "tasks_stolen": 2})
    m = schema.scheduler_stats(schema.merge_counters(
        [a, b], schema.SCHEDULER_COUNTERS))
    schema.validate("scheduler", m)
    assert m["tasks_run"] == 14 and m["tasks_per_steal"] == 2.0
    d = schema.delta_counters(m, b, schema.SCHEDULER_COUNTERS)
    assert d["tasks_run"] == 10 and "tasks_per_steal" not in d


def test_real_producers_conform_to_schema(small_db):
    db, p = small_db
    bm, counts = pack_database(db, p.n_dense_items, return_counts=True)
    res, met = mine(bm, int(0.3 * len(db)), policy="clustered",
                    n_workers=2, max_k=4, item_counts=counts)
    schema.validate("scheduler", met.scheduler)
    for row in met.per_device:
        schema.validate("device", row)


# -------------------------------------------------------------- registry --

def test_registry_snapshot_isolates_failing_source():
    reg = MetricsRegistry()
    reg.register("ok", lambda: {"x": 1})
    reg.register("boom", lambda: 1 / 0)
    snap = reg.snapshot()
    assert snap["ok"] == {"x": 1}
    assert "ZeroDivisionError" in snap["boom"]["error"]
    reg.unregister("boom")
    assert reg.names() == ["ok"]


def test_latency_recorder_exact_percentiles():
    rec = LatencyRecorder(cap=1000)
    for ms in range(1, 101):                       # 1..100 ms
        rec.record("hit", ms / 1000.0)
    p = rec.percentiles("hit")
    assert p["n"] == 100
    assert p["p50"] == pytest.approx(0.051)        # round(0.50·99) = 50
    assert p["p95"] == pytest.approx(0.095)        # round(0.95·99) = 94
    assert p["p99"] == pytest.approx(0.099)        # round(0.99·99) = 98
    assert p["max"] == pytest.approx(0.100)
    rec.record("sweep", 0.002, n=3)                # batched share
    assert rec.counts() == {"hit": 100, "sweep": 3}


# ---------------------------------------------------- traced engine runs --

def test_traced_mine_matches_untraced_and_covers_workers(small_db):
    """The acceptance run: traced bucket/clustered mine yields a
    Perfetto-loadable trace with one lane per worker carrying task +
    flush/sweep + steal spans, well-formed nesting, and per-worker
    time-in-state that tiles the worker's active extent to within
    5%. Each task is a burst: one worker-lane ``sweep`` span covers
    its wait, and the spans' ``requests`` add up to the dispatcher's
    sweep requests."""
    db, p = small_db
    bm, counts = pack_database(db, p.n_dense_items, return_counts=True)
    ms = int(0.3 * len(db))
    ref, _ = mine(bm, ms, policy="clustered", n_workers=4, max_k=4,
                  granularity="bucket", item_counts=counts)
    tr = Tracer()
    res, met = mine(bm, ms, policy="clustered", n_workers=4, max_k=4,
                    granularity="bucket", item_counts=counts, trace=tr)
    assert res == ref                              # tracing is inert
    names = tr.lane_names()
    workers = [n for n in names if n.startswith("worker-")]
    assert len(workers) == 4 and "driver" in names
    assert any(n.startswith("dispatcher-") for n in names)
    spans = [e for e in tr.events() if e.ph == "X"]
    cats = {e.cat for e in spans}
    assert {"task", "level", "flush", "sweep"} <= cats
    assert any(e.cat == "steal" or e.cat == "idle" for e in spans)
    assert check_nesting(tr.events()) == []
    per_worker = {e.lane for e in spans if e.cat == "task"}
    assert per_worker >= set(workers)              # every worker ran tasks
    sweeps = [e for e in spans if e.name == "sweep"]
    assert {e.lane for e in sweeps} <= set(workers)
    assert len(sweeps) == met.bursts == met.scheduler["tasks_run"]
    assert sum(e.args["requests"] for e in sweeps) == \
        sum(row["sweep_requests"] for row in met.per_device)
    assert max(e.args["requests"] for e in sweeps) > 1
    for key, row in time_in_state(tr).items():
        if not row["lane"].startswith("worker-"):
            continue
        # spans tile the worker loop: total within 5% of the lane's
        # extent (+2ms absolute slack for inter-span bookkeeping)
        assert row["total"] >= 0.95 * row["extent"] - 0.002, row
        assert row["total"] <= row["extent"] + 1e-6, row
    doc = chrome_trace(tr)
    lanes_with_tasks = {(e["pid"], e["tid"]) for e in doc["traceEvents"]
                       if e.get("cat") == "task"}
    assert len(lanes_with_tasks) >= 4
    json.dumps(doc)                                # serializable


DRIVER_SPANS = {"mine.arena", "mine.level1", "mine.start", "mine.close",
                "mine.finalize", "level.candidates", "level.plan",
                "level.spawn", "level.barrier", "level.collect"}
DISPATCHER_SPANS = {"dispatch.idle", "flush", "flush.prepare",
                    "flush.launch", "flush.wait"}


@pytest.mark.parametrize("representation,support",
                         [("bitmap", 0.3), ("sparse", 0.2)])
def test_traced_kernel_mine_splits_driver_and_flush(small_db,
                                                    representation,
                                                    support):
    """A kernel-backend mine: the driver lane tiles the call into its
    steps, every flush holds one prepare/launch/wait triple per kernel
    launch, and each kernel's logical work is at most its padded
    work (the sparse case reaches level 3, whose cached prefixes are
    tid-lists swept by gather_intersect). A flush forms either after
    a ``dispatch.form`` straggler wait or at once from a burst's
    backlog, so there is at most one wait per flush, and the flushes'
    ``requests`` add up to the dispatcher's sweep requests."""
    db, p = small_db
    bm, counts = pack_database(db, p.n_dense_items, return_counts=True)
    ms = int(support * len(db))
    tr = Tracer()
    res, met = mine(bm, ms, n_workers=2, max_k=4,
                    backend="pallas-interpret", item_counts=counts,
                    representation=representation, trace=tr)
    ref, _ = mine(bm, ms, n_workers=2, max_k=4, item_counts=counts)
    assert res == ref
    evs = tr.events()
    assert check_nesting(evs) == []
    spans = [e for e in evs if e.ph == "X"]
    by_lane = {}
    for e in spans:
        by_lane.setdefault(e.lane, set()).add(e.name)
    assert DRIVER_SPANS <= by_lane["driver"]
    assert DISPATCHER_SPANS <= by_lane["dispatcher-0"]
    launches = [e for e in spans if e.name == "flush.launch"]
    flushes = [e for e in spans if e.name == "flush"]
    for name in ("flush.prepare", "flush.wait"):
        assert sum(e.name == name for e in spans) == len(launches)
    assert len(launches) >= len(flushes)
    row = met.per_device[0]
    schema.validate("device", row)
    n_launch = row["bitmap_join_launches"] + row["gather_intersect_launches"]
    assert n_launch == len(launches)
    kernels = {e.args["kernel"] for e in launches}
    if representation == "sparse":
        assert "gather_intersect" in kernels
    assert 0 < row["bitmap_join_words"] <= row["bitmap_join_padded_words"]
    assert row["gather_intersect_probes"] <= \
        row["gather_intersect_padded_probes"]
    assert (row["gather_intersect_probes"] > 0) == \
        ("gather_intersect" in kernels)
    assert row["flushes"] == len(flushes)
    assert row["sweep_requests"] >= row["flushes"] > 0
    assert sum(e.args["requests"] for e in flushes) == row["sweep_requests"]
    forms = [e for e in spans if e.name == "dispatch.form"]
    assert {e.lane for e in forms} <= {"dispatcher-0"}
    assert len(forms) <= len(flushes)
    assert row["queue_wait_us"] > 0
    # the driver's top-level spans tile the call; a level-k span only
    # for a level that had candidates
    top = [e for e in spans if e.lane == "driver"
           and (e.name.startswith("mine.") or e.name.startswith("level-")
                or e.name == "level.candidates")]
    assert sum(e.name.startswith("level-") for e in top) == met.levels
    first = min(e.ts for e in top)
    last = max(e.ts + e.dur for e in top)
    assert sum(e.dur for e in top) >= 0.95 * (last - first)


def test_mixed_flush_launches_every_part_before_its_first_wait():
    """A flush over two segments with dense and sparse requests has four
    parts (segment × representation), and enqueues all four launches
    before it reads any counts back; a delta flush of one dense part
    after it has nothing to overlap. ``overlapped_launches`` is the
    launches less the flushes that launched anything, and each launch
    keeps its own prepare/launch/wait triple."""
    import numpy as np

    from repro.core.join_backend import SweepDispatcher, get_backend
    from repro.core.tidlist import BitmapArena
    rng = np.random.default_rng(17)
    arena = BitmapArena.from_bitmaps(
        rng.integers(0, 2 ** 32, size=(12, 40), dtype=np.uint32))
    arena.add_segment(rng.integers(0, 2 ** 32, size=(12, 3),
                                   dtype=np.uint32))
    t = arena.push_tids(np.sort(rng.choice(32 * 43, 200, replace=False)))
    tr = Tracer()
    disp = SweepDispatcher(arena, get_backend("pallas-interpret"),
                           n_clients=3, flush_us=5e6, tracer=tr)
    try:
        for f in disp.submit_many([((0, 1), tuple(range(2, 12))),
                                   (t, (1, 2, 3)), (4, (5, 6))]):
            f.result(timeout=60)
        disp.submit_many([(5, (6, 7))], segments=(1,))[0].result(
            timeout=60)
    finally:
        disp.stop()
    spans = [e for e in tr.events()
             if e.ph == "X" and e.lane == "dispatcher-0"]
    flushes = [e for e in spans if e.name == "flush"]
    assert [e.args["parts"] for e in flushes] == [4, 1]
    for fl in flushes:
        inner = [e for e in spans if e.name.startswith("flush.")
                 and fl.ts <= e.ts <= fl.ts + fl.dur]
        launches = [e.ts for e in inner if e.name == "flush.launch"]
        waits = [e.ts for e in inner if e.name == "flush.wait"]
        assert len(launches) == len(waits) == fl.args["parts"]
        assert max(launches) < min(waits)
    row = disp.stats()
    n_launch = row["bitmap_join_launches"] + row["gather_intersect_launches"]
    assert n_launch == 5 == sum(e.name == "flush.launch" for e in spans)
    for name in ("flush.prepare", "flush.wait"):
        assert sum(e.name == name for e in spans) == n_launch
    assert row["overlapped_launches"] == n_launch - sum(
        e.args["parts"] > 0 for e in flushes) == 3
    assert check_nesting(tr.events()) == []


def _level_args(tr):
    return [e.args for e in tr.events()
            if e.ph == "X" and e.name.startswith("level-")]


def test_level_spans_count_the_pairs_each_level_materialized(small_db):
    """A plain mine thresholds each bucket's counts before it builds an
    (itemset, support) pair, so every level-k span reads materialized
    == frequent, and its buckets are the level's share of
    ``metrics.buckets``; a streaming refresh (the delta path) keeps
    every swept support and reads materialized == candidates."""
    from repro.core.streaming import StreamingMiner
    db, p = small_db
    bm = pack_database(db, p.n_dense_items)
    ms = int(0.25 * len(db))
    tr = Tracer()
    res, met = mine(bm, ms, n_workers=2, max_k=4, trace=tr)
    levels = _level_args(tr)
    assert len(levels) == met.levels >= 2
    for a in levels:
        assert a["materialized"] == a["frequent"]
        assert 0 < a["buckets"] <= a["candidates"]
    assert sum(a["buckets"] for a in levels) == met.buckets
    assert sum(a["candidates"] for a in levels) == met.candidates
    assert sum(a["frequent"] for a in levels) == \
        sum(len(c) > 1 for c in res)
    assert any(a["frequent"] < a["candidates"] for a in levels)

    tr = Tracer()
    sm = StreamingMiner(p.n_dense_items, ms, initial_db=db[:200],
                        n_workers=2, max_k=4, tracer=tr)
    try:
        sm.refresh()
        sm.ingest(db[200:300])
        sm.refresh()
    finally:
        sm.close()
    levels = _level_args(tr)
    assert levels
    for a in levels:
        assert a["materialized"] == a["candidates"] > a["frequent"]


def test_traced_streaming_spans_lag_and_latency(small_db):
    from repro.core.streaming import PatternServer, StreamingMiner
    db, p = small_db
    ms = int(0.25 * len(db))
    tr = Tracer()
    sm = StreamingMiner(p.n_dense_items, ms, initial_db=db[:200],
                        n_workers=2, max_k=3, tracer=tr)
    try:
        sm.refresh()
        assert sm.refresh_lag == 0.0
        sm.ingest(db[200:260])
        assert sm.refresh_lag > 0.0                # pending segment waits
        sm.ingest(db[260:300])
        sm.refresh()
        assert sm.refresh_lag == 0.0               # publish drains the lag
        names = {e.name for e in tr.events()}
        assert {"ingest", "refresh", "publish"} <= names
        assert any(e.ph == "C" and e.name == "refresh_lag"
                   for e in tr.events())
        assert check_nesting(tr.events()) == []
        srv = PatternServer(sm)
        srv.support((0,))
        srv.top_k((), 3)
        kinds = set(srv.latency_percentiles())
        assert "top_k" in kinds and ("hit" in kinds or "sweep" in kinds)
        snap = sm.metrics_registry().snapshot()
        assert snap["stream"]["generation"] == sm.generation
        assert snap["stream"]["refresh_lag_s"] == 0.0
        assert "query_latency" in snap and "scheduler" in snap
        schema.validate("query", srv.merged_stats())
    finally:
        sm.close()
