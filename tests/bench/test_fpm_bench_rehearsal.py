"""Each traffic driver end to end at toy size on the CPU (numpy
backend): a sound run is correct, and its per-layer readers find what
they read in the program's spans and counters."""
import pytest

import harness
import run

# the readers of the serve cell, which BENCHMARK.json does not list yet,
# that read the program's spans or the queries' latencies
SERVE_READERS = ("ingest_ms.serve", "hit_p95_ms.serve")


def _rehearse(name, seed, seconds, trace):
    try:
        cell = harness.Cell(name)
    except harness.SpecError:
        cell = harness.Cell.unlisted(name)
    return cell, cell.driver().rehearse(cell.config, cell.params, seed,
                                        seconds, trace)


@pytest.mark.parametrize("name, seed", [("t40i10-mine", 2 ** 35 + 17),
                                        ("t10i4-serve", 3)])
def test_sound_run_is_correct(name, seed):
    cell, out = _rehearse(name, seed, 1.5, trace=True)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] >= 1
    assert all(v["value"] == 0 == v["limit"]
               for v in out["checks"].values())
    assert all(v > 0 for v in out["metrics"].values()), out["metrics"]
    # per-layer metrics read from the program's spans and counters
    # (the device's need a chip trace and are left out here)
    got = run.per_layer(cell, out["record"])
    want = {m["name"] for m in cell.per_layer
            if m["source"] != "device_trace"}
    assert want <= set(got), (want, got)
    assert all(v["value"] >= 0 for v in got.values())
    if "park_share.mine" in got:
        assert got["park_share.mine"]["value"] <= 100.0
    if cell.params["kind"] == "serve":
        for metric in SERVE_READERS:
            assert cell.reader(metric).read(out["record"]) > 0, metric
