"""Every fault a cell can have, planted under the timed path, and the
control (the reference in the program's place, counting in bfloat16),
make the run come out not correct."""
import pytest

from faults import run_with

CASES = [("t40i10-mine", f) for f in ("answer_altered", "half_batch",
                                      "control")] + \
        [("t10i4-serve", f) for f in ("answer_altered", "half_batch",
                                      "refresh_unchanged", "ingest_half",
                                      "control")]


@pytest.mark.parametrize("workload, fault", CASES)
def test_fault_is_caught(workload, fault):
    out = run_with(fault, workload, seed=2 ** 34 + 3, seconds=1.0,
                   on_chip=False)
    assert not out["correct"]
    assert any(v["value"] > v["limit"] for v in out["checks"].values())
