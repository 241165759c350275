"""The readers of the level driver's and the sweep dispatcher's program
spans, on synthetic records: what each reads, and that a record from a
program without those spans gives nothing."""
import os

import pytest

import harness


def _reader(metric):
    return harness.load_module(
        os.path.join(harness.BENCH, "layer_metrics", metric + ".py"),
        "test_reader_" + metric.replace(".", "_"))


def _op(t0, t1, spans, dropped=0):
    return {"t0": t0, "t1": t1, "spans": spans, "dropped": dropped}


# two mines: 10 s and 4 s of wall time, the driver's spawn and barrier
# spans 1 + 6 s and 0.5 + 2.5 s; a worker-lane span of the same name and
# the tail of a span past the mine's end are not counted
MINES = {"ops": [
    _op(0.0, 10.0, [["level-2", "driver", 0.5, 9.0],
                    ["level.candidates", "driver", 0.5, 1.0],
                    ["level.spawn", "driver", 2.0, 3.0],
                    ["level.barrier", "driver", 3.0, 9.0],
                    ["level.barrier", "worker-0", 3.0, 9.0],
                    ["park", "worker-0", 1.0, 2.0]]),
    _op(20.0, 24.0, [["level.spawn", "driver", 20.5, 21.0],
                     ["level.barrier", "driver", 21.5, 25.0]]),
]}

# three flushes; the first carries two launches (dense and sparse)
FLUSHES = {"ops": [_op(0.0, 1.0, [
    ["flush", "dispatcher-0", 0.0, 0.010],
    ["flush.prepare", "dispatcher-0", 0.000, 0.002],
    ["flush.launch", "dispatcher-0", 0.002, 0.003],
    ["flush.wait", "dispatcher-0", 0.003, 0.005],
    ["flush.prepare", "dispatcher-0", 0.005, 0.006],
    ["flush.launch", "dispatcher-0", 0.006, 0.007],
    ["flush.wait", "dispatcher-0", 0.007, 0.010],
    ["flush", "dispatcher-0", 0.1, 0.104],
    ["flush.prepare", "dispatcher-0", 0.100, 0.101],
    ["flush.launch", "dispatcher-0", 0.101, 0.102],
    ["flush.wait", "dispatcher-0", 0.102, 0.104],
    ["flush", "dispatcher-0", 0.2, 0.201],
    ["flush.launch", "dispatcher-0", 0.200, 0.201],
])]}

# what a program with the older instrumentation records: no driver
# steps, no flush anatomy
OLDER = {"ops": [_op(0.0, 2.0, [["level-2", "driver", 0.0, 1.9],
                                ["flush", "dispatcher-0", 0.1, 0.2],
                                ["park", "worker-0", 0.3, 0.4]])]}


def test_driver_serial_is_wall_time_less_spawn_and_barrier():
    # (10 - 1 - 6) and (4 - 0.5 - 2.5, the barrier clipped to the mine)
    assert _reader("driver_serial_ms.mine").read(MINES) == \
        pytest.approx(1000.0 * (3.0 + 1.0) / 2)


def test_driver_serial_needs_every_mine_whole():
    ops = [dict(MINES["ops"][0]), dict(MINES["ops"][1], dropped=3)]
    assert _reader("driver_serial_ms.mine").read({"ops": ops}) is None


def test_flush_host_and_wait_are_per_flush_means():
    # host: prepare + launch = 2+1+1+1 ms, 1+1 ms, 1 ms over 3 flushes;
    # wait: 2+3 ms, 2 ms, none
    assert _reader("flush_host_ms.mine").read(FLUSHES) == \
        pytest.approx(8.0 / 3)
    assert _reader("flush_wait_ms.mine").read(FLUSHES) == \
        pytest.approx(7.0 / 3)


@pytest.mark.parametrize("metric", ["driver_serial_ms.mine",
                                    "flush_host_ms.mine",
                                    "flush_wait_ms.mine"])
def test_older_program_or_no_trace_gives_nothing(metric):
    reader = _reader(metric)
    assert reader.read(OLDER) is None
    assert reader.read({"ops": [{"t0": 0.0, "t1": 1.0}]}) is None
    assert reader.read({}) is None
