"""The reduction from a profiler trace to device busy/idle time, kernel
time by name, top operations and named idle gaps."""
import pytest

import trace_reduce

# one TPU plane and one host plane, times in ps from each line's start:
# device ops [1000, 3000] and [2000, 4000] overlap, then [11000, 12000]
# (ns); the window annotation spans [0, 20000], a mine [500, 8500]
XSPACE = '''
planes {
  id: 1
  name: "/device:TPU:0"
  lines {
    id: 1
    name: "XLA Ops"
    timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 1000000 duration_ps: 2000000 }
    events { metadata_id: 3 offset_ps: 10000000 duration_ps: 1000000
             stats { metadata_id: 1 str_value: "gather_intersect_many" } }
    events { metadata_id: 2 offset_ps: 30000000 duration_ps: 1000000 }
  }
  lines {
    id: 2
    name: "XLA Modules"
    timestamp_ns: 0
    events { metadata_id: 2 offset_ps: 0 duration_ps: 19000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bitmap_join_many" } }
  event_metadata { key: 2 value { id: 2 name: "fusion.1" } }
  event_metadata { key: 3 value { id: 3 name: "custom-call.7" } }
  stat_metadata { key: 1 value { id: 1 name: "long_name" } }
}
planes {
  id: 2
  name: "/host:CPU"
  lines {
    id: 5
    name: "main"
    timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 20000000 }
    events { metadata_id: 2 offset_ps: 500000 duration_ps: 8000000 }
    events { metadata_id: 3 offset_ps: 600000 duration_ps: 100000 }
  }
  event_metadata { key: 1 value { id: 1 name: "fpm_bench:window" } }
  event_metadata { key: 2 value { id: 2 name: "fpm_bench:mine" } }
  event_metadata { key: 3 value { id: 3 name: "PjitFunction(dense)" } }
}
'''


@pytest.fixture(scope="module")
def reduced():
    from jax.profiler import ProfileData
    return trace_reduce.reduce_profile(ProfileData.from_text_proto(XSPACE))


def test_busy_is_the_union_of_op_intervals_in_the_window(reduced):
    # [1000, 4000] merged + [11000, 12000]; the op at 31000 is outside
    assert reduced["busy_s"] == pytest.approx(4000e-9)
    assert reduced["window_s"] == pytest.approx(20000e-9)


def test_kernel_time_by_name_or_hlo_name(reduced):
    assert reduced["kernel_s"]["bitmap_join_many"] == pytest.approx(2e-6)
    assert reduced["kernel_s"]["gather_intersect_many"] == \
        pytest.approx(1e-6)
    assert reduced["top_ops"][0][0] in ("bitmap_join_many", "fusion.1")


def test_idle_gaps_named_by_the_enclosing_annotation(reduced):
    assert reduced["idle_gaps"] == [
        ["outside any annotation", pytest.approx(8e-6)],
        ["mine", pytest.approx(7e-6)],
        ["mine", pytest.approx(1e-6)]]


def test_union_and_gaps():
    busy = trace_reduce.union([(5, 9), (0, 2), (1, 3), (9, 10)])
    assert busy == [(0, 3), (5, 10)]
    assert trace_reduce.gaps(busy, 0, 12) == [(3, 5), (10, 12)]


def test_a_trace_without_the_window_is_refused():
    from jax.profiler import ProfileData
    text = XSPACE.replace("fpm_bench:window", "other")
    with pytest.raises(ValueError):
        trace_reduce.reduce_profile(ProfileData.from_text_proto(text))
