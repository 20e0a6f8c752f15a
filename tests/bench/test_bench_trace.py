"""The trace reduction on a small recorded trace."""
import pytest
from jax.profiler import ProfileData

from bench import trace_reduce

# one device and one host thread, times in ns from a common origin:
# window 1000..11000; ops 1000-3000, 2000-4000 (overlap), 6000-7000;
# host spans: a count over 1000..5000, a pump over 4500..11000
TRACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 1000000 duration_ps: 2000000 }
    events { metadata_id: 1 offset_ps: 5000000 duration_ps: 1000000 }
    events { metadata_id: 4 offset_ps: 1500000 duration_ps: 100000 } }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 3 offset_ps: 0 duration_ps: 9000000 } }
  event_metadata { key: 1 value { id: 1 name: "fusion.7" } }
  event_metadata { key: 2 value { id: 2 name: "%fused_expand_intersect_pallas.1 = (u32[256,768]{1,0:T(8,128)S(1)}, s32[256,1]{1,0:T(8,128)S(1)}) custom-call(s32[256]{0:T(256)S(1)} %a, s32[256] %b, s32[512] %c, u32[1000,1,768]{2,1,0} %d, u32[900,1,768]{2,1,0} %e), custom_call_target=\\\"tpu_custom_call\\\", operand_layout_constraints={s32[256]{0}, u32[1000,1,768]{2,1,0}, u32[900,1,768]{2,1,0}}" } }
  event_metadata { key: 3 value { id: 3 name: "jit_step" } }
  event_metadata { key: 4 value { id: 4 name: "%get-tuple-element.5 = u32[256,768]{1,0} get-tuple-element((u32[256,768], s32[256,1]) %fused_expand_intersect_pallas.1), index=0" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 1000000 duration_ps: 4000000 }
    events { metadata_id: 3 offset_ps: 4500000 duration_ps: 6500000 }
    events { metadata_id: 4 offset_ps: 0 duration_ps: 500000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "bench.count" } }
  event_metadata { key: 3 value { id: 3 name: "bench.pump" } }
  event_metadata { key: 4 value { id: 4 name: "other" } }
}
"""


@pytest.fixture(scope="module")
def red():
    return trace_reduce.reduce(ProfileData.from_text_proto(TRACE),
                               n_devices=1)


def test_busy_is_the_union_of_op_intervals(red):
    assert red.window_s == pytest.approx(10e-6)
    assert red.busy_s == pytest.approx(4e-6)       # 1000-4000, 6000-7000
    assert red.idle_share == pytest.approx(0.6)
    assert red.n_devices == 1


def test_idle_gaps_labelled_by_host_spans(red):
    # gaps 4000-6000 (three quarters under the pump) and 7000-11000
    assert red.idle_gaps == [("bench.pump", pytest.approx(4e-6)),
                             ("bench.pump", pytest.approx(2e-6))]


def test_kernel_events_found_by_name(red):
    # the kernel's own op, not the op that reads its output
    calls = red.events_named(r"^%?fused_expand_intersect_pallas[.\d]* = ")
    assert len(calls) == 1
    assert calls[0].name.startswith("%fused_expand_intersect_pallas.1 = ")
    assert calls[0].dur_ns == pytest.approx(2000)


def test_breakdown_top_ops_and_gaps(red):
    b = red.breakdown()
    assert b["device_ops"][0] == ["fusion.7", pytest.approx(3e-6)]
    assert b["device_ops"][1][0].startswith("%fused_expand_intersect_pallas")
    assert b["device_ops"][1][1] == pytest.approx(2e-6)
    assert len(b["idle_gaps"]) == 2


def test_union_and_gaps_helpers():
    assert trace_reduce.union_ns([(5, 6), (1, 3), (2, 4)]) == [[1, 4], [5, 6]]
    assert trace_reduce.gaps_ns([[1, 4], [5, 6]], 0, 8) == \
        [[0, 1], [4, 5], [6, 8]]


def test_a_trace_without_a_window_span_is_refused():
    bare = TRACE.replace('"bench.window"', '"elsewhere"')
    with pytest.raises(ValueError, match="bench.window"):
        trace_reduce.reduce(ProfileData.from_text_proto(bare), n_devices=1)
