"""The trace reduction on a small trace with known answers."""
import pytest

from bench import trace

# one host thread: a tick span holding a prefill and then a decode
# dispatch; one chip: the prefill program, then the decode program with
# the paged-attention kernel inside (times in ns, written as ps)
XSPACE = """
planes {
  id: 1 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 5000000 }
    events { metadata_id: 2 offset_ps: 0 duration_ps: 100000 }
    events { metadata_id: 3 offset_ps: 2000000 duration_ps: 100000 }
    events { metadata_id: 4 offset_ps: 0 duration_ps: 1000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.step" } }
  event_metadata { key: 4 value { id: 4 name: "bench.clock" } }
  event_metadata { key: 2 value { id: 2 name: "paged.prefill" } }
  event_metadata { key: 3 value { id: 3 name: "paged.decode" } }
}
planes {
  id: 2 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 200000 duration_ps: 1000000
             stats { metadata_id: 1 int64_value: 11 } }
    events { metadata_id: 2 offset_ps: 2200000 duration_ps: 2000000
             stats { metadata_id: 1 int64_value: 22 } } }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 3 offset_ps: 200000 duration_ps: 1000000
             stats { metadata_id: 1 int64_value: 11 } }
    events { metadata_id: 4 offset_ps: 2200000 duration_ps: 1000000
             stats { metadata_id: 1 int64_value: 22 } }
    events { metadata_id: 3 offset_ps: 3200000 duration_ps: 1000000
             stats { metadata_id: 1 int64_value: 22 } } }
  event_metadata { key: 1 value { id: 1 name: "jit_step(11)" } }
  event_metadata { key: 2 value { id: 2 name: "jit_step(22)" } }
  event_metadata { key: 3 value { id: 3 name: "fusion.1" } }
  event_metadata { key: 4 value { id: 4 name: "paged_attention_kernel" } }
  stat_metadata { key: 1 value { id: 1 name: "program_id" } }
}
"""


@pytest.fixture(scope="module")
def summary():
    from jax.profiler import ProfileData

    return trace.summarize(ProfileData.from_text_proto(XSPACE))


def test_busy_is_the_union_of_device_ops(summary):
    assert summary.busy_s == pytest.approx(3e-6)
    # the recorded interval runs from the first event to the last
    assert summary.window_s == pytest.approx(5e-6)
    assert summary.clock_ns == 0


def test_programs_take_the_span_that_dispatched_them(summary):
    (dec,) = summary.label("paged.decode")
    (pre,) = summary.label("paged.prefill")
    assert dec["count"] == 1 and dec["device_s"] == pytest.approx(2e-6)
    assert pre["count"] == 1 and pre["device_s"] == pytest.approx(1e-6)
    assert dec["kernel_s"] == pytest.approx(1e-6) and dec["kernels"] == 1
    assert pre["kernel_s"] == 0


def test_idle_gaps_by_host_span_and_breakdown(summary):
    assert summary.gaps["bench.step"] == pytest.approx(1e-6)
    b = summary.breakdown()
    assert b["device_ops"][0] == ["fusion.1", pytest.approx(2e-6)]
    assert b["idle_gaps"] == [["bench.step", pytest.approx(1e-6)]]
