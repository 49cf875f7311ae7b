"""The trace reduction: busy union, per-program device time and idle gaps,
on a synthetic xspace and on a trace recorded here."""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import pytest  # noqa: E402
from jax.profiler import ProfileData  # noqa: E402

from bench import trace  # noqa: E402

# device ops [1, 3) and [4, 5) us, a second device [2, 6) us; one program
# execution per device; a bench.window of [0.5, 10.5) us with a bench.step
# inside and a bench.wait over the tail
XSPACE = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 1500000 duration_ps: 1000000 }
    events { metadata_id: 2 offset_ps: 3000000 duration_ps: 1000000 } }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 3 offset_ps: 0 duration_ps: 4000000 } }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
  event_metadata { key: 2 value { id: 2 name: "copy.2" } }
  event_metadata { key: 3 value { id: 3 name: "jit__fused_loop(7)" } }
}
planes { id: 2 name: "/device:TPU:1"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 2000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 4000000 } }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 2000
    events { metadata_id: 2 offset_ps: 0 duration_ps: 4000000 } }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
  event_metadata { key: 2 value { id: 2 name: "jit__admit(3)" } }
}
planes { id: 3 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 500000 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 600000 duration_ps: 5000000 }
    events { metadata_id: 3 offset_ps: 6000000 duration_ps: 4000000 }
    events { metadata_id: 4 offset_ps: 700000 duration_ps: 100000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "bench.step" } }
  event_metadata { key: 3 value { id: 3 name: "bench.wait" } }
  event_metadata { key: 4 value { id: 4 name: "PjitFunction(f)" } }
}
"""


@pytest.fixture(scope="module")
def synthetic():
    return trace.from_profile(ProfileData.from_text_proto(XSPACE))


def test_planes_and_host_annotations(synthetic):
    assert sorted(synthetic.devices) == ["/device:TPU:0", "/device:TPU:1"]
    # only the benchmark's own annotations are kept from the host
    assert sorted(n for n, _, _ in synthetic.host) == [
        "bench.step", "bench.wait", "bench.window"]
    assert synthetic.window() == (500.0, 10500.0)


def test_busy_union_and_window(synthetic):
    red = trace.reduce(synthetic)
    # TPU:0: [1000, 3500) and [4000, 5000) -> 3.5 us; TPU:1: [2000, 6000) -> 4 us
    assert red["window_s"] == pytest.approx(10e-6)
    assert red["busy_s"] == pytest.approx((3.5e-6 + 4e-6) / 2)


def test_program_time_by_jitted_name(synthetic):
    red = trace.reduce(synthetic)
    assert red["program_s"] == pytest.approx(
        {"_fused_loop": 4e-6 / 2, "_admit": 4e-6 / 2})


def test_busy_clips_to_the_window():
    dev = trace.DeviceTrace([("a", 0.0, 10.0), ("b", 5.0, 20.0)], [])
    assert trace.busy_ns(dev, 2.0, 12.0) == 10.0
    assert trace.merge([(0, 1), (3, 4), (0.5, 2)], 0, 10) == [(0, 2), (3, 4)]


def test_breakdown(synthetic):
    red = trace.reduce(synthetic)
    ops = dict(red["breakdown"]["device_ops"])
    assert ops["_admit/fusion.1"] == pytest.approx(4e-6 / 2)
    assert ops["_fused_loop/fusion.1"] == pytest.approx(2e-6 / 2)
    assert ops["_fused_loop/copy.2"] == pytest.approx(2e-6 / 2)
    gaps = red["breakdown"]["idle_gaps"]
    # longest: TPU:0 after 5000 ns (5.5 us, mid in bench.wait), then TPU:1
    # after 6000 (4.5 us), then TPU:1's [500, 2000) under bench.step
    assert gaps[0] == ["bench.wait", pytest.approx(5.5e-6)]
    assert gaps[1] == ["bench.wait", pytest.approx(4.5e-6)]
    assert gaps[2] == ["bench.step", pytest.approx(1.5e-6)]
    assert len(gaps) <= 10


def test_breakdown_counts_innermost_operations():
    ops = [("p/while.1", 0.0, 100.0), ("p/fusion.2", 10.0, 40.0),
           ("p/fusion.3", 50.0, 90.0), ("p/copy.4", 95.0, 99.0),
           ("p/copy.5", 120.0, 130.0)]
    assert [n for n, _, _ in trace.leaves(ops)] == [
        "p/fusion.2", "p/fusion.3", "p/copy.4", "p/copy.5"]
    t = trace.Trace({"/device:TPU:0": trace.DeviceTrace(ops, [])}, [])
    got = dict(trace.top_ops(t, 0.0, 200.0))
    assert got == pytest.approx({"p/fusion.2": 30e-9, "p/fusion.3": 40e-9,
                                 "p/copy.4": 4e-9, "p/copy.5": 10e-9})


def test_program_spans_name_the_gaps():
    from bench.serve_loop import add_obs_spans

    t = trace.from_profile(ProfileData.from_text_proto(XSPACE))
    # the window began at 500 ns on the trace and at 2.0 s on the host
    # clock; serve.admit ran 3.1-3.4 us into it, inside bench.step
    add_obs_spans(t, [("X", "serve.admit", 2.0e9 + 3100, 300, 0, {}),
                      ("i", "serve.retire", 2.0e9 + 3200, 0, 0, {})], 2.0)
    assert ("serve.admit", 3600.0, 3900.0) in t.host
    gaps = trace.reduce(t)["breakdown"]["idle_gaps"]
    # TPU:0's gap [3500, 4000) has its middle in serve.admit
    assert ["serve.admit", pytest.approx(0.5e-6)] in gaps
    assert ["bench.step", pytest.approx(1.5e-6)] in gaps


def test_op_name():
    assert trace.op_name("%fusion.12 = f32[8]{0} fusion(f32[8]{0} %x), kind=kLoop") == "fusion.12"
    assert trace.op_name("copy.2") == "copy.2"


def test_program_name():
    assert trace.program_name("jit__fused_loop(12)") == "_fused_loop"
    assert trace.program_name("jit_step") == "step"


def test_recorded_trace_on_this_host():
    """A real capture: the window annotation and the host spans come back;
    the CPU has no device plane, so there is nothing to reduce."""
    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    with trace.Capture() as cap:
        with jax.profiler.TraceAnnotation(trace.WINDOW):
            with jax.profiler.TraceAnnotation("bench.step"):
                f(x).block_until_ready()
    names = {n for n, _, _ in cap.trace.host}
    assert {"bench.window", "bench.step"} <= names
    lo, hi = cap.trace.window()
    assert hi > lo
    if not cap.trace.devices:
        assert trace.reduce(cap.trace) is None
