"""The trace reduction on a hand-built profiler trace (XSpace text)."""
import os

import pytest
from jax.profiler import ProfileData

from harness import common, costs, trace

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CATEGORY = 'stats { metadata_id: 1 str_value: "%s" }'


def _event(meta, start_ns, dur_ns, stats=""):
    return (f"events {{ metadata_id: {meta} offset_ps: {start_ns * 1000} "
            f"duration_ps: {dur_ns * 1000} {stats} }}")


def _xspace():
    # device ops (ns): [0,30) before the window, [100,300) GEMM,
    # [250,400) overlapping it, [600,700) attention, [950,1100) past the
    # window's end; window [50, 1000); a loop op over [90, 720) that
    # holds some of them
    dev = "\n".join([
        _event(6, 90, 630),
        _event(1, 0, 30),
        _event(2, 100, 200, CATEGORY % "convolution fusion"),
        _event(3, 250, 150),
        _event(4, 600, 100, CATEGORY % "flash attention kernel"),
        _event(5, 950, 150),
    ])
    host = "\n".join([
        _event(1, 50, 950),          # bench.window
        _event(2, 60, 440),          # bench.step [60, 500)
        _event(3, 500, 90),          # bench.wait [500, 590)
        _event(2, 590, 410),         # bench.step [590, 1000)
        _event(4, 700, 200),         # bench.batch inside the 2nd step
    ])
    return f"""
planes {{ id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0 {dev} }}
  lines {{ id: 2 name: "XLA Modules" timestamp_ns: 0 {_event(1, 0, 2000)} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "copy.1" }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "fusion.2" }} }}
  event_metadata {{ key: 3 value {{ id: 3 name: "custom-call.3" }} }}
  event_metadata {{ key: 4 value {{ id: 4 name: "custom-call.4" }} }}
  event_metadata {{ key: 5 value {{ id: 5 name: "fusion.5" }} }}
  event_metadata {{ key: 6 value {{ id: 6 name: "%while.7 = (s32[]) while(s32[] %t), body=%b" }} }}
  stat_metadata {{ key: 1 value {{ id: 1 name: "hlo_category" }} }}
}}
planes {{ id: 2 name: "/host:CPU"
  lines {{ id: 1 name: "python" timestamp_ns: 0 {host} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "bench.window" }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "bench.step" }} }}
  event_metadata {{ key: 3 value {{ id: 3 name: "bench.wait" }} }}
  event_metadata {{ key: 4 value {{ id: 4 name: "bench.batch" }} }}
}}
"""


@pytest.fixture(scope="module")
def reduced():
    dev, host = trace.events_of(ProfileData.from_text_proto(_xspace()))
    return trace.reduce(dev, host)


def test_busy_is_the_union_of_device_ops_inside_the_window(reduced):
    assert reduced["window_s"] == pytest.approx(950e-9)
    # [100,400) + [600,700) + [950,1000): 300 + 100 + 50 ns
    assert reduced["busy_s"] == pytest.approx(450e-9)


def test_op_times_are_clipped_to_the_window(reduced):
    ops = {n: s for n, _, s in reduced["ops"]}
    assert "copy.1" not in ops
    assert ops == pytest.approx({"fusion.2": 200e-9, "custom-call.3": 150e-9,
                                 "custom-call.4": 100e-9, "fusion.5": 50e-9})
    assert dict(reduced["device_ops"]) == pytest.approx(
        {"fusion": 250e-9, "custom-call": 250e-9})


def test_idle_gaps_go_to_the_innermost_host_span(reduced):
    gaps = dict(reduced["idle_gaps"])
    # idle: [50,100) of which [50,60) before the first step; [400,600)
    # across a step, a wait and the next step; [700,950) in a batch span
    # until 900, then in its step
    assert gaps == pytest.approx({"no bench span": 10e-9,
                                  "bench.step": 200e-9, "bench.wait": 90e-9,
                                  "bench.batch": 200e-9})
    assert sum(gaps.values()) == pytest.approx(
        reduced["window_s"] - reduced["busy_s"])


def test_a_trace_without_the_window_span_is_refused():
    dev, host = trace.events_of(ProfileData.from_text_proto(_xspace()))
    with pytest.raises(ValueError, match="bench.window"):
        trace.reduce(dev, [h for h in host if h[0] != trace.WINDOW])


def _reader(name):
    return common.load_file_module(os.path.join(BENCH, "metrics", name),
                                   name)


def test_gemm_roofline_attributes_gemm_ops_and_divides_least_time(reduced):
    gr = _reader("gemm_roofline.py")
    names = [n for n, st, _ in reduced["ops"] if gr.is_gemm(n, st)]
    assert names == ["fusion.2"]
    dims = dict(d=64, h=4, kv=2, dh=16, ff=128, v=256, layers=2,
                gated=True, tied=False)
    peak = {"flops": 1e12, "hbm_bytes_per_s": 1e11}
    obs = {"kind": "train", "dims": dims, "peak": peak, "batch": 2,
           "seq": 8, "steps": [(0.0, 1.0), (1.0, 2.0), (2.0, 3.5)],
           "trace_host": (1.0, 3.5), "trace": reduced}
    want = 2 * costs.gemms_least_time(costs.train_gemms(dims, 16), peak)
    assert gr.read(obs) == pytest.approx(100 * want / 200e-9)


def test_device_idle_reads_one_minus_busy_over_window(reduced):
    di = _reader("device_idle.py")
    assert di.read({"trace": reduced}) == pytest.approx(
        100 * (1 - 450 / 950))
    assert di.read({"trace": None}) is None
