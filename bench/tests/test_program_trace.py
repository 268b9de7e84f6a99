"""Device time by program and named scope, idle time by program span, and
the readers of the scoped metrics and of the engine's host time, on a
hand-built profiler trace."""
import os

import pytest
from jax.profiler import ProfileData

from harness import common, costs, program_trace, trace

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DECODE, TRAIN = "jit__decode", "jit_train_step"
PIDS = {DECODE: 11, TRAIN: 22}
# op: (op_name path as the trace keeps it, program)
OPS = {
    1: ("jit(_decode)/vmap(kv_gather)/jit(_take)/gather:", DECODE),
    2: ("jit(_decode)/vmap()/while/body/closed_call/attention/core/"
        "bhqd,bhkd->bhqk/dot_general:", DECODE),
    3: ("jit(_decode)/vmap()/while/body/closed_call/attention/kv_write/"
        "scatter:", DECODE),
    4: ("jit(_decode)/kv_scatter/scatter:", DECODE),
    5: ("", DECODE),                 # an op XLA made, with no op_name
    6: ("jit(train_step)/transpose(jvp())/while/body/closed_call/"
        "checkpoint/attention/core/jit(flash_attention_bwd_pallas):",
        TRAIN),
    7: ("jit(train_step)/transpose(jvp(attention))/core/pallas_call:",
        TRAIN),
    8: ("jit(train_step)/jvp()/while/body/closed_call/mlp/"
        "jit(matmul_pallas):", TRAIN),
    9: ("jit(train_step)/optimizer/mul:", TRAIN),
}
REF_PATH = 8                         # this op's path is a ref_value stat


def _event(meta, start_ns, dur_ns):
    return (f"events {{ metadata_id: {meta} offset_ps: {start_ns * 1000} "
            f"duration_ps: {dur_ns * 1000} }}")


def _op_meta(key):
    path, prog = OPS[key]
    stats = f"stats {{ metadata_id: 2 uint64_value: {PIDS[prog]} }}"
    if key == REF_PATH:
        stats += " stats { metadata_id: 1 ref_value: 3 }"
    elif path:
        stats += f' stats {{ metadata_id: 1 str_value: "{path}" }}'
    return (f'event_metadata {{ key: {key} value {{ id: {key} '
            f'name: "%op.{key} = f32[8] op()" {stats} }} }}')


def _xspace():
    # device ops (ns); window [50, 1000).  Decode, in its module [0,
    # 360): gather [0,80) (30 ns in the window), core [100,200),
    # kv_write [200,250), kv_scatter [250,300), an op with no op_name
    # [300,350).  Train, in [390, 1100): flash backward [400,500), its
    # transpose [500,550), mlp [600,700), optimizer [950,1100) (50 ns
    # in the window).
    ops = "\n".join([
        _event(1, 0, 80), _event(2, 100, 100), _event(3, 200, 50),
        _event(4, 250, 50), _event(5, 300, 50), _event(6, 400, 100),
        _event(7, 500, 50), _event(8, 600, 100), _event(9, 950, 150),
    ])
    modules = "\n".join([_event(101, 0, 360), _event(102, 390, 710)])
    # host spans: the window; step [60, 520) holding admit [62, 90),
    # decode [90, 360) with decode.upload [92, 100) and decode.wait
    # [110, 360), emit [360, 520); step [560, 1000) holding admit
    # [562, 600) with prefill.wait [570, 600); a harness span
    host = "\n".join([
        _event(1, 50, 950), _event(2, 60, 460), _event(3, 62, 28),
        _event(4, 90, 270), _event(5, 92, 8), _event(6, 110, 250),
        _event(7, 360, 160), _event(2, 560, 440), _event(3, 562, 38),
        _event(8, 570, 30), _event(9, 55, 2),
    ])
    metas = "\n  ".join(_op_meta(k) for k in OPS)
    return f"""
planes {{ id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Modules" timestamp_ns: 0 {modules} }}
  lines {{ id: 2 name: "XLA Ops" timestamp_ns: 0 {ops} }}
  {metas}
  event_metadata {{ key: 101 value {{ id: 101 name: "{DECODE}(11)" }} }}
  event_metadata {{ key: 102 value {{ id: 102 name: "{TRAIN}(22)" }} }}
  stat_metadata {{ key: 1 value {{ id: 1 name: "tf_op" }} }}
  stat_metadata {{ key: 2 value {{ id: 2 name: "program_id" }} }}
  stat_metadata {{ key: 3 value {{ id: 3 name: "{OPS[REF_PATH][0]}" }} }}
}}
planes {{ id: 2 name: "/host:CPU"
  lines {{ id: 1 name: "python" timestamp_ns: 0 {host} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "bench.window" }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "repro.step" }} }}
  event_metadata {{ key: 3 value {{ id: 3 name: "repro.admit" }} }}
  event_metadata {{ key: 4 value {{ id: 4 name: "repro.decode" }} }}
  event_metadata {{ key: 5 value {{ id: 5 name: "repro.decode.upload" }} }}
  event_metadata {{ key: 6 value {{ id: 6 name: "repro.decode.wait" }} }}
  event_metadata {{ key: 7 value {{ id: 7 name: "repro.emit" }} }}
  event_metadata {{ key: 8 value {{ id: 8 name: "repro.prefill.wait" }} }}
  event_metadata {{ key: 9 value {{ id: 9 name: "bench.submit" }} }}
}}
"""


@pytest.fixture(scope="module")
def raw():
    return ProfileData.text_proto_to_serialized_xspace(_xspace())


@pytest.fixture(scope="module")
def reduced(raw):
    profile = ProfileData.from_serialized_xspace(raw)
    return program_trace.reduce(*program_trace.events_of(
        profile, program_trace.op_paths(raw)))


@pytest.mark.parametrize("path,scope", [
    ("jit(f)/transpose(jvp(attention))/core/dot_general", "attention/core"),
    ("jit(f)/vmap(kv_gather)/jit(_take)/gather", "kv_gather"),
    ("jit(f)/attention/kv_write/vmap()/scatter", "attention/kv_write"),
    ("jit(f)/attention/jit(matmul_pallas)", "attention"),
    ("jit(f)/checkpoint/rematted_computation/mlp/mul", "mlp"),
    ("jit(f)/embed/core/add", "embed"),          # core only under attention
    ("jit(f)/vmap()/while/body/dynamic_update_slice", "unscoped"),
    ("jit(f)/jit(head_fn)/mul", "unscoped"),     # a function, not a scope
    ("", "unscoped"),
])
def test_scope_of_unwraps_transforms_and_takes_the_innermost(path, scope):
    assert program_trace.scope_of(path) == scope


def test_op_paths_read_the_event_metadata_of_device_planes(raw):
    paths = program_trace.op_paths(raw)
    assert paths == {(PIDS[prog], f"%op.{k} = f32[8] op()"): path[:-1]
                     for k, (path, prog) in OPS.items() if path}


def test_device_time_splits_by_program_and_scope_in_the_window(reduced):
    assert reduced["scopes"] == pytest.approx({
        (DECODE, "kv_gather"): 30e-9, (DECODE, "attention/core"): 100e-9,
        (DECODE, "attention/kv_write"): 50e-9,
        (DECODE, "kv_scatter"): 50e-9, (DECODE, "unscoped"): 50e-9,
        (TRAIN, "attention/core"): 150e-9, (TRAIN, "mlp"): 100e-9,
        (TRAIN, "optimizer"): 50e-9})
    assert reduced["unscoped_kinds"] == {DECODE: [["op", pytest.approx(
        50e-9)]]}
    assert reduced["window_s"] == pytest.approx(950e-9)
    assert reduced["busy_s"] == pytest.approx(580e-9)


def test_busy_time_agrees_with_the_harness_reduction(raw, reduced):
    red = trace.reduce(*trace.events_of(ProfileData.from_serialized_xspace(
        raw)))
    assert red["busy_s"] == pytest.approx(reduced["busy_s"])
    assert red["window_s"] == pytest.approx(reduced["window_s"])


def test_idle_goes_to_the_innermost_program_span(reduced):
    # idle: [80,100) in admit, decode, decode.upload; [350,400) in
    # decode.wait then emit; [550,600) in no span, the second step, its
    # admit, prefill.wait; [700,950) in the second step
    assert reduced["idle"] == pytest.approx({
        program_trace.NO_SPAN: 10e-9, "repro.admit": 10e-9 + 8e-9,
        "repro.decode": 2e-9, "repro.decode.upload": 8e-9,
        "repro.decode.wait": 10e-9, "repro.emit": 40e-9,
        "repro.step": 2e-9 + 250e-9, "repro.prefill.wait": 30e-9})
    assert sum(reduced["idle"].values()) == pytest.approx(
        reduced["window_s"] - reduced["busy_s"])


def test_log_lines_give_scoped_shares_and_the_idle_table(reduced):
    lines = program_trace.lines(reduced)
    assert next(x for x in lines if DECODE in x).endswith(
        "82.14% scoped: attention/core 0.000000, attention/kv_write "
        "0.000000, kv_scatter 0.000000, unscoped 0.000000, kv_gather "
        "0.000000")                  # 230 of 280 ns
    assert f"  unscoped in {DECODE} by op kind: op 0.000000" in lines
    assert "100.00% scoped" in next(x for x in lines if TRAIN in x)
    assert lines[-1].startswith("idle s by program span: 0.000000 of "
                                "0.000000 idle: repro.step")


def _reader(name):
    return common.load_file_module(os.path.join(BENCH, "metrics", name),
                                   name)


DIMS = dict(d=64, h=4, kv=2, dh=16, ff=128, v=256, layers=2, gated=True,
            tied=False)
PEAK = {"flops": 1e12, "hbm_bytes_per_s": 1e11}


def test_kv_roofline_reads_least_kv_time_over_the_decode_kv_ops(reduced):
    steps = [(0.0, 1.0, [10, 20]), (1.0, 2.0, [11, 21]),
             (2.0, 3.0, [12]), (3.0, 4.0, [])]
    obs = {"kind": "serve", "dims": DIMS, "peak": PEAK, "steps": steps,
           "trace_host": (1.0, 4.0), "program": reduced}
    kvb = costs.kv_bytes_per_token(DIMS)
    want = 0.0
    for lengths in ([11, 21], [12]):    # the steps in the trace
        f = sum(4.0 * 4 * 16 * (n + 1) * 2 for n in lengths)
        b = kvb * (sum(lengths) + len(lengths))
        want += max(f / PEAK["flops"], b / PEAK["hbm_bytes_per_s"])
    kvr = _reader("kv_roofline.py")
    # kv_gather 30 + core 100 + kv_write 50 + kv_scatter 50 ns
    assert kvr.read(obs) == pytest.approx(100 * want / 230e-9)
    assert kvr.read({**obs, "kind": "train"}) is None


def test_attention_roofline_reads_model_flops_over_the_core_ops(reduced):
    obs = {"kind": "train", "dims": DIMS, "peak": PEAK, "batch": 2,
           "seq": 8, "steps": [(0.0, 1.0), (1.0, 2.0), (2.0, 3.5)],
           "trace_host": (1.0, 3.5), "program": reduced}
    want = 2 * 3 * 2 * costs.attention_flops(DIMS, 8, 0) / PEAK["flops"]
    ar = _reader("attention_roofline.py")
    assert ar.read(obs) == pytest.approx(100 * want / 150e-9)
    assert ar.read({**obs, "kind": "serve"}) is None


def test_host_step_ms_takes_the_waits_out_of_each_step():
    spans = {"step": [(0.0, 0.010, {}), (0.020, 0.024, {})],
             "decode.wait": [(0.002, 0.008, {}), (0.021, 0.022, {})],
             "prefill.wait": [(0.001, 0.002, {})],
             "decode": [(0.0015, 0.0095, {})]}
    hs = _reader("host_step_ms.py")
    # (10 - 6 - 1) and (4 - 1) ms
    assert hs.read({"spans": spans}) == pytest.approx(3.0)
    assert hs.read({"spans": {"decode": spans["decode"]}}) is None


def test_readers_read_nothing_from_a_run_without_program_data(raw):
    """The harness hands no program reduction or engine step spans (or
    the program names no scopes): no reading, and nothing raises."""
    profile = ProfileData.from_serialized_xspace(raw)
    unscoped = program_trace.reduce(*program_trace.events_of(profile, {}))
    for program in (None, unscoped):
        obs = {"dims": DIMS, "peak": PEAK, "trace_host": (1.0, 3.5),
               "program": program, "batch": 2, "seq": 8, "spans": None}
        assert _reader("kv_roofline.py").read(
            {**obs, "kind": "serve", "steps": [(1.0, 2.0, [10])]}) is None
        assert _reader("attention_roofline.py").read(
            {**obs, "kind": "train", "steps": [(1.0, 2.0)]}) is None
        assert _reader("host_step_ms.py").read(obs) is None


def test_load_reads_the_newest_trace_of_a_profile_directory(raw, reduced,
                                                          tmp_path):
    run = tmp_path / "plugins" / "profile" / "run1"
    run.mkdir(parents=True)
    (run / "host.xplane.pb").write_bytes(raw)
    # the same computation on the same events: equal to the last bit
    assert program_trace.reduce(*program_trace.load(str(tmp_path))) == (
        reduced)
    with pytest.raises(FileNotFoundError):
        program_trace.load(str(tmp_path / "plugins" / "none"))
