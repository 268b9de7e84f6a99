"""The code cell's readers (``prefill_chunk_ms``, ``mfu.code``,
``gemm_roofline.code``) on hand-made observations.

One layer of d 4, 2 query heads on 1 KV head of 2, d_ff 8, gated,
vocabulary 16, untied.  Per row a layer's GEMMs do 2 * (16 + 8 + 8 +
16 + 32 + 32 + 32) = 288 FLOPs, the head 2 * 16 * 4 = 128 per row it
runs on.  The weights are 2 * (144 + 64) = 416 bytes and a token's KV 8.
At 1,000 FLOP/s and a bandwidth too wide to bind, a least time is the
FLOPs over 1,000.
"""
import os

import pytest

from harness import common

METRICS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "metrics")
DIMS = dict(d=4, h=2, kv=1, dh=2, ff=8, v=16, layers=1, gated=True,
            tied=False)
PEAK = {"flops": 1000.0, "hbm_bytes_per_s": 1e15}


def _reader(name):
    return common.load_file_module(os.path.join(METRICS, f"{name}.py"),
                                   f"test_metric_{name}")


def _obs(**kw):
    obs = {"kind": "serve", "dims": DIMS, "peak": PEAK,
           # the harness's step record: (start, end, cache lengths of the
           # step's decode tokens)
           "steps": [(10.0, 16.0, [3, 5])],
           "spans": {
               "prefill": [(10.5, 10.6, {"prompt_len": 3})],
               # a chunk of 4 tokens at position 8: the second of its prompt
               "prefill.chunk": [(11.0, 15.0, {"pos": 8, "width": 4})],
               "decode": [(15.0, 16.0, {"n_active": 2})]},
           "trace": None, "trace_host": None}
    obs.update(kw)
    return obs


def test_prefill_chunk_ms_is_the_mean_chunk_span():
    spans = dict(_obs()["spans"])
    spans["prefill.chunk"] = spans["prefill.chunk"] + [
        (20.0, 22.0, {"pos": 0, "width": 4})]
    assert _reader("prefill_chunk_ms").read(_obs(spans=spans)) == \
        pytest.approx(1e3 * (4.0 + 2.0) / 2)


def test_prefill_chunk_ms_without_chunks_reads_nothing():
    spans = dict(_obs()["spans"], **{"prefill.chunk": []})
    assert _reader("prefill_chunk_ms").read(_obs(spans=spans)) is None
    assert _reader("prefill_chunk_ms").read(_obs(spans=None)) is None


def test_mfu_code_prices_chunks_and_decode_steps():
    # chunk: GEMMs 288 * 4 + 128 = 1280, attention 4 * 2 * 2 * (4 * 8 +
    # 4 * 5 / 2) = 672; decode over caches of 3 and 5: GEMMs (288 + 128)
    # * 2 = 832, attention 4 * 2 * 2 * (4 + 6) = 160; spans 4 s and 1 s;
    # the one-shot prefill is in neither sum
    want = 100.0 * ((1280 + 672) + (832 + 160)) / 1000.0 / (4.0 + 1.0)
    assert _reader("mfu.code").read(_obs()) == pytest.approx(want)


def test_mfu_code_bound_by_bytes_and_without_peak():
    # at 250 B/s the chunk's bytes bind: 416 + 8 * (8 + 4) = 512 B, and
    # the decode's: 416 + 8 * (3 + 5 + 2) = 496 B
    slow = {"flops": 1000.0, "hbm_bytes_per_s": 250.0}
    assert _reader("mfu.code").read(_obs(peak=slow)) == pytest.approx(
        100.0 * (512 + 496) / 250.0 / 5.0)
    assert _reader("mfu.code").read(_obs(peak=None)) is None
    assert _reader("mfu.code").read(_obs(spans=None)) is None


def _trace(*ops):
    return {"window_s": 10.0, "busy_s": 9.0,
            "ops": [(name, {}, s) for name, s in ops]}


GEMM_OPS = [("%matmul_pallas.1 = bf16[4,8] custom-call(...)", 2.0),
            ("%convolution.2 = bf16[4,4] convolution(...)", 1.88),
            ("%fusion.3 = f32[4] fusion(...)", 5.0)]


def test_gemm_roofline_code_over_a_window_cut_mid_chunk():
    """The trace opens at 12: the chunk that started at 11 is left out;
    the chunk, the prefill and the decode step that start in it count."""
    spans = {
        "prefill.chunk": [(11.0, 13.0, {"pos": 0, "width": 4}),
                          (13.0, 14.0, {"pos": 4, "width": 4})],
        "prefill": [(14.0, 14.5, {"prompt_len": 3})],
        "decode": [(14.5, 15.0, {"n_active": 2})]}
    obs = _obs(spans=spans, trace=_trace(*GEMM_OPS), trace_host=(12.0, 20.0))
    # chunk 288 * 4 + 128 = 1280, prefill 288 * 3 + 128 = 992, decode
    # (288 + 128) * 2 = 832 FLOPs; GEMM ops 2 + 1.88 s of device time
    assert _reader("gemm_roofline.code").read(obs) == pytest.approx(
        100.0 * (1280 + 992 + 832) / 1000.0 / 3.88)


def test_gemm_roofline_code_without_gemm_ops_reads_nothing():
    obs = _obs(trace=_trace(("%fusion.3 = f32[4] fusion(...)", 5.0),
                            ("%flash_attention_pallas.4 = custom-call", 1.)),
               trace_host=(10.0, 20.0))
    assert _reader("gemm_roofline.code").read(obs) is None
    assert _reader("gemm_roofline.code").read(_obs()) is None   # no trace
    late = _obs(trace=_trace(*GEMM_OPS), trace_host=(30.0, 40.0))
    assert _reader("gemm_roofline.code").read(late) is None     # no spans
