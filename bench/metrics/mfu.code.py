"""mfu.code: the least time the chip could take for the window's prompt
chunks and decode steps over the time they took (the engine's
``prefill.chunk`` and ``decode`` spans, each of which ends when its
device work is done).

A chunk of ``width`` tokens at position ``pos`` does the GEMMs of every
layer over its rows and the head over its last row, and causal attention
from its queries to the ``pos + width`` keys they see; its least bytes
are the weights once and the cache it reads and writes.  A decode step
is priced as ``mfu.chat`` prices it.  One-shot prefills (prompts within
one chunk) are left out of both sums: their span ends before their
device work does.
"""
import os

from harness import common, costs

_CHAT = common.load_file_module(
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "mfu.chat.py"),
    "bench_metric_mfu_chat")


def chunk_cost(m: dict, pos: int, width: int) -> tuple[float, float]:
    """(FLOPs, least bytes) of one prompt chunk."""
    f, _ = costs.gemms_cost(costs.forward_gemms(m, width, 1))
    f += costs.attention_flops(m, width, pos)
    return f, (costs.weight_bytes(m)
               + costs.kv_bytes_per_token(m) * (pos + width))


def read(obs):
    spans = obs.get("spans") or {}
    chunks = spans.get("prefill.chunk") or []
    decodes = spans.get("decode") or []
    if not obs.get("peak") or not (chunks or decodes):
        return None
    least = sum(costs.least_time(*chunk_cost(obs["dims"], a["pos"],
                                             a["width"]), obs["peak"])
                for _, _, a in chunks)
    least += sum(t for t, _ in _CHAT.decode_work(obs, decodes))
    took = sum(t1 - t0 for t0, t1, _ in chunks + decodes)
    if took <= 0:
        return None
    return 100.0 * least / took
