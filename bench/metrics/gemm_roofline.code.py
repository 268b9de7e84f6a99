"""gemm_roofline.code: the summed least time of the model GEMMs of the
prompt chunks, one-shot prefills and decode steps whose engine spans
start in the traced window, over the device time of every trace op that
``gemm_roofline.is_gemm`` takes for a model GEMM.

A chunk or prefill of ``n`` tokens does every layer's GEMMs over its
rows and the head over its last row; a decode step every layer's GEMMs
and the head over its active rows.  Each span's device work runs after
the span starts and is done when the window closes (every engine step
ends in a sync), so no counted GEMM lies outside the trace; a span that
started before the trace did is left out, and the part of its work that
the trace holds then only lowers the share.
"""
import os

from harness import common, costs

_GEMM = common.load_file_module(
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "gemm_roofline.py"), "bench_metric_gemm_roofline")


def _gemms(obs):
    t0, _ = obs["trace_host"]
    spans = obs.get("spans") or {}
    m = obs["dims"]
    out = []
    for name, rows in (("prefill.chunk", lambda a: a["width"]),
                       ("prefill", lambda a: a["prompt_len"])):
        for s, _, a in spans.get(name) or []:
            if s >= t0:
                out += costs.forward_gemms(m, rows(a), 1)
    for s, _, a in spans.get("decode") or []:
        if s >= t0:
            out += costs.forward_gemms(m, a["n_active"], a["n_active"])
    return out


def read(obs):
    tr = obs.get("trace")
    if not tr or not obs.get("peak") or not obs.get("trace_host"):
        return None
    device_s = sum(s for n, st, s in tr["ops"] if _GEMM.is_gemm(n, st))
    gemms = _gemms(obs)
    if device_s <= 0 or not gemms:
        return None
    return 100.0 * costs.gemms_least_time(gemms, obs["peak"]) / device_s
