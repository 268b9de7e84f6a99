"""gemm_roofline.<cell>: the summed least time of the model's GEMMs run
in the traced window, from their shapes, over the device time of every
trace op that implements a model GEMM.

The GEMMs of a training step follow from the mix (forward, input
gradient and weight gradient of each); a cell of another kind brings a
reader of its own (``gemm_roofline.<cell>.py``).  An op implements
a model GEMM when it is the Pallas batch-reduce GEMM kernel or an XLA
dot or convolution that is no attention product: so the share reads the
same work whichever backend runs it.
"""
import re

from harness import costs

# the kernels of kernels/brgemm (Pallas, named after their body), and
# XLA's dot and convolution ops and fusions
GEMM = re.compile(r"brgemm|matmul|_mm_body|convolution|\bdot\b|dot_general")
# attention's own products, on whatever path runs them
NOT_GEMM = re.compile(r"flash|attention|mha|bhqk|bhqd|qhd|khd")


def is_gemm(name: str, stats: dict) -> bool:
    text = " ".join([name] + [str(v) for v in stats.values()])
    return bool(GEMM.search(text)) and not NOT_GEMM.search(text)


def _gemms(obs):
    if obs["kind"] != "train":
        return []
    t0, t1 = obs["trace_host"]
    # the trace opens and closes between steps
    n = sum(1 for s, e in obs["steps"] if e > t0 and s < t1)
    return costs.train_gemms(obs["dims"], obs["batch"] * obs["seq"]) * n


def read(obs):
    tr = obs.get("trace")
    if not tr or not obs.get("peak") or not obs.get("trace_host"):
        return None
    device_s = sum(s for n, st, s in tr["ops"] if is_gemm(n, st))
    gemms = _gemms(obs)
    if device_s <= 0 or not gemms:
        return None
    return 100.0 * costs.gemms_least_time(gemms, obs["peak"]) / device_s
