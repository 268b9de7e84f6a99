"""kv_roofline.<cell>: the least time of the KV-cache and attention work of
the decode steps in the traced window over the device time of the decode
program's ops under the scopes that do it (``kv_gather``, ``kv_scatter``,
``attention/kv_write``, ``attention/core``).

A step's least time is the larger of its attention FLOPs over each
request's live keys (the token's own included) over peak FLOP/s, and its
minimal KV bytes (every live key and value read once, the new token's
written once) over peak bytes/s.  Device time by scope is
``program_trace.reduce``'s (``observed["program"]``); a trace without
the program's scopes gives no reading.
"""
from harness import costs, program_trace

PROGRAM = "jit__decode"      # the engine's jitted ``_decode``
KV_SCOPES = ("kv_gather", "kv_scatter", "attention/kv_write",
             "attention/core")


def kv_work(m: dict, lengths) -> tuple[float, float]:
    """(attention FLOPs, minimal KV bytes) of a decode step over requests
    whose caches hold ``lengths`` tokens before it."""
    flops = sum(costs.attention_flops(m, 1, n) for n in lengths)
    return flops, costs.kv_bytes_per_token(m) * (sum(lengths) + len(lengths))


def read(obs):
    prog = obs.get("program")
    if (obs.get("kind") != "serve" or not prog or not obs.get("peak")
            or not obs.get("trace_host")):
        return None
    device_s = program_trace.seconds_in(prog["scopes"], PROGRAM, KV_SCOPES)
    t0, t1 = obs["trace_host"]
    # the trace opens and closes between engine steps
    least = sum(costs.least_time(*kv_work(obs["dims"], kv), obs["peak"])
                for s, e, kv in obs["steps"] if kv and e > t0 and s < t1)
    if device_s <= 0 or least <= 0:
        return None
    return 100.0 * least / device_s
