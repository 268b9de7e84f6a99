"""attention_roofline.<cell>: causal attention's model FLOPs, forward and
backward, of the training steps in the traced window at peak FLOP/s,
over the device time of the train step's ops under the scope
``attention/core`` (the flash kernels: forward, the forward recomputed
for the backward, and backward).

Model FLOPs count no recomputation: three times the forward's products
over the keys each query may see (``costs.attention_flops``).  Device
time by scope is ``program_trace.reduce``'s (``observed["program"]``); a
trace without the program's scopes gives no reading.
"""
from harness import costs, program_trace

PROGRAM = "jit_train_step"   # the jitted ``make_train_step`` step


def read(obs):
    prog = obs.get("program")
    if (obs.get("kind") != "train" or not prog or not obs.get("peak")
            or not obs.get("trace_host")):
        return None
    device_s = program_trace.seconds_in(prog["scopes"], PROGRAM,
                                        ("attention/core",))
    t0, t1 = obs["trace_host"]
    # the trace opens and closes between steps
    n = sum(1 for s, e in obs["steps"] if e > t0 and s < t1)
    flops = n * 3.0 * obs["batch"] * costs.attention_flops(
        obs["dims"], obs["seq"], 0)
    if device_s <= 0 or flops <= 0:
        return None
    return 100.0 * flops / obs["peak"]["flops"] / device_s
