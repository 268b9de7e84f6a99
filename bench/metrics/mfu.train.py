"""mfu.train: model FLOPs of the training steps completed in the window
(forward and backward of every GEMM and of causal attention; no
recomputation) over the window's length times peak FLOP/s."""
from harness import costs


def read(obs):
    if not obs.get("peak") or not obs.get("steps"):
        return None
    w0, w1 = obs["window"]
    flops = len(obs["steps"]) * costs.train_flops(obs["dims"], obs["batch"],
                                                  obs["seq"])
    return 100.0 * flops / ((w1 - w0) * obs["peak"]["flops"])
