"""device_idle.<cell>: share of the traced window in which no operation
ran on the device (1 - busy / window, busy the union of the device-op
intervals of the profiler trace)."""


def read(obs):
    tr = obs.get("trace")
    if not tr or tr["window_s"] <= 0 or tr["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
