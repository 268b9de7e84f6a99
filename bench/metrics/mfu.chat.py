"""mfu.chat: the least time the chip could take for the window's decode
steps over the time they took (the engine's ``decode`` spans).  A step's
least time is the larger of its model FLOPs (the GEMMs of its active
rows and attention over each request's live keys) over peak FLOP/s and
its minimal bytes (the weights once, the live cache once) over peak
bytes/s."""
import bisect

from harness import common, costs


def decode_work(obs, spans):
    """[(least time, compute-bound?)] of each decode span, its active
    requests' cache lengths taken from the harness's step record."""
    steps = obs["steps"]
    starts = [s[0] for s in steps]
    out = []
    for t0, _, _ in spans:
        i = bisect.bisect_right(starts, t0) - 1
        lengths = steps[i][2] if i >= 0 else []
        if not lengths:
            continue
        f, b = costs.decode_cost(obs["dims"], lengths)
        out.append((costs.least_time(f, b, obs["peak"]),
                    f / obs["peak"]["flops"]
                    >= b / obs["peak"]["hbm_bytes_per_s"]))
    return out


def read(obs):
    spans = (obs.get("spans") or {}).get("decode")
    if not spans or not obs.get("peak"):
        return None
    work = decode_work(obs, spans)
    took = sum(t1 - t0 for t0, t1, _ in spans)
    if not work or took <= 0:
        return None
    common.log(f"mfu.chat: {sum(c for _, c in work)}/{len(work)} decode "
               f"steps compute-bound, the rest bound by bytes")
    return 100.0 * sum(t for t, _ in work) / took
