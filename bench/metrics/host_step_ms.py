"""host_step_ms.<cell>: host time of an engine step that is not spent
waiting for the device: the mean, over the window's ``step`` spans, of
a step's length less the ``decode.wait`` and ``prefill.wait`` spans
inside it (admission, page bookkeeping, uploads, dispatch, callbacks)."""


def read(obs):
    spans = obs.get("spans") or {}
    steps = spans.get("step")
    if not steps:
        return None
    waits = sorted((t0, t1) for name in ("decode.wait", "prefill.wait")
                   for t0, t1, _ in spans.get(name) or ())
    host = 0.0
    for s0, s1, _ in steps:
        host += (s1 - s0) - sum(w1 - w0 for w0, w1 in waits
                                if s0 <= w0 and w1 <= s1)
    return 1e3 * host / len(steps)
