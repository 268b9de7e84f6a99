"""decode_step_ms.<cell>: host time of the engine's decode section (its
``decode`` span, which ends in a sync) per decode step of the window."""


def read(obs):
    spans = (obs.get("spans") or {}).get("decode")
    if not spans:
        return None
    return 1e3 * sum(t1 - t0 for t0, t1, _ in spans) / len(spans)
