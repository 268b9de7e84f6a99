"""prefill_chunk_ms.<cell>: host time of the engine's ``prefill.chunk``
spans (one chunk of a long prompt through every layer; a recorded span
waits for the chunk's device work) per chunk of the window."""


def read(obs):
    spans = (obs.get("spans") or {}).get("prefill.chunk")
    if not spans:
        return None
    return 1e3 * sum(t1 - t0 for t0, t1, _ in spans) / len(spans)
