"""slot_occupancy.<cell>: share of the KV pool's slots that held a
running request, over the decode steps of the window (the engine's own
``slot_steps / slot_capacity_steps`` counters)."""


def read(obs):
    c = obs.get("counters") or {}
    if not c.get("slot_capacity_steps"):
        return None
    return 100.0 * c["slot_steps"] / c["slot_capacity_steps"]
