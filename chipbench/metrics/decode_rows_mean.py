"""Mean live rows per decode step in the slice, from the engine's own
``TierStats`` counters (occupancy summed over decode steps)."""


def read(sl):
    steps = sum(s["n_decode_steps"] for s in sl.stats.values())
    if not steps:
        return None
    return sum(s["occupancy_sum"] for s in sl.stats.values()) / steps
