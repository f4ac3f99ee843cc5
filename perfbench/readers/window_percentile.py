def read(facts, pct, scale):
    """A percentile over ALL calls of the window, each timed on its own
    (nearest rank: the smallest time with pct% of the calls at or
    below it)."""
    times = sorted(facts["call_seconds"])
    if not times:
        return None
    k = max(0, -(-len(times) * pct // 100) - 1)
    return times[int(k)] * scale
