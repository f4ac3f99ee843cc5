def read(facts, pvars, scale):
    """A pvar's change across the window over the calls in it. A pvar
    the library does not have on this path reads nothing."""
    delta = facts["pvars"].get(pvars[0])
    if delta is None or not facts["calls"]:
        return None
    return delta / facts["calls"] * scale
