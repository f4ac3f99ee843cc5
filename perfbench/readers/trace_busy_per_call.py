def read(facts, scale):
    """Busy time of the busiest device in the traced slice over the
    calls in it."""
    sl = facts["slice"]
    if not sl or not sl["calls"]:
        return None
    return sl["busy_s"] / sl["calls"] * scale
