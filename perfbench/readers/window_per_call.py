def read(facts, scale):
    """The window's seconds over all calls completed in it."""
    if not facts["calls"]:
        return None
    return facts["seconds"] / facts["calls"] * scale
