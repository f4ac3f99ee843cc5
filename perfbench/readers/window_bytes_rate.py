def read(facts, scale):
    """All payload bytes of the window (OSU's message size: the bytes of
    one rank's send buffer, per call) over the window's seconds."""
    if not facts["seconds"] or not facts["payload_bytes"]:
        return None
    return facts["payload_bytes"] / facts["seconds"] * scale
