def read(facts):
    """The least time the chip could take for the slice's calls (from
    their shapes and the published peaks) over the slice's WALL seconds:
    the same work whatever implements it."""
    sl = facts["slice"]
    if not sl or not sl["least_s"]:
        return None
    return 100.0 * sl["least_s"] / sl["window_s"]
