def read(facts):
    """The same least time over the busiest device's BUSY seconds in the
    slice."""
    sl = facts["slice"]
    if not sl or not sl["least_s"]:
        return None
    return 100.0 * sl["least_s"] / sl["busy_s"]
