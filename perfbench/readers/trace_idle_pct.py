def read(facts):
    """1 - busy/window of the busiest device over the traced slice."""
    sl = facts["slice"]
    if not sl:
        return None
    return 100.0 * (1.0 - sl["busy_s"] / sl["window_s"])
