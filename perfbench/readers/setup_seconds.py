def read(facts):
    """Process start (the parent's) to the first timed call."""
    return facts["setup_s"]
