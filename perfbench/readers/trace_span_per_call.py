from perfbench import trace


def read(facts, pattern, scale):
    """Seconds of the host spans whose name matches ``pattern`` in the
    traced slice, over the calls in it: the library's ``ompi.*`` spans
    (``obs/spans.py``) from every thread of the traced process. A metric
    over a span the library gains is this reader and a pattern."""
    sl = facts["slice"]
    if not sl or not sl["calls"]:
        return None
    seconds = trace.span_seconds(sl["spans"], pattern)
    return seconds / sl["calls"] * scale if seconds else None
