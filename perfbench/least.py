"""The least time one chip could take for a call, from shapes and the
published peaks alone: independent of component and algorithm."""

from perfbench import manifest


def seconds(op, n, nbytes, peaks, interconnect):
    """The larger of (bytes the busiest chip must send over its links /
    link peak) and (bytes it must read and write in its memory / memory
    peak). A spanning deployment has no link term: what leaves and
    enters the chip goes through its memory."""
    link, mem = manifest.operation(op).least_bytes(n, nbytes)
    t = mem / peaks["hbm_bytes_per_s"]
    if interconnect == "ici":
        t = max(t, link / peaks["ici_bytes_per_s"])
    return t
