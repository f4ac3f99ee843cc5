"""From a profiler trace to device busy time, idle gaps and the
operations that took the time.

The profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``;
``jax.profiler.ProfileData`` reads it. On a TPU every chip is a plane
``/device:TPU:<k>`` whose ``XLA Ops`` line holds one event per
operation that ran there. A CPU rehearsal (``--rehearse-cpu``, and only
that) has no device plane: there the events of ``/host:CPU`` that carry
an ``hlo_op`` stat stand in, by their ``device_ordinal`` (labelled a
rehearsal wherever it is printed). In any other run a trace without a
``/device:TPU:<k>`` plane has no device operations, whatever the host
plane holds, and the run ends with no result.

Busy time is the UNION of one device's operation intervals, clipped to
the traced slice, for the busiest single device: never a sum over
devices or over overlapping operations, so 0 < busy_s <= window_s.
"""

import bisect
import glob
import os
import re

SLICE = "perfbench.slice"       # the benchmark's span around the traced rounds
CALL = "perfbench.call:"        # ... and around each call in it
LIBRARY = "ompi."               # the library's own spans (obs/spans.py)
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"


class NoDevicePlane(Exception):
    """The trace holds no device operations; carries what it did hold."""


def newest_xplane(trace_dir):
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise NoDevicePlane(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path, rehearsal=False):
    """(device intervals, host spans, what the trace holds). ``rehearsal``
    is the run's own ``--rehearse-cpu``, never read off the trace: only
    then do host events stand in for a device.

    device intervals: {device: [(start_ns, end_ns, name), ...]}
    host spans:       [(start_ns, end_ns, name), ...] for the benchmark's
                      own spans and every other host event that lasted
    """
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, spans, seen = {}, [], []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            events = list(line.events)
            seen.append((plane.name, line.name, len(events)))
            if m and line.name == OPS_LINE:
                devices.setdefault(int(m.group(1)), []).extend(
                    (e.start_ns, e.start_ns + e.duration_ns, e.name)
                    for e in events)
            elif plane.name == "/host:CPU":
                for e in events:
                    if e.duration_ns <= 0:
                        continue
                    iv = (e.start_ns, e.start_ns + e.duration_ns, e.name)
                    stats = dict(e.stats) if rehearsal else {}
                    if "hlo_op" in stats:  # what XLA:CPU ran stands in
                        devices.setdefault(
                            stats.get("device_ordinal", 0), []).append(iv)
                    else:
                        spans.append(iv)
    return devices, spans, seen


def union(intervals, lo, hi):
    """Merged [start, end) pieces of ``intervals`` inside [lo, hi]."""
    merged = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e, *_ in intervals):
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _owner(open_spans):
    """The name an idle moment under ``open_spans`` goes by: the
    benchmark's call span, then the library span that opened last (of two
    that opened together, the one that closes first)."""
    call = next((m[2] for m in open_spans if m[2].startswith(CALL)), None)
    inner = [m for m in open_spans if m[2].startswith(LIBRARY)]
    if not inner:
        return call
    last = max(inner, key=lambda m: (m[0], -m[1]))[2]
    return f"{call}:{last}" if call else last


def owners(spans):
    """Who an idle moment is credited to, as sorted pieces that do not
    overlap, (start_ns, end_ns, name): inside the library's ``ompi.*``
    spans the innermost one that covers the moment, named after the
    benchmark's call span around it; elsewhere inside a call, the call's
    own span. Moments outside both belong to no piece."""
    marks = [iv for iv in spans
             if iv[2].startswith((CALL, LIBRARY)) and iv[1] > iv[0]]
    # at one instant ends sort before starts: no span is open at its own end
    edges = sorted([(s, 1, i) for i, (s, _, _) in enumerate(marks)]
                   + [(e, 0, i) for i, (_, e, _) in enumerate(marks)])
    pieces, open_now, since = [], set(), None
    for t, opens, i in edges:
        if open_now and t > since:
            name = _owner([marks[j] for j in open_now])
            if pieces and pieces[-1][1:] == (since, name):
                pieces[-1] = (pieces[-1][0], t, name)
            else:
                pieces.append((since, t, name))
        (open_now.add if opens else open_now.discard)(i)
        since = t
    return pieces


def _share_gap(gaps, pieces, starts, a, b):
    """Credit the idle stretch [a, b) to the pieces of ``owners`` it
    overlaps (sorted, not overlapping), and what lies outside every one
    to "between calls"."""
    left = b - a
    i = max(0, bisect.bisect_right(starts, a) - 1)
    while i < len(pieces) and pieces[i][0] < b:
        part = min(b, pieces[i][1]) - max(a, pieces[i][0])
        if part > 0:
            gaps[pieces[i][2]] = gaps.get(pieces[i][2], 0) + part
            left -= part
        i += 1
    if left > 0:
        gaps["between calls"] = gaps.get("between calls", 0) + left


def short(name):
    """An operation's name as the trace has it, without the operands:
    ``%copy.1 = f32[1,2]{1,0:T(1,128)} copy(...)`` -> ``%copy.1 f32[1,2]``."""
    head, sep, rest = name.partition(" = ")
    return f"{head} {rest.split('{')[0].split(' ')[0]}" if sep else name


def reduce(devices, spans, window_s=None):
    """The traced slice as numbers. ``window_s`` is the slice's seconds
    by the host's clock, used where the trace lacks the slice's span."""
    if not devices or not any(devices.values()):
        raise NoDevicePlane("no device operations in the trace")
    marks = [(s, e) for s, e, name in spans if name == SLICE]
    if marks:
        lo, hi = marks[0]
    else:
        lo = min(s for iv in devices.values() for s, _, _ in iv)
        hi = max(e for iv in devices.values() for _, e, _ in iv)
        if window_s is not None:
            hi = max(hi, lo + int(window_s * 1e9))
    busy = {d: union(iv, lo, hi) for d, iv in devices.items()}
    busy_ns = {d: sum(e - s for s, e in m) for d, m in busy.items()}
    top = max(busy_ns, key=busy_ns.get)
    if not 0 < busy_ns[top] <= hi - lo:
        raise NoDevicePlane(
            f"busy {busy_ns[top]} ns outside (0, {hi - lo}] on device {top}")
    by_op = {}
    for s, e, name in devices[top]:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            by_op[short(name)] = by_op.get(short(name), 0) + (e - s)
    calls = [iv for iv in spans if iv[2].startswith(CALL)]
    pieces = owners(spans)
    starts = [s for s, _, _ in pieces]
    gaps, edge = {}, lo
    for s, e in busy[top] + [[hi, hi]]:
        if s > edge:
            _share_gap(gaps, pieces, starts, edge, s)
        edge = max(edge, e)

    def top10(d):
        return [[k, v / 1e9] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:10]]

    return {"window_s": (hi - lo) / 1e9, "busy_s": busy_ns[top] / 1e9,
            "busiest_device": str(top), "devices": len(devices),
            "calls_in_slice": sum(lo <= s and e <= hi for s, e, _ in calls),
            "breakdown": {"device_ops": top10(by_op),
                          "idle_gaps": top10(gaps)}}


def span_seconds(spans, pattern):
    """Total seconds of the host spans whose name matches ``pattern``."""
    rx = re.compile(pattern)
    return sum(e - s for s, e, name in spans if rx.search(name)) / 1e9


def describe(seen):
    return "; ".join(f"{p} / {ln}: {n} events" for p, ln, n in seen[:40])
