"""The program's spans (``Tracer.span`` in ``obs/tracer.py``) as the
metric readers take them.

A span is recorded twice: in the tracer's ring (``cell.events``: a
``TraceEvent`` with its ``kind``, ``(track, id)`` and duration on the
host clock), and, while a profiler session runs, as a host event named
``<track><id>.<kind>`` (``region1.wait``, ``sched0.handle``) on the
device trace's timeline (``cell.trace.host``).  A program that records
no such span gives every reader here nothing to read.
"""
from __future__ import annotations

import re
from typing import List, Optional

from bench.trace import Interval, merge

REGION_SPAN = re.compile(r"^region\d+\.")


def mean_ms(events, kind: str, track: str = "region") -> Optional[float]:
    """Mean duration of the ring spans ``kind`` on ``track`` timelines."""
    d = [e.dur for e in events
         if e.kind == kind and e.track and e.track[0] == track]
    return sum(d) / len(d) * 1e3 if d else None


def attr_values(events, kind: str, track: str, attr: str) -> List[float]:
    """The ``attr`` of each ring span ``kind`` on a ``track`` timeline."""
    return [e.attrs[attr] for e in events
            if e.kind == kind and e.track and e.track[0] == track
            and e.attrs and attr in e.attrs]


def region_spans(trace) -> List[Interval]:
    """Union of the region spans on the host plane, clipped to the
    profiled window (nanoseconds)."""
    w0, w1 = trace.window
    return merge([(max(s, w0), min(e, w1)) for s, e, name, _ in trace.host
                  if REGION_SPAN.match(name) and min(e, w1) > max(s, w0)])


def overlap_ns(a: List[Interval], b: List[Interval]) -> int:
    """Length of the intersection of two merged interval lists."""
    i = j = n = 0
    while i < len(a) and j < len(b):
        n += max(0, min(a[i][1], b[j][1]) - max(a[i][0], b[j][0]))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return n


def idle_with_work_share(trace) -> Optional[float]:
    """Percent of the profiled window in which no program ran on a chip
    while some region span was open, averaged over the chips used."""
    if trace is None or not trace.devices_used():
        return None
    work = region_spans(trace)
    if not work:
        return None
    held = sum(e - s for s, e in work)
    used = trace.devices_used()
    idle = sum(held - overlap_ns(work, trace.busy_intervals(d))
               for d in used) / len(used)
    w0, w1 = trace.window
    return idle / (w1 - w0) * 100.0
