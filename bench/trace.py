"""Reduction of a ``jax.profiler`` trace to device numbers.

The harness wraps the traced part of a window in one
``TraceAnnotation(WINDOW)``; everything here is clipped to that span.
On a TPU the trace has one ``/device:TPU:<n>`` plane per chip, with an
``XLA Modules`` line (one event per program execution) and an ``XLA Ops``
line (one event per HLO instruction, nested: a ``while`` contains its
body's ops).  Host threads are lines of the ``/host:CPU`` plane.  Device
and host events share one timeline (nanoseconds from the session start).

- busy: the union of the module intervals of a chip; idle share is one
  minus busy over the window, averaged over the chips that ran anything;
- kernel calls: the op events whose HLO text matches a pattern (a
  Pallas kernel's custom call, by its jit name);
- breakdown: the leaf ops that took most time, and the longest idle gaps
  named by the host event that overlapped each gap most.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional, Tuple

WINDOW = "bench.window"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_OP_NAME = re.compile(r"^%([A-Za-z_][\w\-]*?)(?:\.\d+)?\s*=\s*(\S+)")

Interval = Tuple[int, int]


def merge(intervals: List[Interval]) -> List[Interval]:
    """Union of half-open intervals, sorted and non-overlapping."""
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(s: int, e: int, window: Interval) -> int:
    """Length of ``[s, e)`` inside ``window``."""
    return max(0, min(e, window[1]) - max(s, window[0]))


def op_label(hlo: str) -> str:
    """A short stable name for an XLA op event: the instruction name
    without its numeric suffix, and its result type.  ``%blur_block.3 =
    f32[32,512]{...} custom-call(...)`` reads ``blur_block f32[32,512]``."""
    m = _OP_NAME.match(hlo)
    if not m:
        return hlo[:80]
    return f"{m.group(1)} {m.group(2).split('{')[0]}"


class Trace:
    """The events of one profiler session that the metrics read."""

    def __init__(self, planes):
        self.modules: Dict[int, List[Tuple[int, int, str]]] = {}
        self.ops: Dict[int, List[Tuple[int, int, str]]] = {}
        self.host: List[Tuple[int, int, str, str]] = []
        self.window: Optional[Interval] = None
        for plane in planes:
            m = DEVICE_PLANE.match(plane.name)
            if m:
                dev = int(m.group(1))
                for line in plane.lines:
                    evs = [(e.start_ns, e.end_ns, e.name) for e in line.events]
                    if line.name == "XLA Modules":
                        self.modules[dev] = evs
                    elif line.name == "XLA Ops":
                        self.ops[dev] = evs
            elif plane.name == "/host:CPU":
                for line in plane.lines:
                    for e in line.events:
                        self.host.append((e.start_ns, e.end_ns, e.name,
                                          line.name))
                        if e.name == WINDOW and self.window is None:
                            self.window = (e.start_ns, e.end_ns)
        self.host.sort()

    @classmethod
    def from_dir(cls, log_dir: str) -> "Trace":
        from jax.profiler import ProfileData

        files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                          recursive=True)
        if len(files) != 1:
            raise RuntimeError(f"expected one .xplane.pb under {log_dir}, "
                               f"found {len(files)}")
        return cls(ProfileData.from_file(files[0]).planes)

    # -- the window --------------------------------------------------------
    def _window(self) -> Interval:
        if self.window is None:
            raise RuntimeError(f"no {WINDOW!r} annotation in the trace")
        return self.window

    @property
    def window_s(self) -> float:
        s, e = self._window()
        return (e - s) / 1e9

    # -- device busy -----------------------------------------------------
    def busy_intervals(self, dev: int) -> List[Interval]:
        w = self._window()
        return [(max(s, w[0]), min(e, w[1]))
                for s, e in merge([(s, e) for s, e, _ in
                                   self.modules.get(dev, [])])
                if clip(s, e, w) > 0]

    def devices_used(self) -> List[int]:
        return sorted(d for d in self.modules if self.busy_intervals(d))

    def busy_s(self) -> float:
        """Seconds in which a program ran, averaged over the chips used."""
        used = self.devices_used()
        if not used:
            return 0.0
        return sum(sum(e - s for s, e in self.busy_intervals(d))
                   for d in used) / len(used) / 1e9

    # -- kernels -----------------------------------------------------------
    def op_events(self, pattern: str) -> List[Tuple[int, int, str, int]]:
        """``(start, end, hlo, device)`` of every op in the window whose
        HLO text matches ``pattern`` (a regular expression)."""
        rx = re.compile(pattern)
        w = self._window()
        return [(s, e, name, d) for d, evs in self.ops.items()
                for s, e, name in evs if clip(s, e, w) > 0 and rx.search(name)]

    # -- breakdown -----------------------------------------------------------
    def leaf_ops(self, dev: int) -> List[Tuple[int, int, str]]:
        """Ops that contain no other op (a ``while`` is its body's ops)."""
        evs = sorted(self.ops.get(dev, []), key=lambda x: (x[0], -x[1]))
        leaves = []
        for i, (s, e, name) in enumerate(evs):
            nxt = evs[i + 1] if i + 1 < len(evs) else None
            if nxt is None or nxt[0] >= e:
                leaves.append((s, e, name))
        return leaves

    def top_ops(self, k: int = 10) -> List[list]:
        w = self._window()
        tot: Dict[str, int] = {}
        for d in self.ops:
            for s, e, name in self.leaf_ops(d):
                n = clip(s, e, w)
                if n:
                    label = op_label(name)
                    tot[label] = tot.get(label, 0) + n
        top = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
        return [[name, ns / 1e9] for name, ns in top]

    def idle_gaps(self, k: int = 10) -> List[list]:
        """The ``k`` longest device idle gaps inside the window, each named
        by the host event that overlaps it most (``event@thread``)."""
        w = self._window()
        gaps: List[Interval] = []
        for d in self.devices_used():
            edges = [w[0]]
            for s, e in self.busy_intervals(d):
                edges += [s, e]
            edges.append(w[1])
            gaps += [(edges[i], edges[i + 1])
                     for i in range(0, len(edges), 2)
                     if edges[i + 1] > edges[i]]
        gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:k]
        out = []
        for gs, ge in gaps:
            best, best_n = "no host event", 0
            for s, e, name, thread in self.host:
                if s >= ge:
                    break
                n = clip(s, e, (gs, ge))
                if n > best_n and name != WINDOW:
                    best, best_n = f"{name}@{thread or 'thread'}", n
            out.append([best, (ge - gs) / 1e9])
        return out
