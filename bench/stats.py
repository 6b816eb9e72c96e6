"""Order statistics shared by the metric readers.

Every tail the benchmark reports is a nearest-rank percentile over all
requests due in the window: the value below which ``q`` percent of the
samples lie, taken from the samples themselves (no interpolation), so a
reading is always a latency that some request had.
"""
from __future__ import annotations

import math
from typing import Iterable, Optional


def percentile(values: Iterable[float], q: float) -> Optional[float]:
    """Nearest-rank ``q``-th percentile (0 < q <= 100); None when empty."""
    s = sorted(values)
    if not s:
        return None
    if not 0.0 < q <= 100.0:
        raise ValueError(f"percentile q must be in (0, 100], got {q}")
    return s[min(len(s) - 1, max(0, math.ceil(q / 100.0 * len(s)) - 1))]


def since_due(records, start_key: str, end_key: str = "t_done",
              where=None) -> list:
    """Per-request latencies ``record[end_key] - record[start_key]`` in
    seconds, over the requests ``where`` accepts.  A request that never
    reached ``end_key`` counts as an infinite latency: it missed every
    limit, and a tail over the requests due must not quietly drop it."""
    out = []
    for r in records:
        if where is not None and not where(r):
            continue
        t0, t1 = r.get(start_key), r.get(end_key)
        out.append(math.inf if t0 is None or t1 is None else t1 - t0)
    return out
