"""Region engine, preemption: 95th percentile of the time from a region's
``preempt_request`` to its ``preempt_honored`` (the program's tracer).

The pairing is copied from the program's ``obs/metrics.py``: per region
track, the first outstanding request pairs with the next honour, and a
``done`` on that track drops a request the task outran."""
from bench.stats import percentile


def response_times(events) -> list:
    pending, samples = {}, []
    for e in sorted(events, key=lambda e: e.t):
        if not e.track or e.track[0] != "region":
            continue
        if e.kind == "preempt_request":
            pending.setdefault(e.track, e.t)
        elif e.kind == "preempt_honored":
            t_req = pending.pop(e.track, None)
            if t_req is not None:
                samples.append(max(e.t - t_req, 0.0))
        elif e.kind == "done":
            pending.pop(e.track, None)
    return samples


def read(cell):
    v = percentile(response_times(cell.events), 95)
    return None if v is None else v * 1e3
