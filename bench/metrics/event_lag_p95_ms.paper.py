"""Scheduler (``core/scheduler.py``, ``_handle``): 95th percentile of the
time an interrupt (task done, preempted, reconfigured, a submission's
wake-up) waits from being raised to the event loop taking it (the
``lag_s`` of the ring's ``handle`` spans)."""
from bench.spans import attr_values
from bench.stats import percentile


def read(cell):
    v = percentile(attr_values(cell.events, "handle", "sched", "lag_s"), 95)
    return None if v is None else v * 1e3
