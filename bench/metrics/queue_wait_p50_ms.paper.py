"""Scheduler (``core/scheduler.py``, ``core/policy.py``): median time a
task waits in the priority queues, ``Task.t_arrived`` (admission) to
``Task.t_first_served`` (first launch on a region)."""
from bench.stats import percentile, since_due


def read(cell):
    v = percentile(since_due(cell.records, "t_arrived", "t_first"), 50)
    return None if v is None else v * 1e3
