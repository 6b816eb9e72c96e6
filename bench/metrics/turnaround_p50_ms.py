"""Median, over every task due in the window, of the time from the moment
it was due to ``Task.t_done``."""
from bench.stats import percentile, since_due


def read(cell):
    v = percentile(since_due(cell.records, "due", "t_done"), 50)
    return None if v is None else v * 1e3
