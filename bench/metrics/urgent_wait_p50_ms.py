"""Median, over the priority-0 tasks due in the window, of the time from
the moment each was due to ``Task.t_first_served``: the paper's service
time (i), "deploying the most urgent ones as fast as possible"."""
from bench.stats import percentile, since_due


def read(cell):
    v = percentile(since_due(cell.records, "due", "t_first",
                             where=lambda r: r["priority"] == 0), 50)
    return None if v is None else v * 1e3
