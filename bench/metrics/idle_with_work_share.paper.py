"""Device: share of the profiled span in which no program ran on a chip
while a region held a task, i.e. some ``region<N>.*`` span (prepare,
issue, wait, readback, commit, reconfig) was open on a host thread
(``bench/spans.py``).  The rest of ``idle_share.paper`` is idle time
with no region at work."""
from bench.spans import idle_with_work_share


def read(cell):
    return idle_with_work_share(cell.trace)
