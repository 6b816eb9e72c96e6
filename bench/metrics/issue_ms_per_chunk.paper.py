"""Region engine (``core/region.py``, ``_do_launch``'s ``issue``): mean
host time of one chunk executable call, from the call to its return
with the chunk enqueued (the ring's ``issue`` spans)."""
from bench.spans import mean_ms


def read(cell):
    return mean_ms(cell.events, "issue")
