"""Region engine (``core/region.py``, ``_do_launch``'s ``prepare``): mean
host time to put one launch's context, buffers and scalars on its
region's device, fresh or resumed (the ring's ``prepare`` spans)."""
from bench.spans import mean_ms


def read(cell):
    return mean_ms(cell.events, "prepare")
