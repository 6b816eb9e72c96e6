"""Region engine (``core/region.py``, ``_finish_done``): mean time of a
finished task's result copy from the device to the host (the ring's
``readback`` spans)."""
from bench.spans import mean_ms


def read(cell):
    return mean_ms(cell.events, "readback")
