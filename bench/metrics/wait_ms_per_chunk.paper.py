"""Region engine (``core/region.py``, ``_do_launch``): mean time a region
waits for a chunk's ``done`` flag, polling (``_wait_ready``) and then
reading it to the host (the ring's ``wait`` spans)."""
from bench.spans import mean_ms


def read(cell):
    return mean_ms(cell.events, "wait")
