"""Device: share of the profiled span in which no program ran on a chip,
averaged over the chips used (``bench/trace.py``)."""


def read(cell):
    if cell.trace is None or not cell.trace.devices_used():
        return None
    return (1.0 - cell.trace.busy_s() / cell.trace.window_s) * 100.0
