"""Region engine (``core/region.py``): host wall time a region spends on
its tasks (``RegionStats.busy_s``: dispatch, polling, commits) per chunk
(``RegionStats.chunks``) over the window."""


def read(cell):
    chunks = cell.counters_end["chunks"] - cell.counters_open["chunks"]
    if not chunks:
        return None
    busy = cell.counters_end["busy_s"] - cell.counters_open["busy_s"]
    return busy / chunks * 1e3
