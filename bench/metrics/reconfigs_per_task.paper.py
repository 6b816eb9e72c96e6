"""Reconfiguration (``core/reconfig.py``): executable loads into regions
(``RegionStats.reconfigs``) over the window, per task done."""


def read(cell):
    n = cell.n_done()
    if not n:
        return None
    return (cell.counters_end["reconfigs"]
            - cell.counters_open["reconfigs"]) / n
