"""Cluster frontend (``cluster/frontend.py``): cross-shell migrations the
rebalancer completed over the window, per 100 tasks done."""


def read(cell):
    if "migrations" not in cell.counters_end:
        return None
    n = cell.n_done()
    if not n:
        return None
    return (cell.counters_end["migrations"]
            - cell.counters_open["migrations"]) / n * 100.0
