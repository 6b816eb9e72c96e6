"""Kernels (``kernels/blur``): share of the HBM roofline that the blur
Pallas kernel reaches in the profiled span.

Each traced call of the kernel names the padded block it wrote.  The
bytes a call needs are its block's bytes times the live share of the
images of that padded side (``bench/work.py``: live ``size x size``
pixels against the padded side the program blurs), over the tasks the
window finished; the least time is those bytes over the chip's peak
bandwidth, and the share is that over the calls' device time."""
from bench import work
from bench.blurmix import padded_side


def read(cell):
    if cell.trace is None or cell.peaks is None:
        return None
    calls = cell.trace.op_events(work.BLUR_CALL)
    reqs = {r["i"]: r for r in cell.requests}
    live, padded = {}, {}
    for rec in cell.records:
        if rec["t_done"] is None:
            continue
        r = reqs[rec["i"]]
        side = padded_side(r["size"])
        live[side] = live.get(side, 0) + r["iters"] * work.blur_live_bytes(
            r["size"])
        padded[side] = padded.get(side, 0) + r["iters"] * \
            work.blur_pass_bytes(side)
    need, busy_ns = 0.0, 0
    for s, e, hlo, _dev in calls:
        rows, width = work.blur_call_shape(hlo)
        if width not in padded:
            continue
        need += work.blur_call_bytes(rows, width) * live[width] / \
            padded[width]
        busy_ns += e - s
    if not busy_ns:
        return None
    return need / cell.peaks["hbm_bytes_per_s"] / (busy_ns / 1e9) * 100.0
