"""Operations and bytes that the algorithm needs, per kernel call, from
shapes.  This counts the work of the live data, not what a padded
program computes, so a change that stops padding raises a share and can
never push it past 100%.

Blur (``kernels/blur``): one call blurs one block of ``rows`` image rows
of width ``width`` (a 3x3 stencil over a halo of one row and column on
each side).  It must read the halo block once and write the block once;
the stencil's 9 loads per output hit on-chip memory.  Its arithmetic (at
most 38 operations a pixel: the 19 compare-exchanges of a 9-input median
network) would take under 2% of the time its bytes take at the chip's
published peaks, so bytes bound it and are all that is counted.
"""
from __future__ import annotations

import re

F32 = 4

# a blur call in the device trace: the custom call of the jitted
# ``blur_block`` wrapper, ``%blur_block.N = f32[rows,width]{...}``
BLUR_CALL = r"^%blur_block(\.\d+)? = f32\[\d+,\d+\].*tpu_custom_call"
_BLUR_SHAPE = re.compile(r"^%blur_block(?:\.\d+)? = f32\[(\d+),(\d+)\]")


def blur_call_shape(hlo: str) -> tuple:
    """``(rows, width)`` of the block a traced blur call wrote."""
    m = _BLUR_SHAPE.match(hlo)
    if not m:
        raise ValueError(f"not a blur call: {hlo[:80]}")
    return int(m.group(1)), int(m.group(2))


def blur_call_bytes(rows: int, width: int) -> int:
    """HBM bytes one call needs: the ``(rows+2) x (width+2)`` halo block
    in, the ``rows x width`` block out, f32."""
    return F32 * ((rows + 2) * (width + 2) + rows * width)


def blur_pass_bytes(side: int, rows: int = 32) -> int:
    """HBM bytes of one pass over a padded ``side x side`` image as the
    program runs it: ``side / rows`` calls of ``rows x side``."""
    return (side // rows) * blur_call_bytes(rows, side)


def blur_live_bytes(size: int) -> int:
    """HBM bytes one pass needs for the live ``size x size`` image: its
    halo-ringed input once and its output once, f32."""
    return F32 * ((size + 2) * (size + 2) + size * size)
