"""Plain reference for the paper's blur tasks, written from the task
definition alone (arXiv 2209.04410 §6; 3x3 Median Blur and 3x3 Gaussian
Blur with weights [[1,2,1],[2,4,2],[1,2,1]]/16, iterated).

An image is a ``[H+2, W+2]`` array with a one-pixel zero ring that stays
zero: each pass replaces the interior from its 3x3 neighbourhood.  The
whole array is blurred, including any zero padding inside the ring.
Nothing here imports the program.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

GAUSS = (1.0, 2.0, 1.0, 2.0, 4.0, 2.0, 1.0, 2.0, 1.0)


def _neighbours(x):
    h, w = x.shape[0] - 2, x.shape[1] - 2
    return [x[i:i + h, j:j + w] for i in range(3) for j in range(3)]


def _pass(x, kind: str):
    nb = _neighbours(x)
    if kind == "median":
        y = jnp.sort(jnp.stack(nb), axis=0)[4]
    elif kind == "gaussian":
        y = nb[0] * (GAUSS[0] / 16.0)
        for v, wgt in zip(nb[1:], GAUSS[1:]):
            y = y + v * (wgt / 16.0)
    else:
        raise ValueError(f"unknown blur kind {kind!r}")
    return jnp.zeros_like(x).at[1:-1, 1:-1].set(y)


@partial(jax.jit, static_argnames=("iters", "kind", "dtype"))
def blur(img, iters: int, kind: str, dtype=jnp.float32):
    """``iters`` passes of ``kind`` blur over a padded image, computed in
    ``dtype`` and returned as float32."""
    x = img.astype(dtype)
    for _ in range(iters):
        x = _pass(x, kind)
    return x.astype(jnp.float32)
