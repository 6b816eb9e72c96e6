"""jit'd wrappers for the blur kernels.  The Pallas path is the TPU target:
compiled where a lowering exists, interpreted on CPU (the mode resolves via
``kernels.pallas_support``); ``use_ref=True`` selects the pure-jnp oracle."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.blur import kernel as K
from repro.kernels.blur import ref as R
from repro.kernels.pallas_support import resolve_interpret


@partial(jax.jit, static_argnames=("kind", "use_ref"))
def blur_block(block: jax.Array, kind: str = "median",
               use_ref: bool = False) -> jax.Array:
    """block: padded [RB+2, W+2] -> blurred interior [RB, W]."""
    if use_ref:
        full = (R.median_blur_ref(block) if kind == "median"
                else R.gaussian_blur_ref(block))
        return full[1:-1, 1:-1]
    return K.blur_rows_pallas(block, kind=kind,
                              interpret=resolve_interpret(None))


def blur_rows(src_padded: jax.Array, row_block: int, r, kind: str,
              use_ref: bool = False) -> jax.Array:
    """Blur rows [r*RB, (r+1)*RB) of a padded image [H+2, W+2].
    ``r`` may be traced (dynamic row-block index)."""
    RB = row_block
    halo = jax.lax.dynamic_slice_in_dim(src_padded, r * RB, RB + 2, axis=0)
    return blur_block(halo, kind=kind, use_ref=use_ref)
