"""The paper's task set (§6.1) as preemptible Controller kernels:
Median Blur over 1/2/3 iterations and Gaussian Blur over 1 iteration,
written with the ``for_save`` / ``checkpoint`` abstractions of §5.2.

State layout (ArgBundle buffer slots):
    bufs[0] = ping image, padded [H+2, W+2] f32 (zero ring)
    bufs[1] = pong image, same shape
Iteration k reads ping when k is even and writes pong (and vice versa), so
partial progress always lives in the buffers — checkpoint/resume needs no
extra copies.  Context slots: 0 = iteration k, 1 = row block index.  The
checkpoint convention stores the NEXT index (exactly-once row blocks).

The row-block loop is the preemption granularity: one ``budget`` unit = one
row block = one Pallas kernel invocation (the analogue of the paper's
checkpoint at each (col, row, k) level, coarsened to row blocks for TPU
efficiency).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.controller.kernels import ctrl_kernel
from repro.core.context import ContextRecord
from repro.core.preemption import for_save
from repro.kernels.blur.ops import blur_rows

ROW_BLOCK = 32
SLOT_K, SLOT_ROW = 0, 1


def _blur_task(ctx: ContextRecord, bufs, ints, floats, kind: str):
    ping, pong = bufs[0], bufs[1]
    Hp2, Wp2 = ping.shape
    H = Hp2 - 2
    n_rb = H // ROW_BLOCK
    iters = ints[2]

    def body_row(ctx, r, state):
        k = ctx.var[SLOT_K]

        # one branch per parity, chosen by lax.cond: a whole-image
        # jnp.where select between the buffers feeding the kernel's
        # dynamic_update_slice aborts the TPU fusion pass
        def step(src, dst):
            rows = blur_rows(src, ROW_BLOCK, r, kind)
            return jax.lax.dynamic_update_slice(
                dst, rows.astype(dst.dtype), (r * ROW_BLOCK + 1, 1))

        def even(ping, pong):  # iteration k reads ping, writes pong
            return ping, step(ping, pong)

        def odd(ping, pong):
            return step(pong, ping), pong

        state = jax.lax.cond(k % 2 == 0, even, odd, *state)
        ctx = ctx.checkpoint(SLOT_ROW, r + 1)  # paper: checkpoint(row);
        return ctx, state

    def body_k(ctx, k, state):
        # row loop nested under the iteration loop (Listing 1.1 structure)
        ctx = ctx.checkpoint(SLOT_K, k)  # current iteration (re-entrant)
        ctx, state = for_save(ctx, SLOT_ROW, 0, n_rb, 1, body_row, state)
        # advance k iff the row loop fully completed (paper: checkpoint(k);)
        ctx_adv = ctx.checkpoint(SLOT_K, k + 1)
        completed = ctx.intr == 0
        ctx = jax.tree.map(lambda a, b: jnp.where(completed, a, b),
                           ctx_adv, ctx)
        return ctx, state

    ctx, (ping, pong) = for_save(ctx, SLOT_K, 0, iters, 1, body_k,
                                 (ping, pong))
    finished = ctx.intr == 0
    done_ctx = ctx.finish()
    ctx = jax.tree.map(lambda a, b: jnp.where(finished, a, b), done_ctx, ctx)
    return ctx, (ping, pong) + tuple(bufs[2:])


@ctrl_kernel("MedianBlur", backend="PYNQ",
             ktile_args=("input_array", "output_array"),
             int_args=("H", "W", "iters"), default_budget=8, pallas=True)
def median_blur_task(ctx, bufs, ints, floats):
    return _blur_task(ctx, bufs, ints, floats, "median")


@ctrl_kernel("GaussianBlur", backend="PYNQ",
             ktile_args=("input_array", "output_array"),
             int_args=("H", "W", "iters"), default_budget=8, pallas=True)
def gaussian_blur_task(ctx, bufs, ints, floats):
    return _blur_task(ctx, bufs, ints, floats, "gaussian")


def make_image(rng, size: int, pad_to: int = 128):
    """Random image padded to a 128-multiple width plus the zero halo ring."""
    import numpy as np

    H = W = int(np.ceil(size / pad_to) * pad_to)
    img = np.zeros((H + 2, W + 2), np.float32)
    img[1:size + 1, 1:size + 1] = rng.random((size, size), dtype=np.float32)
    return img


def result_image(task, iters: int):
    """Fetch the blurred image from a finished task (ping/pong parity)."""
    ping, pong = task.result
    return pong if iters % 2 == 1 else ping
