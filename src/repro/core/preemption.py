"""Programmer abstractions for preemption (paper §5.2): ``for_save``,
``checkpoint`` (on ContextRecord), and the chunked preemptible runner.

A preemptible kernel is written as::

    def kernel(ctx, state, ints, floats):
        def body_k(ctx, k, state):
            def body_row(ctx, row, state):
                ... compute ...
                ctx = ctx.checkpoint(SLOT_ROW, row)   # paper: checkpoint(row);
                return ctx, state
            ctx, state = for_save(ctx, SLOT_ROW, 0, H, 1, body_row, state)
            ctx = ctx.checkpoint(SLOT_K, k)           # paper: checkpoint(k);
            return ctx, state
        ctx, state = for_save(ctx, SLOT_K, 0, iters, 1, body_k, state)
        return ctx.finish(), state

The kernel runs in bounded *chunks*: each dispatch gets ``ctx.budget``
innermost iterations; when the budget hits 0 every enclosing ``for_save``
exits, leaving the checkpointed slots as the resume point.  Preemption and
stragglers are handled BETWEEN chunks by the region worker (DESIGN.md §2.1:
the TPU-idiomatic replacement for the FPGA's asynchronous per-RR reset).
"""
from __future__ import annotations

import ctypes
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.context import ContextRecord


def for_save(ctx: ContextRecord, slot: int, start, stop, step,
             body: Callable, state: Any):
    """Preemptible counted loop (paper's ``for_save`` macro).

    ``body(ctx, i, state) -> (ctx, state)`` SHOULD call
    ``ctx.checkpoint(slot, i)`` (by convention at iteration end) — exactly
    like the paper, where what/when to checkpoint is the programmer's choice.
    Resumes from the checkpointed slot if set; restarts cleanly otherwise.
    """
    ctx = ctx.declare(slot, start, step)
    i0 = ctx.resume_value(slot, start)
    ctx = ctx.unsave(slot)

    def cond(carry):
        c, i, _ = carry
        return jnp.logical_and(jnp.logical_and(i < stop, c.budget > 0),
                               c.intr == 0)

    def loop(carry):
        c, i, s = carry
        c = c.clear_intr()
        c, s = body(c, i, s)
        # the iteration counts iff the body fully completed — i.e. no nested
        # for_save inside it was interrupted by the budget.  An interrupted
        # iteration resumes from its own checkpoints on the next chunk.
        ok = c.intr == 0
        c = c.dec_budget()
        i2 = jnp.where(ok, i + step, i)
        return (c, i2, s)

    ctx, i_end, state = jax.lax.while_loop(cond, loop, (ctx, i0, state))
    # completed normally -> clear the slot so a later re-entry restarts;
    # interrupted -> keep the user's checkpoints, and tell enclosing loops.
    completed = i_end >= stop
    cleared = ctx.clear(slot)
    ctx = jax.tree.map(lambda a, b: jnp.where(completed, a, b), cleared, ctx)
    ctx = ctx.mark_intr(jnp.where(completed, 0, 1))
    return ctx, state


def make_chunk_fn(kernel_fn: Callable):
    """Wrap a preemptible kernel into the uniform chunk entry point:

        chunk(ctx, state, ints, floats) -> (ctx, state)

    jit-able; the region worker re-dispatches it until ``ctx.done == 1``.
    """
    def chunk(ctx: ContextRecord, state, ints, floats):
        return kernel_fn(ctx, state, ints, floats)

    return chunk


def make_pipelined_chunk(kernel_fn: Callable):
    """The pipelined chunk entry point (DESIGN.md §8):

        chunk(ctx, state, ints, floats, budget) -> (ctx, state, done)

    Three deltas against ``make_chunk_fn``, all in service of issuing chunk
    *k+1* before chunk *k*'s ``done`` flag has resolved on the host:

    - **done-gated identity** — on a finished context the chunk is an exact
      pass-through.  This is the speculative-discard rule: the one chunk
      the worker issues beyond completion computes nothing and its outputs
      are bit-identical to the final state, so speculation can never change
      results.
    - **budget reset inside the executable** — ``ctx.with_budget`` moves
      from a per-chunk eager host op into the traced program; ``budget`` is
      a *non-donated* scalar argument the worker uploads once per launch.
    - **independent done snapshot** — the third output is a fresh buffer
      (``optimization_barrier`` keeps XLA from aliasing it to the context's
      own ``done``), so the worker can poll/read chunk *k*'s flag after
      chunk *k*'s context has already been donated into chunk *k+1*.
    """
    def chunk(ctx: ContextRecord, state, ints, floats, budget):
        def run(c, s):
            return kernel_fn(c.with_budget(budget), s, ints, floats)

        def skip(c, s):
            return c, s

        ctx, state = jax.lax.cond(ctx.done == 0, run, skip, ctx, state)
        done = jax.lax.optimization_barrier(ctx.done)
        return ctx, state, done

    return chunk


class PreemptFlag:
    """Host-writable device flag the megakernel polls on-device
    (DESIGN.md §10).

    Value protocol: ``0`` = keep running; ``N >= 1`` = exit at the first
    chunk boundary ``k >= N`` (``k`` counts chunks completed within the
    current launch).  ``Region.request_preempt`` writes ``1`` — "the next
    boundary" — while tests and the serving probe write an exact ``N`` for
    deterministic boundary placement.

    The flag lives in a one-element ``int32`` device buffer that is passed
    to the compiled megakernel as a *non-donated* argument.  On the CPU
    backend the buffer is host memory, so a host store is visible to the
    running ``while_loop`` within one iteration — the zero-copy "device
    put" the FPGA's AXI preempt line maps to.  ``np.asarray`` of a jax
    array is zero-copy but read-only; the writable view is built over the
    same bytes via ``unsafe_buffer_pointer`` (an aligned ``int32`` store
    is atomic on every ISA the CPU backend targets, so the device-side
    reader can never observe a torn value).  Any other platform's buffer
    is device memory that a host store must never write through, so the
    platform is checked before the pointer is ever read.
    """

    def __init__(self, device=None):
        device = device if device is not None else jax.devices()[0]
        if device.platform != "cpu":
            raise RuntimeError(
                f"engine='megakernel' needs a host-mappable preempt flag, "
                f"which only the CPU backend has; {device} is a "
                f"{device.platform} device — use engine='pipelined'")
        self._dev = jnp.zeros((1,), jnp.int32, device=device)
        jax.block_until_ready(self._dev)
        ptr = self._dev.unsafe_buffer_pointer()
        self._view = np.ctypeslib.as_array(
            ctypes.cast(ptr, ctypes.POINTER(ctypes.c_int32)), shape=(1,))
        self._view[0] = 0

    @property
    def device(self):
        """The device array to pass as the megakernel's ``flag`` argument
        (must never be donated — one buffer serves every launch)."""
        return self._dev

    def write(self, boundary: int):
        self._view[0] = boundary

    def read(self) -> int:
        return int(self._view[0])

    def clear(self):
        self._view[0] = 0


def make_megakernel(kernel_fn: Callable):
    """The megakernel entry point (DESIGN.md §10):

        mega(ctx, state, ints, floats, budget, flag)
            -> (ctx, state, done, n_chunks)

    The whole per-task chunk loop folded into ONE compiled dispatch: a
    ``jax.lax.while_loop`` whose body is exactly the pipelined chunk body
    (``kernel_fn(ctx.with_budget(budget), ...)``), so a launch costs one
    host round trip regardless of how many chunks the budget slices the
    kernel into.  Preemption stays bounded by one chunk: every iteration
    re-reads ``flag`` (a host-writable one-element buffer) and the loop
    exits at the first boundary ``k >= flag`` when ``flag != 0``.

    The flag read is funnelled through ``optimization_barrier`` together
    with the loop counter: without that data dependence XLA hoists the
    read out of the loop as invariant and the device would never observe
    a mid-flight host write.

    ``done`` is an independent snapshot (same rule as
    ``make_pipelined_chunk``): the worker polls it for completion after
    ``ctx`` has been donated, and ``done == 0`` on exit is exactly "the
    flag fired" — the partial context feeds the ContextBank commit path
    bit-identically to a host-driven preemption at the same boundary.
    ``n_chunks`` reports how many chunks actually ran.
    """
    def mega(ctx: ContextRecord, state, ints, floats, budget, flag):
        def cond(carry):
            c, _s, _k, stop = carry
            return jnp.logical_and(c.done == 0, stop == 0)

        def body(carry):
            c, s, k, _ = carry
            c, s = kernel_fn(c.with_budget(budget), s, ints, floats)
            k = k + 1
            f, _ = jax.lax.optimization_barrier((flag[0], k))
            stop = jnp.where(jnp.logical_and(f != 0, k >= f),
                             jnp.int32(1), jnp.int32(0))
            return (c, s, k, stop)

        ctx, state, k, _stop = jax.lax.while_loop(
            cond, body, (ctx, state, jnp.int32(0), jnp.int32(0)))
        done = jax.lax.optimization_barrier(ctx.done)
        return ctx, state, done, k

    return mega


def run_to_completion(chunk_fn, ctx, state, ints, floats, budget: int,
                      max_chunks: int = 100000):
    """Host loop for tests: run chunks until done (no scheduler)."""
    chunks = 0
    while int(ctx.done) == 0 and chunks < max_chunks:
        ctx = ctx.with_budget(budget)
        ctx, state = chunk_fn(ctx, state, ints, floats)
        chunks += 1
    return ctx, state, chunks
