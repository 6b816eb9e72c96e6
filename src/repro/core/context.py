"""Kernel context — the paper's ``struct context`` (Listing 1.3), verbatim
fields, as a JAX pytree:

    struct context { int var[N]; int init_var[N]; int incr_var[N];
                     int saved[N]; int valid; }

plus three runtime scalars: ``done`` (kernel finished), ``budget`` (chunk
iteration budget — the cooperative-preemption analogue of the asynchronous
RR reset, DESIGN.md §2.1) and ``intr`` (set when a ``for_save`` loop was cut
short by the budget; lets enclosing loops distinguish "inner loop completed
exactly at the budget boundary" from "inner loop interrupted" — without it
the nested-loop resume can livelock).

The device copy lives in a per-region HBM buffer (the BRAM bank analogue).
``ContextBank`` keeps the host-side committed copy with the paper's
``valid``-flag protocol realized as a double-buffered commit: a crash or
preemption *during* a save leaves the previous buffer valid.
"""
from __future__ import annotations

import dataclasses
import threading
from dataclasses import dataclass
from typing import Any, Optional

import jax
import jax.numpy as jnp

N_CTX = 8  # compile-time N of the paper's prototype ("up to N integers")

_FIELDS = ("var", "init_var", "incr_var", "saved", "valid", "done",
           "budget", "intr")


@jax.tree_util.register_pytree_node_class
@dataclass
class ContextRecord:
    var: jax.Array        # i32[N_CTX]
    init_var: jax.Array   # i32[N_CTX]
    incr_var: jax.Array   # i32[N_CTX]
    saved: jax.Array      # i32[N_CTX]
    valid: jax.Array      # i32 scalar
    done: jax.Array       # i32 scalar
    budget: jax.Array     # i32 scalar — remaining iterations this chunk
    intr: jax.Array       # i32 scalar — a loop was interrupted by the budget

    def tree_flatten(self):
        return (tuple(getattr(self, f) for f in _FIELDS), None)

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        return cls(*leaves)

    def _replace(self, **kw) -> "ContextRecord":
        return dataclasses.replace(self, **kw)

    # -- construction ------------------------------------------------------
    @classmethod
    def fresh(cls, budget: int = 0) -> "ContextRecord":
        # NOTE: distinct buffers — the chunk executable donates the context,
        # and XLA rejects donating one buffer for several arguments.
        z = lambda: jnp.zeros((N_CTX,), jnp.int32)
        return cls(var=z(), init_var=z(), incr_var=z(), saved=z(),
                   valid=jnp.int32(1), done=jnp.int32(0),
                   budget=jnp.int32(budget), intr=jnp.int32(0))

    def with_budget(self, budget) -> "ContextRecord":
        return self._replace(budget=jnp.asarray(budget, jnp.int32),
                             intr=jnp.zeros((), jnp.int32))

    # -- the paper's checkpoint()/context_vars() operations ----------------
    def checkpoint(self, slot: int, value) -> "ContextRecord":
        """checkpoint(var): store ``value`` into slot and mark it saved."""
        return self._replace(
            var=self.var.at[slot].set(jnp.asarray(value, jnp.int32)),
            saved=self.saved.at[slot].set(1))

    def declare(self, slot: int, init, incr) -> "ContextRecord":
        """context_vars bookkeeping: remember loop init/increment."""
        return self._replace(init_var=self.init_var.at[slot].set(init),
                             incr_var=self.incr_var.at[slot].set(incr))

    def resume_value(self, slot: int, start):
        """Loop start: saved value if this slot was checkpointed, else start."""
        return jnp.where(self.saved[slot] == 1, self.var[slot],
                         jnp.asarray(start, jnp.int32))

    def unsave(self, slot: int) -> "ContextRecord":
        return self._replace(saved=self.saved.at[slot].set(0))

    def clear(self, slot: int) -> "ContextRecord":
        """Clear a slot after its loop completes (so re-entry restarts)."""
        return self._replace(var=self.var.at[slot].set(0),
                             saved=self.saved.at[slot].set(0))

    def finish(self) -> "ContextRecord":
        return self._replace(done=jnp.int32(1))

    def dec_budget(self) -> "ContextRecord":
        return self._replace(budget=self.budget - 1)

    def clear_intr(self) -> "ContextRecord":
        return self._replace(intr=jnp.zeros((), jnp.int32))

    def mark_intr(self, flag) -> "ContextRecord":
        return self._replace(intr=jnp.asarray(flag, jnp.int32))


@dataclass
class Committed:
    """One committed context snapshot.

    Two residencies (DESIGN.md §8):

    - ``device=False`` (the seed behaviour): ``context``/``payload`` leaves
      are host numpy copies, ready for disk spill or cross-shell shipping.
    - ``device=True`` (lazy spill): the leaves are still device-resident
      ``jax.Array``s committed by the region worker without any host round
      trip.  ``region_rid`` records which region produced them; a resume on
      the *same* region consumes them directly (no host copy at all), while
      migration / checkpointing / cross-region resume calls
      ``materialize()`` to produce the committed host copy on demand.
    """
    seqno: int
    context: Any          # ContextRecord (numpy, or jax.Array when device)
    payload: Any          # kernel state pytree (e.g. partial output buffers)
    # which task committed this snapshot: failover recovery must never
    # resume task X from a stale commit task Y left in the same bank
    tid: Optional[int] = None
    device: bool = False           # leaves still live in device memory
    region_rid: Optional[int] = None  # region whose HBM holds them
    # identity of the owning Region *object* — rids restart at 0 on every
    # shell, so the same-region fast path must compare identity, never the
    # number (a failover commit from another shell's region 0 has to take
    # the materializing path, exactly like any other cross-region resume)
    owner: Any = None
    _host: Optional["Committed"] = dataclasses.field(
        default=None, repr=False, compare=False)
    _mat_lock: Any = dataclasses.field(
        default_factory=threading.Lock, repr=False, compare=False)

    def materialize(self) -> "Committed":
        """The committed *host* copy, produced on demand (and cached).

        A host-resident commit returns itself; a device-resident one pays
        the device→host transfer exactly once — this is the actual spill,
        deferred from preemption time to the first consumer that really
        needs host bytes (disk checkpoint, cross-shell migration, or a
        resume on a different region)."""
        if not self.device:
            return self
        with self._mat_lock:
            if self._host is None:
                # one batched device-to-host copy of every leaf
                host_ctx, host_payload = jax.device_get(
                    (self.context, self.payload))
                self._host = Committed(self.seqno, host_ctx, host_payload,
                                       tid=self.tid)
            return self._host


class KVBlockPool:
    """Fixed-size KV block allocator (DESIGN.md §13) — the paged-KV
    analogue of the region's BRAM banking.

    The *bytes* of the pages live in two device arrays the serving
    engine threads round-to-round (``[NB, BS, KV, hd]`` pools inside the
    decode task's ArgBundle — preemption commits them through the same
    ContextBank lazy-spill path as any payload).  This object is the
    host-side book-keeping: which page ids belong to which sequence,
    the free list, and the occupancy/eviction/reuse accounting the
    telemetry gauges expose.

    Block 0 is the reserved **null page**: block tables are padded with
    it, and inactive decode rows scatter zeros into it — duplicate
    same-value writes, so page content is deterministic under any batch
    composition and resume schedule.
    """

    def __init__(self, n_blocks: int, block_size: int, metrics=None):
        if n_blocks < 2:
            raise ValueError(f"need >= 2 blocks (block 0 is the null "
                             f"page), got {n_blocks}")
        if block_size < 1:
            raise ValueError(f"block_size must be >= 1, got {block_size}")
        self.n_blocks = n_blocks
        self.block_size = block_size
        # live metrics registry (obs/registry.py): None-guarded, same
        # zero-cost-disabled contract as every other layer
        self.metrics = metrics
        self._free = list(range(n_blocks - 1, 0, -1))  # pop() -> 1, 2, ...
        self._by_sid: dict = {}        # sid -> [block ids, in position order]
        self._ever_used: set = set()
        self.in_use = 0
        self.peak_in_use = 0
        self.evictions = 0             # blocks freed back to the pool
        self.reuse = 0                 # allocations of a previously-freed id
        self.alloc_deferred = 0        # ensure() calls refused for capacity

    # -- allocation --------------------------------------------------------
    def blocks_for(self, n_tokens: int) -> int:
        """Pages needed to hold ``n_tokens`` positions."""
        return -(-n_tokens // self.block_size)

    def ensure(self, sid: int, n_tokens: int) -> Optional[list]:
        """Grow ``sid``'s block list to cover ``n_tokens`` positions.

        Returns the sequence's full block list on success, or ``None``
        (and counts ``alloc_deferred``) when the pool cannot cover the
        growth — the caller defers admission until pages free up; the
        transaction is all-or-nothing, so a partial grab is never held
        across a deferral."""
        have = self._by_sid.setdefault(sid, [])
        need = self.blocks_for(n_tokens) - len(have)
        if need <= 0:
            return have
        if need > len(self._free):
            self.alloc_deferred += 1
            if not have:
                self._by_sid.pop(sid, None)
            return None
        for _ in range(need):
            bid = self._free.pop()
            if bid in self._ever_used:
                self.reuse += 1
            self._ever_used.add(bid)
            have.append(bid)
        self.in_use += need
        self.peak_in_use = max(self.peak_in_use, self.in_use)
        self._gauge()
        return have

    def blocks(self, sid: int) -> list:
        return self._by_sid.get(sid, [])

    def release(self, sid: int) -> int:
        """Free every page ``sid`` holds (slot eviction / failure)."""
        blocks = self._by_sid.pop(sid, [])
        if blocks:
            self._free.extend(reversed(blocks))
            self.in_use -= len(blocks)
            self.evictions += len(blocks)
            self._gauge()
            if self.metrics is not None:
                self.metrics.counter("kv_block_evictions").inc(len(blocks))
        return len(blocks)

    def _gauge(self):
        if self.metrics is not None:
            self.metrics.gauge("kv_blocks_in_use").set(self.in_use)

    # -- observability -----------------------------------------------------
    @property
    def free(self) -> int:
        return len(self._free)

    def occupancy(self) -> float:
        """In-use fraction of the allocatable pool (block 0 excluded)."""
        return self.in_use / max(self.n_blocks - 1, 1)

    def stats(self) -> dict:
        return {
            "blocks_total": self.n_blocks - 1,  # allocatable (null excluded)
            "block_size": self.block_size,
            "blocks_in_use": self.in_use,
            "blocks_peak": self.peak_in_use,
            "occupancy": self.occupancy(),
            "evictions": self.evictions,
            "reuse": self.reuse,
            "alloc_deferred": self.alloc_deferred,
        }


class ContextBank:
    """Per-region context storage — the BRAM bank + CPU-visible book-keeping.

    Double-buffered commits realize the paper's ``valid`` flag: ``commit``
    writes into the non-active buffer and only then flips the active index;
    a preemption/crash mid-commit leaves the other buffer intact.  The
    ``interrupt_next_commit`` hook lets tests inject exactly the torn-write
    failure the paper's valid flag guards against.
    """

    def __init__(self):
        self._buffers: list[Optional[Committed]] = [None, None]
        self._active = -1  # no valid commit yet
        self._seq = 0
        self._lock = threading.Lock()
        self.interrupt_next_commit = False  # test hook

    def commit(self, context, payload=None, tid=None, *,
               device: bool = False, region_rid=None, owner=None) -> int:
        """Commit a snapshot.  ``device=True`` is the lazy-spill path: the
        jax arrays are stored as-is (no device→host copy on the preemption
        hot path) and the host copy is produced on demand by
        ``Committed.materialize()``."""
        with self._lock:
            self._seq += 1
            target = (self._active + 1) % 2
            if device:
                committed = Committed(self._seq, context, payload, tid=tid,
                                      device=True, region_rid=region_rid,
                                      owner=owner)
            else:
                # eager device -> host materialization (the BRAM -> CPU copy)
                host_ctx = jax.tree.map(lambda x: jax.device_get(x), context)
                host_payload = (jax.tree.map(lambda x: x, payload)
                                if payload is not None else None)
                committed = Committed(self._seq, host_ctx, host_payload,
                                      tid=tid)
            self._buffers[target] = committed
            if self.interrupt_next_commit:
                # simulate the asynchronous reset landing mid-save: the
                # active index is NOT flipped -> previous commit stays valid
                self.interrupt_next_commit = False
                return self._active
            self._active = target
            return self._active

    def restore(self) -> Optional[Committed]:
        with self._lock:
            if self._active < 0:
                return None
            return self._buffers[self._active]

    def reset(self):
        with self._lock:
            self._buffers = [None, None]
            self._active = -1
