"""Reconfigurable Region (paper §4.1-4.2).

Each region is treated as an independent accelerator: its own command queue
and manager thread (the Controller queue-per-device structure), its own
context bank (BRAM analogue), and a loaded executable ("bitstream").
Reconfiguration requests are internal tasks in the same queue, scheduled
before the associated kernel launch — exactly §4.2.

Preemption is cooperative-chunked (DESIGN.md §2.1): the worker checks the
preempt flag between chunks, saves the context+payload through the
double-buffered bank, and raises a TASK_PREEMPTED interrupt.

The region runs one of three engine modes (DESIGN.md §8/§10):

- ``sync`` — one chunk per dispatch, blocking ``done`` read per chunk:
  the bit-identity reference and the seed-equivalent baseline;
- ``pipelined`` — the worker issues chunk *k+1* while chunk *k*'s ``done``
  flag is still resolving on the device, polling the flag's independent
  snapshot without ever blocking dispatch.  The chunk executable is
  done-gated to identity, so the one speculative chunk issued beyond
  completion (or past a preemption point) computes nothing and results
  stay bit-identical to the synchronous path;
- ``megakernel`` — the whole chunk loop is folded into the compiled
  program (``jax.lax.while_loop``): a launch is ONE device dispatch
  regardless of budget, and preemption rides a host-writable flag buffer
  the device polls at every chunk boundary (``core/preemption.PreemptFlag``).

In every mode, context and payload buffers stay device-resident across
chunks (donated chunk-to-chunk) and across preempt/resume on the same
region; the host copy of a preemption commit is produced lazily, only
when a checkpoint, migration, or cross-region resume actually needs host
bytes — a flag-exited megakernel feeds the exact same commit machinery.
"""
from __future__ import annotations

import functools
import queue
import threading
import time
from collections import deque
from dataclasses import dataclass
from enum import Enum
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.controller.kernels import get_kernel
from repro.core.context import ContextBank, ContextRecord, Committed
from repro.core.interrupts import Event, EventKind, InterruptController
from repro.core.preemption import PreemptFlag
from repro.core.reconfig import ReconfigEngine, placement_device
from repro.core.task import Task, TaskStatus
from repro.obs.tracer import NO_SPAN

# host-side wait while a device flag snapshot resolves: bounded
# exponential backoff instead of a fixed-interval busy-poll — a long
# chunk no longer burns a host core, while the floor keeps short chunks
# prompt.  The device is busy computing during this wait (speculative
# chunk or in-flight megakernel), so the interval only bounds
# preempt/failure *response* latency, never throughput.
_POLL_MIN_S = 5e-6
_POLL_MAX_S = 1e-3

ENGINE_MODES = ("sync", "pipelined", "megakernel")


@functools.partial(jax.jit, static_argnums=0)
def _fresh_state(n_dummies: int):
    """A fresh launch's context (``ContextRecord.fresh()``) and its
    ``n_dummies`` unused buffer slots (``ArgBundle``'s ``(1, 1)`` float32
    dummies), made on the device by one small program, compiled once per
    count and device: one dispatch instead of an upload per leaf."""
    return ContextRecord.fresh(), tuple(
        jnp.zeros((1, 1), jnp.float32) for _ in range(n_dummies))


class RegionState(Enum):
    """Elastic-pool lifecycle (DESIGN.md §6.1).

    ACTIVE regions accept dispatches; a DRAINING region finishes (or is
    checkpoint-preempted off) its current work but receives nothing new; a
    RETIRED region's worker is shut down and its devices have been returned
    to the floorplanner.  ``repair()`` revives a failed region back to
    ACTIVE; RETIRED is terminal.
    """
    ACTIVE = "active"
    DRAINING = "draining"
    RETIRED = "retired"


@dataclass
class RegionStats:
    chunks: int = 0
    kernels_run: int = 0
    reconfigs: int = 0
    preemptions: int = 0
    chunk_ewma_s: float = 0.0
    busy_s: float = 0.0
    reconfig_s: float = 0.0  # wall time this region spent reconfiguring
    # chunk-pipeline accounting (DESIGN.md §8)
    chunks_pipelined: int = 0   # chunks issued while a predecessor resolved
    chunks_discarded: int = 0   # speculative identity chunks past done
    done_prefetches: int = 0    # done snapshots copied to the host at issue
    host_spills_avoided: int = 0  # device-resident resumes (no host copy)
    # megakernel accounting (DESIGN.md §10)
    megakernel_launches: int = 0  # single-dispatch launches
    flag_poll_exits: int = 0      # launches the device exited on the flag
    # launches that found another program loaded than the task's key and
    # loaded it first (a dispatch raced the region's queued reconfig)
    stale_launches: int = 0
    # Pallas dispatch accounting (DESIGN.md §13): which mode the last
    # Pallas-bearing bitstream resolved to ("interpret" | "compiled"),
    # None until one loads — benches read this so they never silently
    # measure the interpreter where a lowering exists
    pallas_mode: Optional[str] = None


class Region:
    def __init__(self, rid: int, engine: ReconfigEngine,
                 interrupts: InterruptController,
                 devices=None, geometry: Tuple[int, ...] = (1,),
                 chunk_budget: Optional[int] = None,
                 pipeline: bool = True,
                 engine_mode: Optional[str] = None,
                 tracer=None, metrics=None):
        self.rid = rid
        self.engine = engine
        self.interrupts = interrupts
        # flight recorder (obs/, DESIGN.md §11): None = tracing disabled,
        # and every emit site below is guarded to a single None check
        self.tracer = tracer
        # live metrics registry (obs/registry.py, DESIGN.md §12): same
        # None-guarded contract as the tracer
        self.metrics = metrics
        self._track = ("region", rid)
        self._t_preempt_req: Optional[float] = None
        self.devices = devices
        # the device this region's bitstreams are compiled for and its
        # buffers live on (None: JAX's default device)
        self.device = placement_device(devices)
        self.geometry = geometry
        self.chunk_budget = chunk_budget
        # execution engine mode: "sync" | "pipelined" | "megakernel"
        # (``pipeline`` is the pre-megakernel boolean, kept as the default
        # selector and as a readable attribute for existing callers)
        mode = engine_mode or ("pipelined" if pipeline else "sync")
        if mode not in ENGINE_MODES:
            raise ValueError(f"unknown engine mode {mode!r}; "
                             f"known: {ENGINE_MODES}")
        self.engine_mode = mode
        self.pipeline = mode == "pipelined"
        # the megakernel's host-writable preempt flag (one per region —
        # at most one launch is in flight on a region at a time)
        self.flag: Optional[PreemptFlag] = (
            PreemptFlag(self.device) if mode == "megakernel" else None)
        # device budget scalars by value: a launch re-resolves the budget
        # and re-uploads iff the value changed (the stale-budget fix —
        # the scalar is cached by VALUE, never by task or launch)
        self._budget_scalars: dict = {}
        self.bank = ContextBank()
        self.loaded: Optional[tuple] = None  # (kernel, sig) "bitstream id"
        self.executable = None
        self.stats = RegionStats()
        self.current_task: Optional[Task] = None
        self.state = RegionState.ACTIVE

        self._q: "queue.Queue[tuple]" = queue.Queue()
        self._inflight = 0  # commands enqueued but not fully processed
        # one lock serializes posting/draining commands and the inflight
        # count, so repair() can drain-and-reject atomically (no command
        # posted concurrently is ever half-counted or silently dropped)
        self._inflight_lock = threading.Lock()
        self._preempt = threading.Event()
        self._failed = threading.Event()
        self._stop = threading.Event()
        self.slowdown_s: float = 0.0  # straggler-injection test hook
        self._thread: Optional[threading.Thread] = None
        self.start()

    # ------------------------------------------------------------------
    def start(self):
        self._stop.clear()
        self._failed.clear()
        self._thread = threading.Thread(
            target=self._run, name=f"region-{self.rid}", daemon=True)
        self._thread.start()

    def shutdown(self):
        self._stop.set()
        self._q.put(("noop", None))  # wake the blocked worker
        if self._thread:
            self._thread.join(timeout=5)

    # -- commands (the per-region Controller queue) ---------------------
    def _post(self, cmd: str, task):
        """Atomically count and enqueue a command: a command is 'in flight'
        from the moment it is posted until the worker fully processed it,
        and ``repair()`` (same lock) can never observe the count and the
        queue out of sync."""
        with self._inflight_lock:
            self._inflight += 1
            self._q.put((cmd, task))

    def _dec(self):
        with self._inflight_lock:
            self._inflight -= 1

    def enqueue_reconfig(self, task: Task):
        self._post("reconfig", task)

    def enqueue_launch(self, task: Task):
        self._post("launch", task)

    def request_preempt(self):
        tr = self.tracer
        if tr is not None:
            cur = self.current_task
            tr.emit("preempt_request", self._track,
                    tid=cur.tid if cur is not None else None)
        m = self.metrics
        if m is not None:
            m.counter("preempt_requests_total", region=self.rid).inc()
        if self._t_preempt_req is None:
            # first unhonored request wins: response latency is measured
            # from what a waiting scheduler actually experiences
            self._t_preempt_req = time.perf_counter()
        self._preempt.set()
        if self.flag is not None:
            # zero-copy device put: the in-flight megakernel observes the
            # store at its next chunk boundary and exits there
            self.flag.write(1)

    def cancel_preempt(self):
        self._preempt.clear()
        self._t_preempt_req = None
        if self.flag is not None:
            self.flag.clear()

    def inject_failure(self):
        """Kill this region (node failure simulation)."""
        self._failed.set()
        if self.flag is not None:
            # pop an in-flight megakernel promptly so the worker's wait
            # resolves and the failure interrupt is raised within a chunk
            self.flag.write(1)

    def begin_drain(self):
        """Elastic shrink step 1: stop accepting dispatches.  The caller
        (``RegionPool``) preempts the current task and retires the region
        once it is idle."""
        if self.state is RegionState.ACTIVE:
            self.state = RegionState.DRAINING

    def retire(self):
        """Elastic shrink step 2 (terminal): shut the worker down."""
        self.state = RegionState.RETIRED
        self.shutdown()

    def repair(self) -> list:
        """Bring the region back (elastic grow).  Its bank survives.

        Returns the tasks of any ``launch`` commands that were still queued
        when the dead worker was restarted: they were dispatched but never
        ran, so the caller must requeue them (the scheduler's auto-repair
        does).  The drain happens under the command lock, so a command
        posted concurrently is either drained-and-returned or preserved
        with a consistent inflight count — never silently lost in between.
        """
        if self.state is RegionState.RETIRED:
            raise RuntimeError(
                f"region {self.rid} is retired; add a new region instead")
        # a DRAINING region stays draining: repair revives the worker so the
        # pool can finish retiring it, but must NOT make it dispatchable
        revived_state = (self.state if self.state is RegionState.DRAINING
                         else RegionState.ACTIVE)
        if self._thread and self._thread.is_alive():
            # failure injected while the worker idled: the thread never hit
            # _check_failure and is still running — just lift the flag
            self._failed.clear()
            self.state = revived_state
            return []
        self.state = revived_state
        self.loaded = None
        self.executable = None
        self.current_task = None
        dropped = []
        with self._inflight_lock:
            while True:
                try:
                    dropped.append(self._q.get_nowait())
                except queue.Empty:
                    break
            self._inflight = 0
        self.start()
        return [t for (cmd, t) in dropped
                if cmd == "launch" and t is not None]

    @property
    def idle(self) -> bool:
        # race-free: a command is 'in flight' from enqueue until the worker
        # fully processed it (the scheduler's exit check must never observe
        # a task in the dequeue->launch window as idle)
        with self._inflight_lock:
            return self._inflight == 0

    @property
    def alive(self) -> bool:
        return (self._thread is not None and self._thread.is_alive()
                and not self._failed.is_set())

    @property
    def dispatchable(self) -> bool:
        """Eligible for new work: alive and not draining/retired."""
        return self.alive and self.state is RegionState.ACTIVE

    # ------------------------------------------------------------------
    def _run(self):
        while not self._stop.is_set():
            # event-driven: block until a command (or wakeup sentinel)
            # arrives — no timeout polling.  Preempt requests interrupt a
            # *running* task via the flag checks inside _do_launch; an idle
            # worker has nothing to preempt.
            cmd, task = self._q.get()
            if cmd == "noop":
                continue
            try:
                try:
                    if cmd == "reconfig":
                        self._do_reconfig(task)
                    elif cmd == "launch":
                        self._do_launch(task)
                finally:
                    self._dec()
            except RegionFailure:
                if self.tracer is not None:
                    self.tracer.emit("region_failed", self._track,
                                     tid=task.tid if task else None)
                self.interrupts.raise_interrupt(Event(
                    EventKind.REGION_FAILED, self.rid, task=task))
                return  # thread dies; scheduler handles re-enqueue
            except Exception as e:  # pragma: no cover - defensive
                import traceback

                traceback.print_exc()
                task.status = TaskStatus.FAILED
                self.current_task = None
                self.interrupts.raise_interrupt(Event(
                    EventKind.REGION_FAILED, self.rid, task=task, payload=e))
                return

    def _check_failure(self):
        if self._failed.is_set():
            raise RegionFailure()

    @property
    def program(self) -> str:
        """Which compiled entry point this region's mode needs."""
        return "mega" if self.engine_mode == "megakernel" else "chunk"

    def _key(self, task: Task) -> tuple:
        """The executable a task needs here: its bitstream id."""
        return (task.kernel, task.args.signature(), self.geometry)

    def _do_reconfig(self, task: Task):
        self._check_failure()
        key = self._key(task)
        if self.loaded == key:
            return
        task.status = TaskStatus.RECONFIGURING
        tr = self.tracer
        with (tr.span("reconfig", self._track, tid=task.tid,
                      kernel=task.kernel) if tr is not None else NO_SPAN):
            fn, dt = self.engine.load(task.kernel, task.args, self.geometry,
                                      self.devices, program=self.program)
        self.loaded = key
        self.executable = fn
        self.stats.reconfigs += 1
        self.stats.reconfig_s += dt
        if get_kernel(task.kernel).pallas:
            from repro.kernels.pallas_support import pallas_mode
            self.stats.pallas_mode = pallas_mode()
        task.n_reconfigs += 1
        m = self.metrics
        if m is not None:
            m.histogram("region_reconfig_seconds",
                        region=self.rid).observe(dt)
            m.counter("reconfigs_total", region=self.rid).inc()
        self.interrupts.raise_interrupt(Event(
            EventKind.RECONFIG_DONE, self.rid, task=task, payload=dt))

    # -- launch argument preparation ------------------------------------
    def _prepare(self, task: Task):
        """A launch's ``(ctx, bufs, ints, floats)`` on this region's
        device, the path taken and the host-to-device calls made:

        - ``fresh`` (two calls): the context and the dummy buffer slots
          come from one small compiled program (``_fresh_state``), the
          bundle's real buffers (``host()``, memoized per bundle, so a
          requeued task never re-pads) from one ``jax.device_put``;
        - ``resume_local`` (resume on the *same* region, one call): the
          committed context/payload never left device memory and is
          copied there;
        - ``resume_host`` (migration, failover, elastic rebalance; one
          call): the committed host copy is materialized on demand and
          uploaded — the only place the spill actually happens.

        The chunk executable donates ``ctx`` and ``bufs``, so every leaf
        comes out a buffer of its own (``may_alias=False``): host leaves
        upload; a device array is copied, never aliased — on this device
        device-side (the bank keeps its committed copy for a
        REGION_FAILED recovery, a bundle its buffers for a re-dispatch;
        serving threads its KV state in as device arrays), from another
        device device to device.  The int/float vectors join the
        ``device_put`` of a bundle's first launch on a device and are
        memoized on the bundle."""
        dev = self.device
        args = task.args
        host_bufs, ints, floats = args.host()
        scalars = args.scalars(dev)
        put = (ints, floats) if scalars is None else None
        saved: Optional[Committed] = task.saved_context
        if saved is None:
            path, calls, n = "fresh", 2, args.n_dummies
            with jax.default_device(dev):
                ctx, dummies = _fresh_state(n)
            real, put = jax.device_put(
                (host_bufs[:len(host_bufs) - n], put), dev, may_alias=False)
            bufs = real + dummies
        else:
            task.saved_context = None
            if saved.device and saved.owner is self:
                self.stats.host_spills_avoided += 1
                path = "resume_local"
            else:
                path, saved = "resume_host", saved.materialize()
            payload = host_bufs if saved.payload is None else saved.payload
            ctx, bufs, put = jax.device_put(
                (saved.context, tuple(payload), put), dev, may_alias=False)
            calls = 1
        if scalars is None:
            scalars = args.keep_scalars(dev, put)
        return (ctx, bufs) + scalars, path, calls

    # -- launch plumbing shared by every engine mode --------------------
    def _budget_scalar(self, value: int):
        """The non-donated device scalar for this launch's chunk budget,
        cached BY VALUE: a task requeued with a different budget (e.g. a
        ``task.chunk_budget`` override set after a preemption) always
        resolves to a freshly uploaded scalar — the stale-budget fix —
        while an unchanged value reuses the cached upload."""
        arr = self._budget_scalars.get(value)
        if arr is None:
            arr = self._budget_scalars[value] = jax.device_put(
                np.int32(value), self.device)
        return arr

    def _wait_ready(self, snapshot, abort_on_preempt: bool) -> bool:
        """Wait for a device flag snapshot with bounded exponential
        backoff (``_POLL_MIN_S`` doubling to ``_POLL_MAX_S``): long chunks
        no longer spin a host core at a fixed interval, short ones still
        resolve promptly.  Returns early when the region fails — or, if
        ``abort_on_preempt``, when a preempt request needs the host loop's
        attention (the pipelined engine handles it between chunks; the
        megakernel's preemption is device-side, so it keeps waiting).
        Returns whether the first poll already found the snapshot
        resolved."""
        if snapshot.is_ready():
            return True
        delay = _POLL_MIN_S
        while True:
            if self._failed.is_set():
                return False
            if abort_on_preempt and self._preempt.is_set():
                return False
            time.sleep(delay)
            delay = min(delay * 2.0, _POLL_MAX_S)
            if snapshot.is_ready():
                return False

    def _commit_preempt(self, task: Task, ctx, bufs, t_busy0: float):
        """Preemption tail, identical for every engine mode: lazy-spill
        commit of the device-resident context + partial outputs, then the
        TASK_PREEMPTED interrupt.  The committed host bytes are produced
        on demand by whoever actually needs them."""
        tr = self.tracer
        with (tr.span("commit", self._track, tid=task.tid)
              if tr is not None else NO_SPAN):
            self.bank.commit(ctx, payload=bufs, tid=task.tid, device=True,
                             region_rid=self.rid, owner=self)
            task.saved_context = self.bank.restore()
        task.status = TaskStatus.PREEMPTED
        task.n_preemptions += 1
        self.stats.preemptions += 1
        self.current_task = None
        now = time.perf_counter()
        self.stats.busy_s += now - t_busy0
        if tr is not None:
            tr.emit_span("run", self._track, t_busy0, tid=task.tid)
            tr.emit("preempt_honored", self._track, tid=task.tid)
        m = self.metrics
        if m is not None:
            m.counter("region_run_seconds_total", region=self.rid).inc(
                now - t_busy0)
            m.counter("preemptions_total", region=self.rid).inc()
            t_req = self._t_preempt_req
            if t_req is not None:
                m.histogram("preempt_response_seconds",
                            region=self.rid).observe(
                    max(now - t_req, 0.0), t=now)
        self._t_preempt_req = None
        self.interrupts.raise_interrupt(Event(
            EventKind.TASK_PREEMPTED, self.rid, task=task))

    def _finish_done(self, task: Task, kd, bufs, t_busy0: float):
        """Completion tail, identical for every engine mode."""
        task.result_devices = frozenset(d.id for b in bufs
                                        for d in b.devices())
        task.t_done = time.perf_counter()
        tr = self.tracer
        if kd.device_result:
            # serving kernels: hand the final device buffers back as-is —
            # the engine streams the token buffer host-side but threads the
            # KV state into the next round without a host round trip
            task.result = tuple(bufs)
        else:
            with (tr.span("readback", self._track, tid=task.tid)
                  if tr is not None else NO_SPAN):
                task.result = tuple(np.asarray(jax.device_get(b))
                                    for b in bufs[:2])
        # DONE only once the result is there: a caller polling the status
        # (or the scheduler settling handles at exit) reads it at once
        task.status = TaskStatus.DONE
        self.stats.kernels_run += 1
        self.current_task = None
        now = time.perf_counter()
        self.stats.busy_s += now - t_busy0
        if tr is not None:
            tr.emit_span("run", self._track, t_busy0, tid=task.tid)
            tr.emit("done", self._track, tid=task.tid)
        m = self.metrics
        if m is not None:
            m.counter("region_run_seconds_total", region=self.rid).inc(
                now - t_busy0)
            m.counter("kernels_run_total", region=self.rid).inc()
        self.interrupts.raise_interrupt(Event(
            EventKind.TASK_DONE, self.rid, task=task))

    # -- the chunk-pipelined execution hot path -------------------------
    def _do_launch(self, task: Task):
        self._check_failure()
        tr = self.tracer
        if self.loaded != self._key(task):
            # the launch was posted against another load than the one it
            # needs (a dispatch raced the region's queued commands): load
            # the task's program first instead of calling the wrong one
            self.stats.stale_launches += 1
            if tr is not None:
                tr.emit("stale_launch", self._track, tid=task.tid,
                        kernel=task.kernel)
            self._do_reconfig(task)
        kd = get_kernel(task.kernel)
        budget = task.chunk_budget or self.chunk_budget or kd.default_budget
        with (tr.span("prepare", self._track, tid=task.tid)
              if tr is not None else NO_SPAN) as sp:
            (ctx, bufs, ints, floats), path, calls = self._prepare(task)
            if sp is not None:
                sp.attrs.update(path=path, calls=calls)

        task.status = TaskStatus.RUNNING
        task.region_history.append(self.rid)
        if task.t_first_served is None:
            task.t_first_served = time.perf_counter()
        self.current_task = task
        t_busy0 = time.perf_counter()
        budget_arr = self._budget_scalar(budget)
        if self.engine_mode == "megakernel":
            return self._launch_megakernel(task, kd, budget_arr, ints,
                                           floats, ctx, bufs, t_busy0)
        depth = 1 if self.pipeline else 0
        pending: "deque" = deque()  # done snapshots of unretired chunks
        t_last = time.perf_counter()
        # deterministic preemption point: issue at most ``arm`` chunks this
        # launch, then commit there unless the task finished (one-shot)
        arm = task.preempt_at_boundary or None  # 0 = no stop, as the flag
        task.preempt_at_boundary = None
        issued = 0

        def issue():
            nonlocal ctx, bufs, issued
            if pending:  # overlapped with an unresolved predecessor
                self.stats.chunks_pipelined += 1
            with (tr.span("issue", self._track, tid=task.tid)
                  if tr is not None else NO_SPAN):
                ctx, bufs, done = self.executable(ctx, bufs, ints, floats,
                                                  budget_arr)
            if depth:
                # queue the flag's copy to the host behind the chunk: the
                # read at this chunk's boundary, one issue later, then
                # finds the value there instead of starting the device-to-
                # host round trip itself.  Sync reads at once: nothing to
                # overlap, so it keeps the plain blocking read.
                done.copy_to_host_async()
                self.stats.done_prefetches += 1
            pending.append(done)
            issued += 1

        def retire(done: int):
            """Account one resolved chunk boundary (EWMA, per-task work)."""
            nonlocal t_last
            dt = time.perf_counter() - t_last
            if self.slowdown_s:
                time.sleep(self.slowdown_s)
                dt += self.slowdown_s
            t_last = time.perf_counter()
            a = 0.3
            self.stats.chunk_ewma_s = (
                dt if self.stats.chunks == 0
                else a * dt + (1 - a) * self.stats.chunk_ewma_s)
            self.stats.chunks += 1
            task.run_s += dt  # per-task (and per-tenant) work attribution
            return done

        def drain() -> int:
            """Resolve every in-flight chunk (blocking): real chunks are
            retired, speculative identity chunks past ``done`` are
            discarded.  Returns whether the task actually finished."""
            done = 0
            while pending:
                with (tr.span("wait", self._track, tid=task.tid,
                              prefetched=bool(depth),
                              ready=pending[0].is_ready())
                      if tr is not None else NO_SPAN):
                    v = int(pending.popleft())
                if done:
                    self.stats.chunks_discarded += 1
                else:
                    retire(v)
                    done = v
            return done

        while True:
            self._check_failure()
            armed_stop = arm is not None and issued >= arm and not pending
            if self._preempt.is_set() or armed_stop:
                self._preempt.clear()
                if drain():  # completion raced the preempt: task is done
                    break
                self._commit_preempt(task, ctx, bufs, t_busy0)
                return

            # keep the pipeline primed: the speculative chunk k+1 is issued
            # before chunk k's done flag is read, so the device never idles
            # across a chunk boundary waiting on the host
            while len(pending) < depth + 1 and (arm is None or issued < arm):
                issue()

            # wait for the oldest chunk to resolve.  Pipelined: poll its
            # snapshot so a preempt/failure request stays prompt during
            # long chunks — the device is meanwhile busy with the
            # speculative chunk, so this wait never blocks dispatch.
            # Synchronous (depth 0): block on the flag directly, exactly
            # the seed's per-chunk host round trip.
            with (tr.span("wait", self._track, tid=task.tid,
                          prefetched=bool(depth))
                  if tr is not None else NO_SPAN) as sp:
                if depth:
                    ready = self._wait_ready(pending[0],
                                             abort_on_preempt=True)
                    if sp is not None:
                        sp.attrs["ready"] = ready
                    if self._preempt.is_set() or self._failed.is_set():
                        continue  # handled at the loop top
                elif sp is not None:
                    sp.attrs["ready"] = pending[0].is_ready()
                done = int(pending.popleft())
            if retire(done):
                # remaining in-flight chunks were done-gated to identity:
                # current ctx/bufs are bit-identical to the final state
                self.stats.chunks_discarded += len(pending)
                pending.clear()
                break

        self._finish_done(task, kd, bufs, t_busy0)

    # -- the megakernel execution hot path (DESIGN.md §10) ---------------
    def _launch_megakernel(self, task: Task, kd, budget_arr, ints, floats,
                           ctx, bufs, t_busy0: float):
        """ONE device dispatch runs every remaining chunk: the compiled
        ``while_loop`` re-reads the region's preempt flag at each chunk
        boundary and exits there when it fires.  ``done == 0`` on return
        is exactly "the flag fired mid-task" — the partial context feeds
        the same commit path a host-driven preemption uses, bit-identically
        to the sync/pipelined engines stopping at the same boundary."""
        flag = self.flag
        if self._preempt.is_set():
            # parity with the pipelined loop-top check: a preempt request
            # that lands before dispatch commits the prepared state as-is
            # (zero chunks ran; resume restarts from the same boundary)
            self._preempt.clear()
            flag.clear()
            self._commit_preempt(task, ctx, bufs, t_busy0)
            return
        arm = task.preempt_at_boundary
        if arm is not None:
            task.preempt_at_boundary = None  # one-shot: consumed at launch
            flag.write(int(arm))
        else:
            # a stale flag value must not preempt this launch; re-assert
            # after clearing in case request_preempt raced the clear (its
            # event store precedes its flag store, so the recheck sees it)
            flag.clear()
            if self._preempt.is_set():
                flag.write(1)
        t0 = time.perf_counter()
        ctx, bufs, done, n_chunks = self.executable(
            ctx, bufs, ints, floats, budget_arr, flag.device)
        self.stats.megakernel_launches += 1
        # the whole loop is in flight on-device; the host only waits for
        # the independent done snapshot.  A failure injected mid-flight
        # pops the device loop via the flag so this wait stays bounded by
        # one chunk, then surfaces through _check_failure below.
        delay = _POLL_MIN_S
        while not done.is_ready():
            if self._failed.is_set() and flag.read() == 0:
                flag.write(1)
            time.sleep(delay)
            delay = min(delay * 2.0, _POLL_MAX_S)
        self._check_failure()
        k = int(n_chunks)
        dt = time.perf_counter() - t0
        if k:
            per = dt / k
            a = 0.3
            self.stats.chunk_ewma_s = (
                per if self.stats.chunks == 0
                else a * per + (1 - a) * self.stats.chunk_ewma_s)
        self.stats.chunks += k
        task.run_s += dt
        tr = self.tracer
        if tr is not None:
            tr.emit("mega_launch", self._track, tid=task.tid,
                    t=t0, dur=dt, n_chunks=k, done=int(done))
        if not int(done):
            # the device exited on the flag at a chunk boundary
            self.stats.flag_poll_exits += 1
            self._preempt.clear()
            flag.clear()
            self._commit_preempt(task, ctx, bufs, t_busy0)
            return
        flag.clear()
        self._finish_done(task, kd, bufs, t_busy0)


class RegionFailure(Exception):
    pass
