"""Chunk-pipelined region engine (DESIGN.md §8): bit-identity of the
pipelined hot path against the synchronous reference under forced
preemption at every chunk boundary, lazy device-resident spill (including
a cross-shell migration consuming it), same-bitstream coalescing semantics
on all three policies, the repair queue-drain fix, and the event-driven
Controller wait."""
import os
import threading
import time

import numpy as np
import pytest

try:  # property tests degrade to deterministic variants without the dep
    from hypothesis import HealthCheck, given, settings
    from hypothesis import strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover - exercised on minimal containers
    HAVE_HYPOTHESIS = False

from repro.controller.kernels import get_kernel
from repro.core.interrupts import EventKind
from repro.core.policy import (EarliestDeadlineFirst, FcfsPriority,
                               WeightedFairShare)
from repro.core.scheduler import Scheduler, SchedulerConfig
from repro.core.shell import Shell
from repro.core.task import Task, TaskStatus
from repro.kernels.blur.tasks import make_image
from repro.obs import Tracer

SIZE = 30


def _blur_task(rng, iters=2, kernel="MedianBlur", img=None, priority=2,
               deadline_s=None, tenant="default", size=SIZE):
    if img is None:
        img = make_image(rng, size)
    kd = get_kernel(kernel)
    t = Task(kernel=kernel,
             args=kd.bundle(img, np.zeros_like(img), H=size, W=size,
                            iters=iters),
             priority=priority, deadline_s=deadline_s, tenant=tenant)
    return t, img


def _drive(shell, task, preempt_at=None, resume_region=None,
           timeout=60.0):
    """Drive one task on a shell's regions directly (no scheduler):
    launch on region 0, optionally force one preemption once the global
    chunk count reaches ``preempt_at``, resuming on ``resume_region``
    (defaults to region 0).  Returns the task's preemption count."""
    regions = shell.regions
    target = regions[0]
    target.enqueue_reconfig(task)
    target.enqueue_launch(task)
    armed = preempt_at is not None
    preemptions = 0
    total = lambda: sum(r.stats.chunks for r in regions)
    deadline = time.perf_counter() + timeout
    while True:
        assert time.perf_counter() < deadline, f"stuck: {task}"
        ev = shell.interrupts.wait(0.0005)
        if ev is not None and ev.kind is EventKind.TASK_DONE:
            break
        if ev is not None and ev.kind is EventKind.TASK_PREEMPTED:
            preemptions += 1
            target.cancel_preempt()
            target = resume_region if resume_region is not None else target
            target.enqueue_reconfig(task)
            target.enqueue_launch(task)
            continue
        if armed and total() >= preempt_at:
            armed = False
            target.request_preempt()
    for r in regions:  # a preempt that raced completion must not leak
        r.cancel_preempt()
    return preemptions


def _reference(img, iters, kernel="MedianBlur"):
    """Synchronous (pipeline=False), uninterrupted run — the bit-identity
    reference."""
    shell = Shell(n_regions=1, chunk_budget=2, pipeline=False,
                  prefetch=False)
    try:
        t, _ = _blur_task(np.random.default_rng(0), iters=iters,
                          kernel=kernel, img=img)
        _drive(shell, t)
        n_chunks = shell.regions[0].stats.chunks
        return tuple(np.asarray(b) for b in t.result), n_chunks
    finally:
        shell.shutdown()


# ------------------------------------------------------------ bit identity
def test_pipelined_matches_sync_bit_identical():
    rng = np.random.default_rng(7)
    img = make_image(rng, SIZE)
    ref, _ = _reference(img, iters=2)
    shell = Shell(n_regions=1, chunk_budget=2, prefetch=False)
    try:
        t, _ = _blur_task(rng, iters=2, img=img)
        _drive(shell, t)
        assert all(np.array_equal(a, b) for a, b in zip(t.result, ref))
        # the pipeline actually overlapped chunks and discarded exactly the
        # one speculative chunk issued past completion
        assert shell.regions[0].stats.chunks_pipelined > 0
        assert shell.regions[0].stats.chunks_discarded >= 1
    finally:
        shell.shutdown()


def test_preempt_at_every_chunk_boundary_bit_identical():
    """Forcing a preemption at each chunk boundary k (resume on the same
    region, device-resident context) never changes the final output."""
    rng = np.random.default_rng(8)
    img = make_image(rng, SIZE)
    ref, n_chunks = _reference(img, iters=2)
    assert n_chunks >= 3
    for k in range(n_chunks):
        shell = Shell(n_regions=1, chunk_budget=2, prefetch=False)
        shell.regions[0].slowdown_s = 0.02  # make boundaries land reliably
        try:
            t, _ = _blur_task(rng, iters=2, img=img)
            _drive(shell, t, preempt_at=k)
            assert t.status is TaskStatus.DONE
            assert all(np.array_equal(a, b)
                       for a, b in zip(t.result, ref)), f"boundary {k}"
        finally:
            shell.shutdown()


@pytest.mark.parametrize("engine", ["sync", "pipelined"])
@pytest.mark.parametrize("boundary", [1, 2])
def test_armed_boundary_stops_host_engines_exactly(engine, boundary):
    """``Task.preempt_at_boundary`` stops the next launch at exactly that
    chunk boundary — no sleep, no race — and the resume is bit-identical."""
    rng = np.random.default_rng(9)
    img = make_image(rng, SIZE)
    ref, n_chunks = _reference(img, iters=2)
    assert n_chunks > boundary
    shell = Shell(n_regions=1, chunk_budget=2, engine=engine,
                  prefetch=False)
    try:
        t, _ = _blur_task(rng, iters=2, img=img)
        t.preempt_at_boundary = boundary
        region = shell.regions[0]
        region.enqueue_reconfig(t)
        region.enqueue_launch(t)
        ev = shell.interrupts.wait(60.0)
        while ev.kind is EventKind.RECONFIG_DONE:
            ev = shell.interrupts.wait(60.0)
        assert ev.kind is EventKind.TASK_PREEMPTED
        assert region.stats.chunks == boundary   # nothing ran past it
        assert t.preempt_at_boundary is None     # one-shot
        region.enqueue_launch(t)
        ev = shell.interrupts.wait(60.0)
        assert ev.kind is EventKind.TASK_DONE
        assert t.n_preemptions == 1
        assert all(np.array_equal(a, b) for a, b in zip(t.result, ref))
    finally:
        shell.shutdown()


if HAVE_HYPOTHESIS:
    @settings(max_examples=6, deadline=None,
              suppress_health_check=list(HealthCheck))
    @given(budget=st.integers(1, 4), iters=st.integers(1, 3),
           kernel=st.sampled_from(["MedianBlur", "GaussianBlur"]),
           preempt_at=st.integers(0, 8), seed=st.integers(0, 2**16))
    def test_property_pipelined_preemption_equivalence(
            budget, iters, kernel, preempt_at, seed):
        """PROPERTY: pipelined execution with a forced preemption at an
        arbitrary boundary is bit-identical to the synchronous
        uninterrupted run."""
        _check_pipelined_equivalence(budget, iters, kernel, preempt_at,
                                     seed)
else:  # deterministic fallback grid
    @pytest.mark.parametrize("budget,iters,kernel,preempt_at,seed", [
        (1, 2, "MedianBlur", 3, 0),
        (2, 1, "GaussianBlur", 1, 1),
        (3, 3, "MedianBlur", 0, 2),
        (4, 2, "GaussianBlur", 6, 3),
    ])
    def test_property_pipelined_preemption_equivalence(
            budget, iters, kernel, preempt_at, seed):
        _check_pipelined_equivalence(budget, iters, kernel, preempt_at,
                                     seed)


def _check_pipelined_equivalence(budget, iters, kernel, preempt_at, seed):
    rng = np.random.default_rng(seed)
    img = make_image(rng, SIZE)
    sync = Shell(n_regions=1, chunk_budget=budget, pipeline=False,
                 prefetch=False)
    try:
        t_ref, _ = _blur_task(rng, iters=iters, kernel=kernel, img=img)
        _drive(sync, t_ref)
        ref = tuple(np.asarray(b) for b in t_ref.result)
    finally:
        sync.shutdown()
    pipe = Shell(n_regions=1, chunk_budget=budget, prefetch=False)
    pipe.regions[0].slowdown_s = 0.01
    try:
        t, _ = _blur_task(rng, iters=iters, kernel=kernel, img=img)
        _drive(pipe, t, preempt_at=preempt_at)
        assert all(np.array_equal(a, b) for a, b in zip(t.result, ref))
    finally:
        pipe.shutdown()


# ------------------------------------------------- done snapshot prefetch
def _run_engine(engine, kernel, iters, img, size, budget, tracer=None):
    """One uninterrupted task on a fresh one-region shell of ``engine``:
    its host results and the region's stats."""
    shell = Shell(n_regions=1, chunk_budget=budget, engine=engine,
                  prefetch=False, tracer=tracer)
    try:
        t, _ = _blur_task(np.random.default_rng(0), iters=iters,
                          kernel=kernel, img=img, size=size)
        _drive(shell, t)
        return tuple(np.asarray(b) for b in t.result), shell.regions[0].stats
    finally:
        shell.shutdown()


def _wait_spans(tracer):
    return [e for e in tracer.events() if e.kind == "wait"]


@pytest.mark.parametrize("n_chunks", [1, 2, 4])
@pytest.mark.parametrize("kernel,iters", [
    ("MedianBlur", 1), ("MedianBlur", 2), ("MedianBlur", 3),
    ("GaussianBlur", 1)])
def test_prefetched_done_reads_bit_identical_to_sync(kernel, iters,
                                                     n_chunks):
    """Pipelined chunks start their ``done`` snapshot's host copy at issue;
    the results stay bit-identical to ``sync``, which starts none.  A pass
    costs one budget unit a 32-row block plus one of its own, so a padded
    side of ``128 * n_chunks`` at a budget of ``5 * iters`` needs
    ``n_chunks`` chunks (asserted on both engines)."""
    side = 128 * n_chunks
    img = make_image(np.random.default_rng(n_chunks), side - 10)
    ref, sync = _run_engine("sync", kernel, iters, img, side, 5 * iters)
    out, pipe = _run_engine("pipelined", kernel, iters, img, side,
                            5 * iters)
    assert sync.chunks == pipe.chunks == n_chunks
    assert all(np.array_equal(a, b) for a, b in zip(out, ref))
    assert sync.done_prefetches == 0
    # every chunk issued, the speculative one past completion too
    assert pipe.chunks_discarded == 1
    assert pipe.done_prefetches == pipe.chunks + pipe.chunks_discarded


@pytest.mark.parametrize("engine", ["sync", "pipelined", "megakernel"])
def test_done_prefetches_count_pipelined_issues_only(engine):
    """``RegionStats.done_prefetches`` equals the chunk executable calls
    (the ``issue`` spans) in ``pipelined`` and stays 0 elsewhere; every
    ``wait`` span says whether its snapshot was prefetched and whether
    its first poll found it resolved."""
    img = make_image(np.random.default_rng(22), SIZE)
    tr = Tracer()
    _, stats = _run_engine(engine, "MedianBlur", 2, img, SIZE, 2,
                           tracer=tr)
    issues = [e for e in tr.events() if e.kind == "issue"]
    waits = _wait_spans(tr)
    if engine == "pipelined":
        assert stats.done_prefetches == len(issues) > 1
    else:
        assert stats.done_prefetches == 0
    assert len(waits) == (0 if engine == "megakernel" else stats.chunks)
    for e in waits:
        assert e.attrs["prefetched"] is (engine == "pipelined")
        assert isinstance(e.attrs["ready"], bool)


@pytest.mark.parametrize("how", ["armed", "requested"])
def test_preempted_pipeline_reads_prefetched_snapshots(how):
    """A pipelined task preempted at a chunk boundary, set
    (``preempt_at_boundary``) or requested while chunks are in flight,
    commits there and resumes to the synchronous answer; every snapshot
    it read, ``drain()``'s on the preemption path among them, had its
    host copy started at issue."""
    rng = np.random.default_rng(23)
    img = make_image(rng, SIZE)
    ref, n_chunks = _reference(img, iters=3)
    tr = Tracer()
    shell = Shell(n_regions=1, chunk_budget=2, prefetch=False, tracer=tr)
    region = shell.regions[0]
    try:
        t, _ = _blur_task(rng, iters=3, img=img)
        if how == "armed":
            t.preempt_at_boundary = 2
            assert _drive(shell, t) == 1
            assert region.stats.chunks == n_chunks  # none ran twice
        else:
            region.slowdown_s = 0.02  # the request lands mid-pipeline
            assert _drive(shell, t, preempt_at=1) >= 1
        stats = region.stats
    finally:
        shell.shutdown()
    assert t.status is TaskStatus.DONE and t.n_preemptions >= 1
    assert all(np.array_equal(a, b) for a, b in zip(t.result, ref))
    assert stats.done_prefetches == stats.chunks + stats.chunks_discarded
    waits = _wait_spans(tr)
    assert len(waits) >= stats.chunks
    assert all(e.attrs["prefetched"] is True
               and isinstance(e.attrs["ready"], bool) for e in waits)


# ------------------------------------------------------------- lazy spill
def test_same_region_resume_is_device_resident():
    """A preempt+resume cycle on one region must avoid the host round trip
    entirely: the commit stays device-resident and the resume consumes it
    in place."""
    rng = np.random.default_rng(9)
    img = make_image(rng, SIZE)
    ref, _ = _reference(img, iters=3)
    shell = Shell(n_regions=1, chunk_budget=1, prefetch=False)
    region = shell.regions[0]
    region.slowdown_s = 0.02
    try:
        t, _ = _blur_task(rng, iters=3, img=img)
        pre = _drive(shell, t, preempt_at=2)
        assert pre >= 1
        assert region.stats.host_spills_avoided >= 1
        committed = region.bank.restore()
        assert committed is not None and committed.device
        assert committed.owner is region and committed.tid == t.tid
        assert all(np.array_equal(a, b) for a, b in zip(t.result, ref))
        # the committed host copy is produced on demand and cached
        host = committed.materialize()
        assert not host.device and host.tid == t.tid
        assert committed.materialize() is host
    finally:
        shell.shutdown()


def test_cross_region_resume_materializes_host_copy():
    """Resuming on a different region is the actual spill: the lazy commit
    materializes through the host, and the result stays bit-identical."""
    rng = np.random.default_rng(10)
    img = make_image(rng, SIZE)
    ref, _ = _reference(img, iters=3)
    shell = Shell(n_regions=2, chunk_budget=1, prefetch=False)
    for r in shell.regions:
        r.slowdown_s = 0.02
    try:
        t, _ = _blur_task(rng, iters=3, img=img)
        pre = _drive(shell, t, preempt_at=2,
                     resume_region=shell.regions[1])
        assert pre >= 1
        assert shell.regions[1].stats.host_spills_avoided == 0
        assert all(np.array_equal(a, b) for a, b in zip(t.result, ref))
    finally:
        shell.shutdown()


def test_cross_shell_migration_consumes_lazy_spill():
    """Checkpoint-migrating a *running* task to another shell consumes the
    device-resident commit through the checksummed disk spill and resumes
    bit-identically to an uninterrupted single-shell run."""
    from repro.cluster import ClusterFrontend

    rng = np.random.default_rng(11)
    img = make_image(rng, SIZE)
    ref, _ = _reference(img, iters=3)
    fe = ClusterFrontend(n_shells=2, regions_per_shell=1, chunk_budget=1,
                         rebalance=False)
    for node in fe.nodes:
        node.shell.region_slowdown_s = 0.02
        for r in node.shell.regions:
            r.slowdown_s = 0.02
    try:
        t, _ = _blur_task(rng, iters=3, img=img)
        h = fe.submit(t)
        deadline = time.perf_counter() + 20.0
        while (t.status is not TaskStatus.RUNNING
               and time.perf_counter() < deadline):
            time.sleep(0.002)  # only a RUNNING task checkpoint-migrates
        migrated = False
        while time.perf_counter() < deadline and not migrated:
            if t.status is TaskStatus.RUNNING and fe.migrate(tid=t.tid):
                migrated = True
                break
            time.sleep(0.004)
        assert migrated, "forced migration never completed"
        # the lazy commit was spilled through the on-disk checkpoint
        spills = [f for f in os.listdir(fe.spill_dir)
                  if f.startswith(f"task{t.tid}.") and f.endswith(".npz")]
        assert spills, os.listdir(fe.spill_dir)
        out = h.result(timeout=60.0)
        assert h.n_migrations == 1
        assert all(np.array_equal(a, b) for a, b in zip(out, ref))
    finally:
        rep = fe.shutdown()
    assert rep["stranded_handles"] == 0 and rep["lost_tasks"] == 0


# ------------------------------------------------------------- coalescing
def _mk_sched_tasks(rng, kernels, priority=2):
    out = []
    for k in kernels:
        t, _ = _blur_task(rng, iters=1, kernel=k, priority=priority)
        out.append(t)
    return out


def test_coalescing_reduces_reconfigs_and_strands_nothing():
    """[M, G, M] on one region: the finished region picks up the queued
    same-bitstream task back-to-back, so the alternation costs 2 reconfigs
    instead of 3 — and without coalescing it stays 3."""
    reconfigs = {}
    for coalesce in (True, False):
        rng = np.random.default_rng(12)
        shell = Shell(n_regions=1, chunk_budget=2, prefetch=False)
        tasks = _mk_sched_tasks(rng, ["MedianBlur", "GaussianBlur",
                                      "MedianBlur"])
        for k in ("MedianBlur", "GaussianBlur"):
            shell.engine.prewarm(k, tasks[0].args, (1,))
        sched = Scheduler(shell, SchedulerConfig(coalescing=coalesce))
        rep = sched.run(tasks, quiet=True)
        shell.shutdown()
        assert rep["n_done"] == 3
        assert rep["stranded_handles"] == 0
        reconfigs[coalesce] = rep["reconfigs"]
        if coalesce:
            assert rep["coalesced_dispatches"] >= 1
            # the two Median tasks ran back-to-back
            order = sorted(tasks, key=lambda t: t.t_first_served)
            assert [t.kernel for t in order] == [
                "MedianBlur", "MedianBlur", "GaussianBlur"]
        else:
            assert rep["coalesced_dispatches"] == 0
    assert reconfigs[True] < reconfigs[False]


def test_coalescing_never_crosses_priority_levels():
    """A same-bitstream task at a lower priority must NOT jump a
    higher-priority head of a different kernel."""
    rng = np.random.default_rng(13)
    shell = Shell(n_regions=1, chunk_budget=1, prefetch=False)
    shell.regions[0].slowdown_s = 0.02  # m1 still running when g0/m2 queue
    m1, _ = _blur_task(rng, iters=2, kernel="MedianBlur", priority=3)
    g0, _ = _blur_task(rng, iters=1, kernel="GaussianBlur", priority=0)
    m2, _ = _blur_task(rng, iters=1, kernel="MedianBlur", priority=3)
    g0.arrival_time = m2.arrival_time = 0.05
    for k in ("MedianBlur", "GaussianBlur"):
        shell.engine.prewarm(k, m1.args, (1,))
    sched = Scheduler(shell, SchedulerConfig(preemption=False))
    rep = sched.run([m1, g0, m2], quiet=True)
    shell.shutdown()
    assert rep["n_done"] == 3
    # when m1 finished, the same-bitstream m2 was queued behind the urgent
    # Gaussian head — the level-0 head must run first, never be jumped
    assert g0.t_first_served < m2.t_first_served
    assert rep["coalesced_dispatches"] == 0


class _FakeRegion:
    devices = None
    loaded = None


def _match(kernel):
    return lambda t: t.kernel == kernel


def test_fcfs_peek_same_bitstream_semantics():
    rng = np.random.default_rng(14)
    pol = FcfsPriority(5)
    g, _ = _blur_task(rng, kernel="GaussianBlur", priority=0)
    m_low, _ = _blur_task(rng, kernel="MedianBlur", priority=3)
    pol.enqueue(g)
    pol.enqueue(m_low)
    region = _FakeRegion()
    # level 0 owns the region: no cross-level coalescing
    assert pol.peek_same_bitstream(_match("MedianBlur"), region, 8) is None
    # drain level 0 -> the level-3 Median becomes reachable
    assert pol.take(g)
    got = pol.peek_same_bitstream(_match("MedianBlur"), region, 8)
    assert got is m_low
    assert pol.take(got) and not pol.has_pending()


def test_edf_peek_never_skips_a_deadline():
    rng = np.random.default_rng(15)
    pol = EarliestDeadlineFirst()
    d, _ = _blur_task(rng, kernel="GaussianBlur", deadline_s=5.0)
    bg_g, _ = _blur_task(rng, kernel="GaussianBlur")
    bg_m, _ = _blur_task(rng, kernel="MedianBlur")
    for t in (d, bg_g, bg_m):
        pol.enqueue(t)
    region = _FakeRegion()
    # a deadline-bearing head is never jumped for a coalescing win
    assert pol.peek_same_bitstream(_match("MedianBlur"), region, 8) is None
    assert pol.take(d)
    # background tasks may jump other background tasks
    got = pol.peek_same_bitstream(_match("MedianBlur"), region, 8)
    assert got is bg_m and pol.take(got)


def test_wfq_peek_respects_tenant_turn_and_charges_vt():
    rng = np.random.default_rng(16)
    pol = WeightedFairShare()
    a1, _ = _blur_task(rng, kernel="MedianBlur", tenant="a")
    a2, _ = _blur_task(rng, kernel="GaussianBlur", tenant="a")
    a3, _ = _blur_task(rng, kernel="MedianBlur", tenant="a")
    b1, _ = _blur_task(rng, kernel="MedianBlur", tenant="b")
    for t in (a1, a2, a3, b1):
        pol.enqueue(t)
    region = _FakeRegion()
    # tenant a's turn: its head matches directly
    got = pol.peek_same_bitstream(_match("MedianBlur"), region, 8)
    assert got is a1 and pol.take(a1)
    vt_a = pol._vt["a"]
    assert vt_a > 0  # the coalesced dispatch charged a's virtual clock
    # now it is b's turn — a's deeper Median must not be offered
    got = pol.peek_same_bitstream(_match("MedianBlur"), region, 8)
    assert got is b1 and pol.take(b1)
    # back to a: intra-tenant FIFO may bend (a3 jumps the Gaussian a2)
    got = pol.peek_same_bitstream(_match("MedianBlur"), region, 8)
    assert got is a3


# ----------------------------------------------------- repair drain race
def test_repair_returns_dropped_launch_commands():
    """Commands still queued when a dead worker is repaired are handed
    back for requeue instead of being silently dropped."""
    rng = np.random.default_rng(17)
    shell = Shell(n_regions=1, chunk_budget=2, prefetch=False)
    region = shell.regions[0]
    try:
        t1, _ = _blur_task(rng, iters=1)
        t2, _ = _blur_task(rng, iters=1)
        region.inject_failure()
        region.enqueue_launch(t1)  # worker hits the failure and dies
        deadline = time.perf_counter() + 10.0
        while region._thread.is_alive():
            assert time.perf_counter() < deadline
            time.sleep(0.005)
        region.enqueue_launch(t2)  # lands on a dead region's queue
        assert not region.idle
        dropped = region.repair()
        assert dropped == [t2]
        assert region.alive and region.idle
        ev = shell.interrupts.drain()
        assert any(e.kind is EventKind.REGION_FAILED for e in ev)
    finally:
        shell.shutdown()


def test_repair_drain_is_atomic_and_reconciles_inflight():
    """The drain-and-reject happens under the single command lock: every
    command queued on the dead region is either handed back by repair()
    or preserved with a consistent inflight count — never silently lost
    (the seed's check-then-restart window could drop one)."""
    rng = np.random.default_rng(18)
    shell = Shell(n_regions=1, chunk_budget=2, prefetch=False)
    region = shell.regions[0]
    try:
        t0, _ = _blur_task(rng, iters=1)
        region.inject_failure()
        region.enqueue_launch(t0)  # worker dies on it
        deadline = time.perf_counter() + 10.0
        while region._thread.is_alive():
            assert time.perf_counter() < deadline
            time.sleep(0.005)
        shell.interrupts.drain()
        # several dispatches race the dead worker: all must come back
        queued = []
        for _ in range(3):
            t, _ = _blur_task(rng, iters=1)
            region.enqueue_reconfig(t)
            region.enqueue_launch(t)
            queued.append(t)
        assert not region.idle
        dropped = region.repair()
        assert dropped == queued  # launch commands, in posting order
        with region._inflight_lock:
            assert region._inflight == region._q.qsize() == 0
        assert region.alive and region.idle
        # enqueues after the repair behave normally (the lock serialized
        # them against the drain; nothing half-counted)
        t1, _ = _blur_task(rng, iters=1)
        region.enqueue_reconfig(t1)
        region.enqueue_launch(t1)
        _drive_done = time.perf_counter() + 30.0
        while t1.status is not TaskStatus.DONE:
            assert time.perf_counter() < _drive_done
            ev = shell.interrupts.wait(0.01)
            if ev is not None and ev.kind is EventKind.TASK_DONE:
                break
        assert t1.status is TaskStatus.DONE
    finally:
        shell.shutdown()


def test_auto_repair_skips_already_requeued_tasks(monkeypatch):
    """A task the REGION_FAILED handler already requeued (its launch
    command was still sitting in the dead worker's queue) must not be
    enqueued a second time by the auto-repair requeue — that would
    double-dispatch one Task onto two regions concurrently."""
    import time as _time

    rng = np.random.default_rng(20)
    shell = Shell(n_regions=1, chunk_budget=2, prefetch=False)
    try:
        sched = Scheduler(shell, SchedulerConfig(repair_after_s=0.0))
        region = shell.regions[0]
        requeued, _ = _blur_task(rng, iters=1)   # already back in a queue
        dropped_only, _ = _blur_task(rng, iters=1)  # genuinely dropped
        elsewhere, _ = _blur_task(rng, iters=1)  # re-dispatched to another
        for t in (requeued, dropped_only, elsewhere):
            t.status = TaskStatus.QUEUED
            t.last_dispatched_rid = region.rid
        # 'elsewhere' was requeued by the failure handler and then served
        # to a different region whose worker has not started it yet — the
        # drained command is stale and must not resurrect it
        elsewhere.last_dispatched_rid = region.rid + 1
        sched.policy.enqueue(requeued)
        monkeypatch.setattr(region, "repair",
                            lambda: [requeued, dropped_only, elsewhere])
        sched.t0 = _time.perf_counter()
        sched._dead_since[region.rid] = 0.0
        sched._maybe_repair()
        pending = sched.policy.pending_tasks()
        assert sum(1 for t in pending if t is requeued) == 1
        assert sum(1 for t in pending if t is dropped_only) == 1
        assert sum(1 for t in pending if t is elsewhere) == 0
    finally:
        shell.shutdown()


# ------------------------------------------------- event-driven controller
def test_controller_wait_is_event_driven():
    from repro.controller.controller import Controller

    rng = np.random.default_rng(19)
    img = make_image(rng, SIZE)
    shell = Shell(n_regions=1, chunk_budget=2, prefetch=False)
    ctrl = Controller(shell)
    try:
        kd = get_kernel("MedianBlur")  # noqa: F841 - registry warm
        t = ctrl.launch("MedianBlur", (img, np.zeros_like(img)),
                        priority=1, H=SIZE, W=SIZE, iters=1)
        with pytest.raises(TimeoutError):
            ctrl.wait(t, timeout=0.1)  # never run -> no handle registered
        th = threading.Thread(target=ctrl.run, kwargs={"quiet": True})
        th.start()
        # a wait racing run() blocks through handle registration, then on
        # completion — the cross-thread pattern the seed's polling allowed
        got = ctrl.wait(t, timeout=30.0)
        assert got.status is TaskStatus.DONE
        th.join(timeout=30)
        assert not th.is_alive()
    finally:
        ctrl.shutdown()
