"""The async reconfiguration subsystem: LRU bitstream cache (eviction
order, capacity bound, per-key stats), prefetch-hit vs cold-compile
accounting, stale-prefetch dropping, and inflight compile deduplication."""
import threading

import numpy as np
import pytest

from repro.controller.kernels import get_kernel
from repro.core.prefetch import BitstreamPrefetcher
from repro.core.reconfig import (CacheEntry, LRUBitstreamCache,
                                 ORIGIN_PREFETCH, ReconfigEngine)
from repro.core.scheduler import Scheduler, SchedulerConfig
from repro.core.shell import Shell
from repro.core.task import Task, TaskStatus, generate_random_tasks
from repro.kernels.blur.tasks import make_image

SIZE = 30


def _bundle(rng, kname="MedianBlur", size=SIZE, iters=1):
    kd = get_kernel(kname)
    img = make_image(rng, size)
    return kd.bundle(img, np.zeros_like(img), H=size, W=size, iters=iters)


# ---------------------------------------------------------------- LRU cache
def test_lru_eviction_order():
    c = LRUBitstreamCache(capacity=2)
    c.put(("a",), CacheEntry(fn=1))
    c.put(("b",), CacheEntry(fn=2))
    assert c.get(("a",)).fn == 1  # refreshes 'a': now 'b' is LRU
    c.put(("c",), CacheEntry(fn=3))
    assert ("b",) not in c and ("a",) in c and ("c",) in c
    assert c.evictions == 1 and list(c.evicted_keys) == [("b",)]


def test_lru_capacity_bound():
    c = LRUBitstreamCache(capacity=3)
    for i in range(10):
        c.put((i,), CacheEntry(fn=i))
        assert len(c) <= 3
    assert len(c) == 3
    assert c.evictions == 7
    assert c.keys() == [(7,), (8,), (9,)]  # least-recent first


def test_lru_unbounded_and_validation():
    c = LRUBitstreamCache(capacity=None)
    for i in range(50):
        c.put((i,), CacheEntry(fn=i))
    assert len(c) == 50 and c.evictions == 0
    with pytest.raises(ValueError):
        LRUBitstreamCache(capacity=0)


def test_engine_evicted_key_recompiles(rng):
    """A key pushed out of a capacity-1 cache must cold-compile again, and
    the eviction is visible in engine stats."""
    eng = ReconfigEngine(cache_capacity=1)
    b_m = _bundle(rng, "MedianBlur")
    b_g = _bundle(rng, "GaussianBlur")
    eng.load("MedianBlur", b_m, (1,))
    eng.load("GaussianBlur", b_g, (1,))   # evicts MedianBlur
    eng.load("MedianBlur", b_m, (1,))     # miss again
    assert eng.stats.evictions == 2
    assert eng.stats.cold_compiles == 3
    assert eng.stats.cache_hits == 0
    assert len(eng.cache) == 1


# ------------------------------------------------- hit/miss/prefetch stats
def test_prefetch_hit_vs_cold_compile_stats(rng):
    eng = ReconfigEngine()
    b_m = _bundle(rng, "MedianBlur")
    b_g = _bundle(rng, "GaussianBlur")

    # prefetched bitstream -> demand load is a prefetch hit, not a stall
    assert eng.prefetch("MedianBlur", b_m, (1,)) == "compiled"
    eng.load("MedianBlur", b_m, (1,))
    assert eng.stats.prefetch_compiles == 1
    assert eng.stats.prefetch_hits == 1
    assert eng.stats.cache_hits == 1
    assert eng.stats.cold_compiles == 0

    # un-prefetched bitstream -> cold compile on the dispatch path
    eng.load("GaussianBlur", b_g, (1,))
    assert eng.stats.cold_compiles == 1
    assert eng.stats.prefetch_hits == 1  # unchanged
    assert eng.stats.total_stall_s > 0
    assert eng.stats.prefetch_hit_rate() == pytest.approx(0.5)

    # duplicate prefetch of a cached key is a no-op
    assert eng.prefetch("MedianBlur", b_m, (1,)) == "cached"
    assert eng.stats.prefetch_compiles == 1

    # repeat demand hits are cache reuse, not additional prefetch wins
    eng.load("MedianBlur", b_m, (1,))
    assert eng.stats.prefetch_hits == 1
    assert eng.stats.cache_hits == 2

    # prewarmed entries never count as prefetch hits (baseline integrity)
    eng2 = ReconfigEngine()
    eng2.prewarm("MedianBlur", b_m, (1,))
    eng2.load("MedianBlur", b_m, (1,))
    assert eng2.stats.prefetch_compiles == 1  # off the dispatch path...
    assert eng2.stats.prefetch_hits == 0      # ...but not a prefetch win

    rep = eng.report()
    assert rep["cache_size"] == 2
    assert rep["prefetch_hit_rate"] == pytest.approx(1 / 3)  # 1 win / 3 loads
    key = "|".join(str(p) for p in
                   eng.cache_key("MedianBlur", b_m.signature(), (1,)))
    assert rep["per_key"][key]["origin"] == ORIGIN_PREFETCH
    assert rep["per_key"][key]["hits"] == 2


def test_stale_prefetch_for_dequeued_task_is_dropped(rng):
    """A prefetch hint whose task already left the queues must be dropped
    without compiling anything."""
    eng = ReconfigEngine()
    pf = BitstreamPrefetcher(eng, auto_start=False)  # deterministic stepping
    task = Task(kernel="MedianBlur", args=_bundle(rng))
    task.status = TaskStatus.QUEUED
    pf.submit(task, [((1,), None)])
    task.status = TaskStatus.RUNNING  # dispatched before the prefetcher ran
    pf.drain_once()
    assert eng.stats.prefetch_stale_drops == 1
    assert eng.stats.prefetch_compiles == 0
    assert len(eng.cache) == 0
    assert pf.stats.submitted == 1 and pf.stats.processed == 1

    # a still-queued task's hint does compile
    t2 = Task(kernel="GaussianBlur", args=_bundle(rng, "GaussianBlur"))
    t2.status = TaskStatus.QUEUED
    pf.submit(t2, [((1,), None)])
    pf.drain_once()
    assert eng.stats.prefetch_compiles == 1
    assert len(eng.cache) == 1


def test_prefetcher_dedupes_geometries_and_bounds_queue(rng):
    eng = ReconfigEngine()
    pf = BitstreamPrefetcher(eng, max_queue=2, auto_start=False)
    task = Task(kernel="MedianBlur", args=_bundle(rng))
    task.status = TaskStatus.QUEUED
    # (geometry, devices) placements; a duplicate collapses
    pf.submit(task, [((1,), None), ((1,), None), ((2,), None)])
    assert pf.stats.submitted == 2
    pf.submit(task, [((3,), None)])      # queue full -> dropped, not stuck
    assert pf.stats.dropped_full == 1
    pf.drain_once()
    assert pf.wait_idle(timeout=1.0)


def test_inflight_compile_dedup(rng):
    """Two threads demanding the same missing bitstream: exactly one
    compiles, the other joins the in-flight compile.  A stub compile with a
    fixed duration keeps the overlap deterministic (XLA's in-process cache
    can make real recompiles near-instant)."""
    import time

    eng = ReconfigEngine()
    eng._compile = lambda kd, bundle, devices, program: (time.sleep(0.3),
                                                         lambda *a: None)[1]
    bundle = _bundle(rng)
    errs = []

    def worker():
        try:
            eng.load("MedianBlur", bundle, (1,))
        except Exception as e:  # pragma: no cover
            errs.append(e)

    threads = [threading.Thread(target=worker) for _ in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not errs
    assert eng.stats.cold_compiles == 1
    assert eng.stats.inflight_joins == 1
    assert eng.stats.partial_loads == 2


# ------------------------------------------------- scheduler integration
def test_scheduler_prefetch_end_to_end(rng):
    """With prefetch on, the scheduler's report carries the new stats and
    the run completes exactly as without it."""
    def arg_factory(r, k):
        return _bundle(r, k, iters=int(r.integers(1, 3)))

    tasks = generate_random_tasks(rng, ["MedianBlur", "GaussianBlur"],
                                  8, 0.3, arg_factory)
    shell = Shell(n_regions=2, chunk_budget=2, prefetch=True)
    sched = Scheduler(shell, SchedulerConfig(preemption=True))
    rep = sched.run(tasks, quiet=True)
    shell.shutdown()
    assert rep["n_done"] == 8
    assert rep["reconfigs"] > 0
    assert 0.0 <= rep["prefetch_hit_rate"] <= 1.0
    assert rep["cold_compiles"] + rep["prefetch_compiles"] > 0
    assert rep["reconfig"]["prefetcher"]["submitted"] > 0
    assert not shell.prefetcher.alive  # shutdown stops the thread


def test_scheduler_prefetch_disabled_still_works(rng):
    def arg_factory(r, k):
        return _bundle(r, k)

    tasks = generate_random_tasks(rng, ["MedianBlur"], 3, 0.1, arg_factory)
    shell = Shell(n_regions=1, chunk_budget=2, prefetch=False)
    sched = Scheduler(shell, SchedulerConfig())
    rep = sched.run(tasks, quiet=True)
    shell.shutdown()
    assert rep["n_done"] == 3
    assert rep["prefetch_hits"] == 0
    assert rep["reconfig"]["prefetcher"]["submitted"] == 0
