"""Paper §6.3: partial (0.07 s) vs full (0.22 s) reconfiguration.

Our analogues, measured directly on the reconfiguration engine:
  - partial/cold    = generating a bitstream (XLA compile of the kernel)
  - partial/cached  = loading an existing partial bitstream (cache hit)
  - full            = tearing down every region + reloading
The ratio cached/full mirrors the paper's 0.07/0.22 regime when the
simulated bitstream-load times are enabled (the scheduler benches use them).

``measure_prefetch`` runs the same task stream with the async bitstream
prefetcher off and on: with prefetch, bitstream generation overlaps
execution, so cold compiles on the dispatch path (and the stall seconds
they cost) must drop while the prefetch hit rate rises — the measurable
form of the paper's latency-hiding claim.
"""
from __future__ import annotations

import time

import jax
import numpy as np

from repro.controller.kernels import get_kernel
from repro.core.reconfig import ReconfigEngine
from repro.core.scheduler import Scheduler, SchedulerConfig
from repro.core.shell import Shell
from repro.core.task import Task
from repro.kernels.blur.tasks import make_image


def measure(sizes=(128, 256), printer=print):
    printer("# §6.3: reconfiguration cost (name,us_per_call,derived)")
    rng = np.random.default_rng(0)
    eng = ReconfigEngine()
    rows = []
    for size in sizes:
        for kname in ("MedianBlur", "GaussianBlur"):
            kd = get_kernel(kname)
            img = make_image(rng, size)
            bundle = kd.bundle(img, np.zeros_like(img), H=size, W=size,
                               iters=1)
            t0 = time.perf_counter()
            eng.load(kname, bundle, (1,))
            cold = time.perf_counter() - t0
            hits = []
            for _ in range(5):
                t0 = time.perf_counter()
                eng.load(kname, bundle, (1,))
                hits.append(time.perf_counter() - t0)
            hit = float(np.median(hits))
            printer(f"reconfig/cold_{kname}_{size},{cold*1e6:.0f},"
                    f"compile_s={cold:.3f}")
            printer(f"reconfig/cached_{kname}_{size},{hit*1e6:.0f},"
                    f"hit_s={hit:.6f};speedup={cold/max(hit,1e-9):.0f}x")
            rows.append((cold, hit))
    # full reconfiguration with the paper's timing regime
    eng2 = ReconfigEngine(simulate_partial_s=0.07, simulate_full_s=0.22)
    t0 = time.perf_counter()
    eng2.full_reconfigure()
    full = time.perf_counter() - t0
    printer(f"reconfig/full_simulated,{full*1e6:.0f},"
            f"full_s={full:.3f};paper_partial_s=0.07;paper_full_s=0.22;"
            f"ratio={full/0.07:.2f}")
    return rows


def _prefetch_workload(prefetch: bool, *, slowdown_s: float,
                       seed: int = 0) -> dict:
    """One region, four tasks with pairwise-distinct bitstream keys
    ({Median, Gaussian} x {128, 256}px — the blur kernel's block width pins
    signatures to 128-multiples), all arriving up front: without prefetch
    every reconfiguration cold-compiles on the dispatch path; with it the
    prefetcher works ahead through the queue while earlier tasks execute."""
    rng = np.random.default_rng(seed)
    tasks = []
    for i, (kname, size) in enumerate((("MedianBlur", 128),
                                       ("GaussianBlur", 128),
                                       ("MedianBlur", 256),
                                       ("GaussianBlur", 256))):
        kd = get_kernel(kname)
        img = make_image(rng, size)
        tasks.append(Task(
            kernel=kname,
            args=kd.bundle(img, np.zeros_like(img), H=size, W=size, iters=2),
            priority=i % 2, arrival_time=0.0))
    shell = Shell(n_regions=1, chunk_budget=1, prefetch=prefetch)
    shell.regions[0].slowdown_s = slowdown_s  # execution window to hide in
    sched = Scheduler(shell, SchedulerConfig(preemption=False))
    rep = sched.run(tasks, quiet=True)
    shell.shutdown()
    return rep


def _prefetch_arm(prefetch: bool, slowdown_s: float) -> dict:
    """Run one arm from cold, in this process (one process owns the chip):
    XLA's in-process caches are cleared and JAX's persistent compilation
    cache is off for the arm, so neither arm — nor what ``measure()``
    compiled earlier — finds a warm bitstream and understates the
    cold-compile stalls being compared."""
    from jax.experimental.compilation_cache import compilation_cache

    was_on = jax.config.jax_enable_compilation_cache
    jax.clear_caches()
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        rep = _prefetch_workload(prefetch, slowdown_s=slowdown_s)
    finally:
        jax.config.update("jax_enable_compilation_cache", was_on)
        compilation_cache.reset_cache()
    keep = ("dispatch_stall_s", "cold_compiles", "prefetch_hit_rate",
            "wall_s", "n_done")
    return {k: rep[k] for k in keep}


def measure_prefetch(printer=print, slowdown_s: float = 0.15) -> dict:
    """Async prefetch vs synchronous baseline on an identical workload."""
    printer("# async prefetch: dispatch-path stalls vs prefetch hit rate")
    off = _prefetch_arm(False, slowdown_s)
    on = _prefetch_arm(True, slowdown_s)
    for name, rep in (("off", off), ("on", on)):
        printer(
            f"reconfig/prefetch_{name},{rep['dispatch_stall_s']*1e6:.0f},"
            f"stall_s={rep['dispatch_stall_s']:.3f};"
            f"cold_compiles={rep['cold_compiles']};"
            f"prefetch_hit_rate={rep['prefetch_hit_rate']:.2f};"
            f"wall_s={rep['wall_s']:.3f}")
    saved = off["dispatch_stall_s"] - on["dispatch_stall_s"]
    printer(f"reconfig/prefetch_stall_saved,{saved*1e6:.0f},"
            f"saved_s={saved:.3f};"
            f"cold_off={off['cold_compiles']};cold_on={on['cold_compiles']}")
    return {"off": off, "on": on}
