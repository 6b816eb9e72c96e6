"""Paper §6.3 headline numbers: the preemption overhead — throughput loss of
preemptive vs non-preemptive scheduling, averaged over rates and sizes, for
1 RR (paper: 1.66% +- 2.60%) and 2 RRs (paper: 4.04% +- 7.16%) — plus the
chunk-pipeline microbench (DESIGN.md §8): per-chunk dispatch overhead of the
synchronous region hot path vs the pipelined one, at 0 / light / heavy
preemption rates, with bit-identity of preempted and cross-region-migrated
results asserted against the synchronous reference."""
from __future__ import annotations

import json
import os
import time

import numpy as np

from benchmarks.bench_throughput import rows


def overheads(sweep):
    rws = rows(sweep)
    out = {}
    for rr in (1, 2):
        deltas = []
        for size in sorted({r["size"] for r in rws}):
            for rate in ("busy", "medium", "idle"):
                pre = [r for r in rws if r["rr"] == rr and r["size"] == size
                       and r["rate"] == rate and r["preemptive"]]
                nop = [r for r in rws if r["rr"] == rr and r["size"] == size
                       and r["rate"] == rate and not r["preemptive"]]
                if pre and nop and nop[0]["tput_mean"] > 0:
                    loss = 1.0 - pre[0]["tput_mean"] / nop[0]["tput_mean"]
                    deltas.append(loss)
        out[rr] = {"mean_pct": float(np.mean(deltas) * 100),
                   "std_pct": float(np.std(deltas) * 100),
                   "max_pct": float(np.max(deltas) * 100),
                   "n_cells": len(deltas)}
    return out


def emit(sweep, printer=print):
    printer("# §6.3: preemption overhead (paper: 1.66% 1RR / 4.04% 2RR)")
    ov = overheads(sweep)
    for rr, o in ov.items():
        printer(f"overhead/preemption_rr{rr},{o['mean_pct']*1e4:.0f},"
                f"mean_pct={o['mean_pct']:.2f};std_pct={o['std_pct']:.2f};"
                f"max_pct={o['max_pct']:.2f};paper_pct="
                f"{1.66 if rr == 1 else 4.04}")


# ------------------------------------------------- chunk pipeline (§8)
def _pipeline_task(seed: int, size: int, iters: int):
    from repro.controller.kernels import get_kernel
    from repro.core.task import Task
    from repro.kernels.blur.tasks import make_image

    rng = np.random.default_rng(seed)
    img = make_image(rng, size)
    kd = get_kernel("MedianBlur")
    bundle = kd.bundle(img, np.zeros_like(img), H=size, W=size, iters=iters)
    return Task(kernel="MedianBlur", args=bundle), bundle


def run_seed_arm(preempt_every: int = 0, *, size: int = 64, iters: int = 48,
                 seed: int = 5) -> dict:
    """The pre-PR synchronous hot path, replicated verbatim as the
    baseline: a fresh ``jax.jit(kd.fn)`` chunk (no done gate, no budget
    arg), an eager ``with_budget`` + blocking ``int(ctx.done)`` host round
    trip on EVERY chunk, and — on each forced preemption — the eager
    device→host commit plus host→device resume the lazy-spill path now
    avoids."""
    import jax
    import jax.numpy as jnp

    from repro.controller.kernels import get_kernel
    from repro.core.context import ContextRecord

    _, bundle = _pipeline_task(seed, size, iters)
    kd = get_kernel("MedianBlur")
    seed_fn = jax.jit(kd.fn, donate_argnums=(0, 1))
    budget = 1
    bufs_np, ints, floats = bundle.padded()
    ctx = ContextRecord.fresh(budget=budget)
    bufs = tuple(jnp.asarray(b) for b in bufs_np)
    # warm the compile outside the measured window (engine arms are
    # prewarmed the same way)
    wc, wb = ContextRecord.fresh(budget=budget), tuple(
        jnp.asarray(b) for b in bufs_np)
    jax.block_until_ready(seed_fn(wc.with_budget(budget), wb, ints, floats))
    preemptions = 0
    chunks = 0
    t0 = time.perf_counter()
    while True:
        ctx = ctx.with_budget(budget)
        ctx, bufs = seed_fn(ctx, bufs, ints, floats)
        done = int(ctx.done)  # blocks until the chunk is ready
        chunks += 1
        if done:
            break
        if preempt_every and chunks % preempt_every == 0:
            # seed preemption: context + payload funnel through the host
            host_ctx = jax.tree.map(lambda x: jax.device_get(x), ctx)
            host_bufs = tuple(np.asarray(jax.device_get(b)) for b in bufs)
            preemptions += 1
            ctx = jax.tree.map(jnp.asarray, host_ctx)  # seed resume
            bufs = tuple(jnp.asarray(b) for b in host_bufs)
    wall = time.perf_counter() - t0
    return {
        "pipeline": False,
        "engine": "seed",
        "preempt_every": preempt_every,
        "migrate": False,
        "wall_s": wall,
        "chunks": chunks,
        "us_per_chunk": wall / max(chunks, 1) * 1e6,
        "preemptions": preemptions,
        "chunks_pipelined": 0,
        "chunks_discarded": 0,
        "host_spills_avoided": 0,
        "megakernel_launches": 0,
        "flag_poll_exits": 0,
        "result": tuple(np.asarray(jax.device_get(b)) for b in bufs[:2]),
    }


def run_pipeline_arm(pipeline: bool, preempt_every: int = 0, *,
                     engine: str = None, migrate: bool = False,
                     size: int = 64, iters: int = 48, seed: int = 5,
                     tracer=None, metrics=None) -> dict:
    """One microbench arm: a single MedianBlur task driven chunk by chunk
    on a region (budget 1 → one row block per chunk), with optional forced
    preemption every ``preempt_every`` chunks, resuming on the *other*
    region when ``migrate`` (the cross-region lazy-spill path).  Returns
    wall time, chunk counts, pipeline stats, and the result buffers.

    ``engine`` overrides the mode (``pipeline`` stays as the two-mode
    selector for the original arms).  The megakernel arm cannot watch
    chunk counts mid-launch (the whole loop is one dispatch; stats land at
    launch end), so its preemption is driven by the deterministic one-shot
    ``task.preempt_at_boundary`` arm instead — the device exits at exactly
    the same boundaries the host-driven arms preempt at."""
    from repro.core.interrupts import EventKind
    from repro.core.shell import Shell

    engine = engine or ("pipelined" if pipeline else "sync")
    mega = engine == "megakernel"
    task, bundle = _pipeline_task(seed, size, iters)
    n_regions = 2 if migrate else 1
    shell = Shell(n_regions=n_regions, chunk_budget=1, engine=engine,
                  prefetch=False, tracer=tracer, metrics=metrics)
    try:
        for r in shell.regions:  # bitstreams warm: measure dispatch, not
            shell.engine.prewarm("MedianBlur", bundle, r.geometry,  # compile
                                 program=shell.prefetcher.program,
                                 devices=r.devices)
        regions = shell.regions
        target = regions[0]
        target.enqueue_reconfig(task)
        if mega and preempt_every:
            task.preempt_at_boundary = preempt_every
        t0 = time.perf_counter()
        target.enqueue_launch(task)
        preemptions = 0
        preempt_armed = bool(preempt_every) and not mega
        total = lambda: sum(r.stats.chunks for r in regions)
        next_preempt = preempt_every
        # no preemption to inject (or device-side arming) -> block quietly
        # on the interrupt queue (a busy-polling driver thread would
        # perturb the measurement)
        wait_s = 0.0005 if (preempt_every and not mega) else 0.25
        while True:
            ev = shell.interrupts.wait(wait_s)
            if ev is not None and ev.kind is EventKind.TASK_DONE:
                break
            if ev is not None and ev.kind is EventKind.TASK_PREEMPTED:
                preemptions += 1
                next_preempt = total() + preempt_every
                preempt_armed = not mega
                if migrate:  # resume on the other region (host spill path)
                    target = regions[preemptions % len(regions)]
                    target.enqueue_reconfig(task)
                if mega:  # re-arm: same relative boundary, next launch
                    task.preempt_at_boundary = preempt_every
                target.enqueue_launch(task)
                continue
            if (preempt_every and preempt_armed
                    and total() >= next_preempt):
                preempt_armed = False
                target.request_preempt()
        wall = time.perf_counter() - t0
        chunks = total()
        return {
            "pipeline": pipeline,
            "engine": engine,
            "preempt_every": preempt_every,
            "migrate": migrate,
            "wall_s": wall,
            "chunks": chunks,
            "us_per_chunk": wall / max(chunks, 1) * 1e6,
            "preemptions": preemptions,
            "chunks_pipelined": sum(r.stats.chunks_pipelined
                                    for r in regions),
            "chunks_discarded": sum(r.stats.chunks_discarded
                                    for r in regions),
            "host_spills_avoided": sum(r.stats.host_spills_avoided
                                       for r in regions),
            "megakernel_launches": sum(r.stats.megakernel_launches
                                       for r in regions),
            "flag_poll_exits": sum(r.stats.flag_poll_exits
                                   for r in regions),
            "result": tuple(np.asarray(b) for b in task.result),
        }
    finally:
        shell.shutdown()


def _ideal_us_per_chunk(size: int, iters: int, seed: int = 5,
                        repeats: int = 3) -> float:
    """Device-bound reference: the same chunk executable issued back to
    back with zero host reads — the floor any dispatch strategy can hope
    to reach."""
    import jax
    import jax.numpy as jnp

    from repro.core.context import ContextRecord
    from repro.core.reconfig import ReconfigEngine

    _, bundle = _pipeline_task(seed, size, iters)
    engine = ReconfigEngine()
    fn, _ = engine.load("MedianBlur", bundle, (1,))
    n_chunks = None
    best = float("inf")
    for _ in range(repeats):
        bufs_np, ints, floats = bundle.padded()
        bufs = tuple(jnp.asarray(b) for b in bufs_np)
        ctx = ContextRecord.fresh()
        budget = jnp.int32(1)
        if n_chunks is None:  # discover the exact chunk count once
            n_chunks = 0
            done = 0
            while not done:
                ctx, bufs, d = fn(ctx, bufs, ints, floats, budget)
                n_chunks += 1
                done = int(d)
            continue
        t0 = time.perf_counter()
        for _ in range(n_chunks):
            ctx, bufs, d = fn(ctx, bufs, ints, floats, budget)
        assert int(d) == 1
        jax.block_until_ready(bufs)
        best = min(best, (time.perf_counter() - t0) / n_chunks * 1e6)
    return best


GATE_RATIO = 0.5  # pipelined per-chunk overhead must be <= 0.5x sync
MEGA_GATE_RATIO = 0.1  # megakernel per-chunk overhead must be <= 0.1x sync


def measure_chunk_pipeline(printer=print,
                           cache_path: str = "bench_chunk_pipeline.json",
                           use_cache: bool = True, repeats: int = 3,
                           size: int = 64, iters: int = 48) -> dict:
    """Per-chunk dispatch overhead at 0 / light / heavy preemption rates,
    plus a cross-region-migration arm, across three dispatch modes:

    - ``seed``      — the pre-PR synchronous hot path (eager per-chunk
      ``with_budget`` + blocking ``int(ctx.done)``, eager host spill on
      every preemption), replicated verbatim: THE synchronous baseline;
    - ``sync``      — the rebuilt engine with the pipeline disabled (same
      executable, blocking flag read): the bit-identity reference mode;
    - ``pipelined`` — the chunk-pipelined engine (speculative issue +
      async flag poll + lazy spill);
    - ``megakernel`` — the whole chunk loop in ONE dispatch (DESIGN.md
      §10), preemption via the device-polled flag (deterministic
      ``preempt_at_boundary`` arming at the same boundaries).

    Per-chunk *overhead* is the arm's wall time per chunk minus the
    device-bound ideal (the same executable issued back to back with no
    host reads).  The gate — enforced here and in CI — requires the
    pipelined no-preemption overhead to be at most ``GATE_RATIO`` of the
    synchronous (seed) path's, the megakernel's at most
    ``MEGA_GATE_RATIO``, and every arm's output — preempted and migrated
    included — to be bit-identical to the synchronous reference.
    """
    if use_cache and os.path.exists(cache_path):
        with open(cache_path) as f:
            result = json.load(f)
    else:
        # the device-bound floor is sampled before AND after the arms (the
        # first samples run in a colder process; the floor is the best
        # observed) so a warmup drift cannot masquerade as arm overhead
        ideal = _ideal_us_per_chunk(size, iters)
        arm_specs = {
            "none": dict(preempt_every=0),
            "light": dict(preempt_every=60),
            "heavy": dict(preempt_every=12),
        }
        reference = None
        arms = {}
        runners = {
            "seed": lambda spec: run_seed_arm(**spec, size=size,
                                              iters=iters),
            "sync": lambda spec: run_pipeline_arm(False, **spec, size=size,
                                                  iters=iters),
            "pipelined": lambda spec: run_pipeline_arm(True, **spec,
                                                       size=size,
                                                       iters=iters),
            "megakernel": lambda spec: run_pipeline_arm(
                True, **spec, engine="megakernel", size=size, iters=iters),
        }
        for mode, runner in runners.items():
            for arm_name, spec in arm_specs.items():
                best = None
                for _ in range(repeats):
                    cell = runner(spec)
                    if best is None or cell["wall_s"] < best["wall_s"]:
                        best = cell
                res = best.pop("result")
                if reference is None:  # seed/none (the pre-PR path) first
                    reference = res
                best["bit_identical"] = all(
                    np.array_equal(a, b) for a, b in zip(res, reference))
                arms[f"{mode}/{arm_name}"] = best
        for mode in ("pipelined", "megakernel"):
            mig = run_pipeline_arm(True, preempt_every=25, migrate=True,
                                   engine=mode, size=size, iters=iters)
            res = mig.pop("result")
            mig["bit_identical"] = all(
                np.array_equal(a, b) for a, b in zip(res, reference))
            arms[f"{mode}/migrated"] = mig
        ideal = min(ideal, _ideal_us_per_chunk(size, iters))
        for a in arms.values():
            a["overhead_us_per_chunk"] = a["us_per_chunk"] - ideal
        seed_overhead = max(arms["seed/none"]["overhead_us_per_chunk"], 1e-9)
        ratio = (arms["pipelined/none"]["overhead_us_per_chunk"]
                 / seed_overhead)
        mega_ratio = (arms["megakernel/none"]["overhead_us_per_chunk"]
                      / seed_overhead)
        result = {
            "config": {"size": size, "iters": iters, "budget": 1,
                       "repeats": repeats},
            "ideal_us_per_chunk": ideal,
            "arms": arms,
            "overhead_ratio_no_preempt": ratio,
            "overhead_ratio_megakernel": mega_ratio,
            "gate": {"threshold": GATE_RATIO,
                     "mega_threshold": MEGA_GATE_RATIO,
                     "pass": bool(ratio <= GATE_RATIO
                                  and mega_ratio <= MEGA_GATE_RATIO)},
        }
        with open(cache_path, "w") as f:
            json.dump(result, f, indent=1)
    printer("# chunk pipeline: sync vs pipelined per-chunk dispatch "
            "overhead (name,us_per_call,derived)")
    for name, a in result["arms"].items():
        printer(f"chunk_pipeline/{name.replace('/', '_')},"
                f"{a['us_per_chunk']:.0f},"
                f"overhead_us={a['overhead_us_per_chunk']:.0f};"
                f"chunks={a['chunks']};preempt={a['preemptions']};"
                f"pipelined={a['chunks_pipelined']};"
                f"spills_avoided={a['host_spills_avoided']};"
                f"bit_identical={a['bit_identical']}")
    ratio = result["overhead_ratio_no_preempt"]
    mega_ratio = result["overhead_ratio_megakernel"]
    printer(f"chunk_pipeline/headline,"
            f"{result['arms']['pipelined/none']['overhead_us_per_chunk']:.0f},"
            f"overhead_ratio={ratio:.3f};gate<={GATE_RATIO};"
            f"ideal_us={result['ideal_us_per_chunk']:.0f}")
    printer(f"chunk_pipeline/megakernel_headline,"
            f"{result['arms']['megakernel/none']['overhead_us_per_chunk']:.0f},"
            f"overhead_ratio={mega_ratio:.3f};gate<={MEGA_GATE_RATIO};"
            f"launches={result['arms']['megakernel/none']['megakernel_launches']}")
    assert ratio <= GATE_RATIO, (
        f"pipelined per-chunk overhead is {ratio:.2f}x the synchronous "
        f"(seed) path (gate: <= {GATE_RATIO}x): {json.dumps(result['arms'])}")
    assert mega_ratio <= MEGA_GATE_RATIO, (
        f"megakernel per-chunk overhead is {mega_ratio:.2f}x the synchronous "
        f"(seed) path (gate: <= {MEGA_GATE_RATIO}x): "
        f"{json.dumps(result['arms'])}")
    bad = [n for n, a in result["arms"].items() if not a["bit_identical"]]
    assert not bad, f"arms not bit-identical to the sync reference: {bad}"
    return result


# ------------------------------------------------- tracer overhead (§11)
TRACER_GATE_DELTA = 0.02   # traced/untraced per-chunk wall: <= +2% ...
TRACER_ABS_FLOOR_US = 2.0  # ... or <= 2us/chunk absolute (noise floor for
#                            arms whose per-chunk wall is already tiny)


def measure_tracer_overhead(printer=print,
                            cache_path: str = "bench_tracer_overhead.json",
                            use_cache: bool = True, repeats: int = 5,
                            size: int = 64, iters: int = 48) -> dict:
    """The flight recorder's dispatch-path cost (DESIGN.md §11): the
    pipelined chunk microbench run untraced vs traced (fresh ``Tracer``
    per repeat, so every issue/wait/dispatch/run span is really recorded),
    at zero and heavy preemption rates.

    The gate — enforced here and in CI — requires the traced arm's
    per-chunk wall time within ``TRACER_GATE_DELTA`` (2%) of the untraced
    arm's, or within ``TRACER_ABS_FLOOR_US`` absolute: one deque append
    under an uncontended lock must stay invisible next to a ~100us chunk
    dispatch.  Min-of-repeats on both arms filters scheduler jitter."""
    from repro.obs import Tracer

    if use_cache and os.path.exists(cache_path):
        with open(cache_path) as f:
            result = json.load(f)
    else:
        arm_specs = {"none": 0, "heavy": 12}
        arms = {}
        for arm_name, preempt_every in arm_specs.items():
            best_off, best_on, events = None, None, 0
            for _ in range(repeats):
                off = run_pipeline_arm(True, preempt_every, size=size,
                                       iters=iters)
                if best_off is None or off["wall_s"] < best_off["wall_s"]:
                    best_off = off
            for _ in range(repeats):
                tr = Tracer()
                on = run_pipeline_arm(True, preempt_every, size=size,
                                      iters=iters, tracer=tr)
                if best_on is None or on["wall_s"] < best_on["wall_s"]:
                    best_on = on
                    events = len(tr)
            off_us = best_off["us_per_chunk"]
            on_us = best_on["us_per_chunk"]
            delta = (on_us - off_us) / max(off_us, 1e-9)
            arms[arm_name] = {
                "untraced_us_per_chunk": off_us,
                "traced_us_per_chunk": on_us,
                "delta_ratio": delta,
                "delta_us": on_us - off_us,
                "chunks": best_on["chunks"],
                "events_recorded": events,
                "pass": bool(delta <= TRACER_GATE_DELTA
                             or (on_us - off_us) <= TRACER_ABS_FLOOR_US),
            }
        result = {
            "config": {"size": size, "iters": iters, "repeats": repeats},
            "arms": arms,
            "gate": {"delta_threshold": TRACER_GATE_DELTA,
                     "abs_floor_us": TRACER_ABS_FLOOR_US,
                     "pass": all(a["pass"] for a in arms.values())},
        }
        with open(cache_path, "w") as f:
            json.dump(result, f, indent=1)
    printer("# tracer overhead: traced vs untraced pipelined dispatch "
            "(name,us_per_call,derived)")
    for name, a in result["arms"].items():
        printer(f"tracer_overhead/{name},{a['traced_us_per_chunk']:.0f},"
                f"untraced_us={a['untraced_us_per_chunk']:.0f};"
                f"delta_ratio={a['delta_ratio']:.4f};"
                f"delta_us={a['delta_us']:.1f};"
                f"events={a['events_recorded']};"
                f"gate<={TRACER_GATE_DELTA}")
    assert result["gate"]["pass"], (
        f"tracer overhead exceeds the gate (<= {TRACER_GATE_DELTA:.0%} "
        f"relative or <= {TRACER_ABS_FLOOR_US}us/chunk absolute): "
        f"{json.dumps(result['arms'])}")
    return result


# live-metrics registry (DESIGN.md §12): same budget as the tracer — an
# instrumented dispatch path must stay within 2% of the bare one, or
# within the same absolute noise floor for tiny per-chunk walls
METRICS_GATE_DELTA = 0.02
METRICS_ABS_FLOOR_US = 2.0


def measure_metrics_overhead(printer=print,
                             cache_path: str = "bench_metrics_overhead.json",
                             use_cache: bool = True, repeats: int = 6,
                             size: int = 64, iters: int = 96) -> dict:
    """The live-metrics registry's dispatch-path cost (DESIGN.md §12):
    the pipelined chunk microbench run metrics-off vs metrics-on (fresh
    ``MetricsRegistry`` per repeat, so every region counter/histogram
    update really lands), at zero and heavy preemption rates — the
    mirror of ``measure_tracer_overhead``.

    The gate requires the instrumented arm's per-chunk wall within
    ``METRICS_GATE_DELTA`` (2%) of the bare arm's, or within
    ``METRICS_ABS_FLOOR_US`` absolute: a few counter increments under
    uncontended locks must stay invisible next to a chunk dispatch.
    Min-of-repeats with the arms *interleaved* (off, on, off, on, ...)
    filters scheduler jitter AND slow environmental drift — back-to-back
    blocks of one arm would fold any machine-state change between the
    blocks into the delta."""
    from repro.obs import MetricsRegistry

    if use_cache and os.path.exists(cache_path):
        with open(cache_path) as f:
            result = json.load(f)
    else:
        arm_specs = {"none": 0, "heavy": 12}
        arms = {}
        for arm_name, preempt_every in arm_specs.items():
            best_off, best_on, series = None, None, 0
            for _ in range(repeats):
                off = run_pipeline_arm(True, preempt_every, size=size,
                                       iters=iters)
                if best_off is None or off["wall_s"] < best_off["wall_s"]:
                    best_off = off
                reg = MetricsRegistry()
                on = run_pipeline_arm(True, preempt_every, size=size,
                                      iters=iters, metrics=reg)
                if best_on is None or on["wall_s"] < best_on["wall_s"]:
                    best_on = on
                    series = reg.n_series()
            off_us = best_off["us_per_chunk"]
            on_us = best_on["us_per_chunk"]
            delta = (on_us - off_us) / max(off_us, 1e-9)
            arms[arm_name] = {
                "bare_us_per_chunk": off_us,
                "metered_us_per_chunk": on_us,
                "delta_ratio": delta,
                "delta_us": on_us - off_us,
                "chunks": best_on["chunks"],
                "series_recorded": series,
                "pass": bool(delta <= METRICS_GATE_DELTA
                             or (on_us - off_us) <= METRICS_ABS_FLOOR_US),
            }
        result = {
            "config": {"size": size, "iters": iters, "repeats": repeats},
            "arms": arms,
            "gate": {"delta_threshold": METRICS_GATE_DELTA,
                     "abs_floor_us": METRICS_ABS_FLOOR_US,
                     "pass": all(a["pass"] for a in arms.values())},
        }
        with open(cache_path, "w") as f:
            json.dump(result, f, indent=1)
    printer("# metrics overhead: metered vs bare pipelined dispatch "
            "(name,us_per_call,derived)")
    for name, a in result["arms"].items():
        printer(f"metrics_overhead/{name},{a['metered_us_per_chunk']:.0f},"
                f"bare_us={a['bare_us_per_chunk']:.0f};"
                f"delta_ratio={a['delta_ratio']:.4f};"
                f"delta_us={a['delta_us']:.1f};"
                f"series={a['series_recorded']};"
                f"gate<={METRICS_GATE_DELTA}")
    assert result["gate"]["pass"], (
        f"metrics overhead exceeds the gate (<= {METRICS_GATE_DELTA:.0%} "
        f"relative or <= {METRICS_ABS_FLOOR_US}us/chunk absolute): "
        f"{json.dumps(result['arms'])}")
    return result
