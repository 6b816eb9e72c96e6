"""Program spans (``Tracer.span``, DESIGN.md §11.2): one span is one ring
event and one profiler annotation ``<track><id>.<kind>`` on the device
trace's clock; the untraced region and scheduler paths build neither;
the region engine's spans carry the task id; and a launch posted against
another loaded program loads the task's own first (``stale_launches``).
"""
import glob
import os
import time

import jax
import numpy as np
import pytest

import repro.obs.tracer as tracer_mod
from repro.controller.kernels import get_kernel
from repro.core.interrupts import EventKind
from repro.core.scheduler import Scheduler, SchedulerConfig
from repro.core.shell import Shell
from repro.core.task import Task, TaskStatus
from repro.kernels.blur.tasks import make_image
from repro.obs import Tracer

SIZE = 30


def _blur_task(rng, iters=2, kernel="MedianBlur", size=SIZE, priority=2,
               img=None):
    if img is None:
        img = make_image(rng, size)
    kd = get_kernel(kernel)
    return Task(kernel=kernel,
                args=kd.bundle(img, np.zeros_like(img), H=size, W=size,
                               iters=iters),
                priority=priority)


def _until(shell, kind, timeout=60.0):
    """Wait for the next interrupt of ``kind``, skipping the others."""
    deadline = time.perf_counter() + timeout
    while True:
        assert time.perf_counter() < deadline, f"no {kind} in {timeout}s"
        ev = shell.interrupts.wait(0.05)
        if ev is not None and ev.kind is kind:
            return ev


def _run(shell, task, region=0):
    r = shell.regions[region]
    r.enqueue_reconfig(task)
    r.enqueue_launch(task)
    _until(shell, EventKind.TASK_DONE)
    assert task.status is TaskStatus.DONE


def test_span_records_one_ring_event():
    tr = Tracer()
    t0 = time.perf_counter()
    with tr.span("issue", ("region", 0), tid=7, x=1) as sp:
        sp.attrs["y"] = 2
        time.sleep(0.001)
    evs = tr.events()
    assert len(evs) == 1
    e = evs[0]
    assert (e.kind, e.track, e.tid) == ("issue", ("region", 0), 7)
    assert e.t >= t0 and e.dur >= 0.001
    assert e.attrs == {"x": 1, "y": 2}


def test_span_recorded_when_its_body_raises():
    tr = Tracer()
    with pytest.raises(KeyError):
        with tr.span("handle", ("sched", 0)):
            raise KeyError("x")
    assert [e.kind for e in tr.events()] == ["handle"]


def test_untraced_paths_build_no_annotation_and_no_event(monkeypatch):
    """With ``tracer=None`` a scheduler and its regions run tasks (with a
    preemption's commit and resume) without touching the profiler or the
    ring: both constructors raise if called."""
    def boom(*a, **k):
        raise AssertionError("built while tracing is off")

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", boom)
    monkeypatch.setattr(tracer_mod, "TraceAnnotation", boom)
    monkeypatch.setattr(tracer_mod, "TraceEvent", boom)
    monkeypatch.setattr(tracer_mod, "Span", boom)
    rng = np.random.default_rng(4)
    low = _blur_task(rng, iters=3, priority=3)
    low.preempt_at_boundary = 1       # commit and resume, deterministically
    tasks = [low, _blur_task(rng, iters=1, priority=0, kernel="GaussianBlur")]
    shell = Shell(n_regions=1, chunk_budget=1, prefetch=False)
    assert shell.tracer is None
    try:
        sched = Scheduler(shell, SchedulerConfig(preemption=True))
        sched.run(tasks, quiet=True)
    finally:
        shell.shutdown()
    assert all(t.status is TaskStatus.DONE for t in tasks)
    assert low.n_preemptions == 1
    assert not hasattr(sched, "events_log")


def test_pipelined_run_emits_region_spans_with_task_id():
    rng = np.random.default_rng(5)
    tr = Tracer()
    shell = Shell(n_regions=1, chunk_budget=2, prefetch=False, tracer=tr)
    try:
        t = _blur_task(rng, iters=2)
        t.preempt_at_boundary = 1
        r = shell.regions[0]
        r.enqueue_reconfig(t)
        r.enqueue_launch(t)
        _until(shell, EventKind.TASK_PREEMPTED)
        r.enqueue_launch(t)
        _until(shell, EventKind.TASK_DONE)
        stats = r.stats
    finally:
        shell.shutdown()
    mine = [e for e in tr.events() if e.tid == t.tid]
    kinds = {e.kind for e in mine}
    assert {"reconfig", "prepare", "issue", "wait", "commit",
            "readback", "run", "done"} <= kinds
    assert "chunk" not in {e.kind for e in tr.events()}
    assert all(e.track == ("region", 0) for e in mine)
    by = lambda k: [e for e in mine if e.kind == k]
    # every chunk executable call is one issue span, speculative ones too
    assert len(by("issue")) == stats.chunks + stats.chunks_discarded
    assert len(by("prepare")) == 2 and len(by("readback")) == 1
    assert all(e.dur > 0 for k in ("prepare", "issue", "wait", "readback")
               for e in by(k))


def test_scheduler_handle_and_dispatch_spans():
    rng = np.random.default_rng(6)
    tr = Tracer()
    tasks = [_blur_task(rng, iters=1) for _ in range(3)]
    shell = Shell(n_regions=1, chunk_budget=4, prefetch=False, tracer=tr)
    try:
        Scheduler(shell).run(tasks, quiet=True)
    finally:
        shell.shutdown()
    evs = tr.events()
    handles = [e for e in evs if e.kind == "handle"]
    dispatch = [e for e in evs if e.kind == "dispatch"]
    assert {e.tid for e in dispatch} == {t.tid for t in tasks}
    assert all(e.track == ("sched", 0) and e.dur > 0 for e in dispatch)
    done = [e for e in handles if e.attrs["event"] == "task_done"]
    assert {e.tid for e in done} == {t.tid for t in tasks}
    assert all(0.0 <= e.attrs["lag_s"] < 60.0 for e in handles)


def test_issue_span_mirrored_into_the_profiler_trace(tmp_path):
    """Inside a profiler session the region's ``issue`` spans appear on the
    host plane as ``region0.issue`` with the task id as ``tid``, each as
    long as its ring event."""
    from jax.profiler import ProfileData

    rng = np.random.default_rng(7)
    tr = Tracer()
    shell = Shell(n_regions=1, chunk_budget=2, prefetch=False, tracer=tr)
    try:
        _run(shell, _blur_task(rng, iters=1))   # compile outside the trace
        tr.clear()
        t = _blur_task(rng, iters=2)
        jax.profiler.start_trace(str(tmp_path))
        try:
            _run(shell, t)
        finally:
            jax.profiler.stop_trace()
    finally:
        shell.shutdown()
    ring = sorted((e.t, e.dur) for e in tr.events() if e.kind == "issue")
    assert ring
    files = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    assert len(files) == 1
    host = [p for p in ProfileData.from_file(files[0]).planes
            if p.name == "/host:CPU"]
    assert host
    mirrored = sorted((e.start_ns, e.duration_ns, dict(e.stats))
                      for line in host[0].lines for e in line.events
                      if e.name == "region0.issue")
    assert len(mirrored) == len(ring)
    for (_, dur_ns, stats), (_, dur) in zip(mirrored, ring):
        assert stats.get("tid") == t.tid
        assert abs(dur_ns / 1e9 - dur) < 2e-4


@pytest.mark.parametrize("other", ["size", "kernel"])
def test_stale_launch_loads_the_tasks_program(other):
    """After ``reconfig(B)``/``launch(T_B)``, a ``launch(T_A)`` posted with
    no reconfig finds B loaded: the region loads A first, counts one stale
    launch, and ``T_A`` finishes equal to a correctly loaded run.  B
    differs in padded side (the old ``TypeError``) or in kernel alone (the
    old wrong answer)."""
    rng = np.random.default_rng(8)
    img_a = make_image(rng, SIZE)
    tr = Tracer()
    shell = Shell(n_regions=1, chunk_budget=2, prefetch=False, tracer=tr)
    try:
        ref = _blur_task(rng, img=img_a)
        _run(shell, ref)
        t_b = (_blur_task(rng, size=200) if other == "size"
               else _blur_task(rng, kernel="GaussianBlur"))
        _run(shell, t_b)
        r = shell.regions[0]
        assert r.stats.stale_launches == 0
        t_a = _blur_task(rng, img=img_a)
        assert r.loaded != (t_a.kernel, t_a.args.signature(), r.geometry)
        r.enqueue_launch(t_a)
        _until(shell, EventKind.TASK_DONE)
        assert r.stats.stale_launches == 1
    finally:
        shell.shutdown()
    assert t_a.status is TaskStatus.DONE
    assert all(np.array_equal(a, b) for a, b in zip(t_a.result, ref.result))
    stale = [e for e in tr.events() if e.kind == "stale_launch"]
    assert [e.tid for e in stale] == [t_a.tid]
