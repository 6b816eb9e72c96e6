"""Launch preparation (``Region._prepare``, DESIGN.md §11.2): a fresh
launch's context and dummy slots come from one small compiled program and
its buffers and scalar vectors from one batched ``jax.device_put``; a
resume (``resume_local``, ``resume_host``) is one ``device_put``; every
donated leaf is a buffer of its own; device arrays handed in are copied,
never donated away; and the ``prepare`` span reports the path and the
number of calls."""
import importlib.util
import os
import sys
import time
from types import SimpleNamespace

import jax
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

import repro.core.region as region_mod
from repro.controller.abi import N_BUF_SLOTS, ArgBundle
from repro.controller.kernels import get_kernel
from repro.core.context import ContextRecord
from repro.core.interrupts import EventKind
from repro.core.region import ENGINE_MODES, Region
from repro.core.shell import Shell
from repro.core.task import Task, TaskStatus
from repro.kernels.blur.tasks import make_image
from repro.obs import Tracer
from repro.obs.tracer import TraceEvent

SIZE = 30
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
METRIC = os.path.join(ROOT, "bench", "metrics",
                      "prepare_ms_per_launch.paper.py")


def _bundle(img, iters=2, kernel="MedianBlur"):
    return get_kernel(kernel).bundle(img, np.zeros_like(img), H=SIZE, W=SIZE,
                                     iters=iters)


def _until(shell, kind, timeout=60.0):
    """Wait for the next interrupt of ``kind``, skipping the others."""
    deadline = time.perf_counter() + timeout
    while True:
        assert time.perf_counter() < deadline, f"no {kind} in {timeout}s"
        ev = shell.interrupts.wait(0.05)
        if ev is not None and ev.kind is kind:
            return ev


def _launch(shell, region, task, until=EventKind.TASK_DONE):
    region.enqueue_reconfig(task)
    region.enqueue_launch(task)
    _until(shell, until)


@pytest.fixture
def shell_of():
    """Shells built through it are shut down after the test."""
    made = []

    def make(**kw):
        made.append(Shell(prefetch=False, **kw))
        return made[-1]

    yield make
    for shell in made:
        shell.shutdown()


@pytest.fixture
def counted_calls(monkeypatch):
    """The host-to-device calls each ``Region._prepare`` makes, in order:
    its ``jax.device_put`` and ``_fresh_state`` calls, counted
    independently of what the region reports."""
    real_put, real_state = jax.device_put, region_mod._fresh_state
    real_prepare = Region._prepare
    n = [0]
    per_prepare = []

    def put(*a, **k):
        n[0] += 1
        return real_put(*a, **k)

    def state(*a, **k):
        n[0] += 1
        return real_state(*a, **k)

    def prepare(self, task):
        n0 = n[0]
        out = real_prepare(self, task)
        per_prepare.append(n[0] - n0)
        return out

    monkeypatch.setattr(jax, "device_put", put)
    monkeypatch.setattr(region_mod, "_fresh_state", state)
    monkeypatch.setattr(Region, "_prepare", prepare)
    return per_prepare


def _prepare_spans(tr, tid=None):
    return [e for e in tr.events() if e.kind == "prepare"
            and (tid is None or e.tid == tid)]


def _metric():
    """The benchmark's ``prepare_ms_per_launch.paper`` reader."""
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    spec = importlib.util.spec_from_file_location("prepare_metric", METRIC)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_prepare_metric_reads_region_prepare_spans_only():
    ev = lambda kind, track, dur: TraceEvent(0.0, kind, track, 1, dur, None)
    events = [ev("prepare", ("region", 0), 0.002),
              ev("prepare", ("region", 1), 0.004),
              ev("prepare", ("cluster", 0), 9.0),
              ev("issue", ("region", 0), 1.0)]
    read = _metric().read
    assert read(SimpleNamespace(events=events)) == pytest.approx(3.0)
    assert read(SimpleNamespace(events=events[2:])) is None


def test_fresh_context_equals_fresh_leaf_for_leaf(shell_of):
    shell = shell_of(n_regions=1)
    r = shell.regions[0]
    img = make_image(np.random.default_rng(0), SIZE)
    (ctx, bufs, ints, floats), path, calls = r._prepare(
        Task(kernel="MedianBlur", args=_bundle(img)))
    assert (path, calls) == ("fresh", 2)
    want = jax.tree.leaves(ContextRecord.fresh())
    got = jax.tree.leaves(ctx)
    assert isinstance(ctx, ContextRecord) and len(got) == len(want) == 8
    for g, w in zip(got, want):
        assert isinstance(g, jax.Array)
        assert (g.shape, g.dtype) == (w.shape, w.dtype)
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    for leaf in got + list(bufs) + [ints, floats]:
        assert leaf.sharding == SingleDeviceSharding(r.device)
    np.testing.assert_array_equal(np.asarray(bufs[0]), img)


@pytest.mark.parametrize("n_real", [0, 2, N_BUF_SLOTS])
def test_fresh_state_matches_fresh_and_the_host_dummies(n_real):
    """The compiled fresh state is ``ContextRecord.fresh()`` and exactly
    the dummy slots ``ArgBundle.host()`` pads with."""
    imgs = [np.ones((4, 128), np.float32)] * n_real
    bundle = ArgBundle(bufs=tuple(imgs))
    host_bufs, _, _ = bundle.host()
    assert bundle.n_dummies == N_BUF_SLOTS - n_real
    ctx, dummies = region_mod._fresh_state(bundle.n_dummies)
    for g, w in zip(jax.tree.leaves(ctx),
                    jax.tree.leaves(ContextRecord.fresh())):
        assert (g.shape, g.dtype) == (w.shape, w.dtype)
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    assert len(dummies) == bundle.n_dummies
    for g, w in zip(dummies, host_bufs[n_real:]):
        assert (g.shape, g.dtype) == (w.shape, w.dtype)
        np.testing.assert_array_equal(np.asarray(g), w)


def test_fresh_leaves_are_distinct_buffers(shell_of):
    shell = shell_of(n_regions=1)
    r = shell.regions[0]
    img = make_image(np.random.default_rng(1), SIZE)
    (ctx, bufs, _, _), _, _ = r._prepare(
        Task(kernel="MedianBlur", args=_bundle(img)))
    leaves = jax.tree.leaves((ctx, bufs))
    assert len(leaves) == 8 + 6
    ptrs = {leaf.unsafe_buffer_pointer() for leaf in leaves}
    assert len(ptrs) == len(leaves)


def test_back_to_back_launches_of_one_bundle(shell_of, counted_calls):
    """Two tasks over one bundle both run: each launch donates buffers of
    its own, and the second finds the first's memoized scalars."""
    tr = Tracer()
    shell = shell_of(n_regions=1, chunk_budget=1, tracer=tr)
    r = shell.regions[0]
    img = make_image(np.random.default_rng(2), SIZE)
    bundle = _bundle(img)
    first = Task(kernel="MedianBlur", args=bundle)
    _launch(shell, r, first)
    scalars = bundle.scalars(r.device)
    assert scalars is not None
    second = Task(kernel="MedianBlur", args=bundle)
    _launch(shell, r, second)
    assert bundle.scalars(r.device) is scalars
    assert all(not s.is_deleted() for s in scalars)
    assert first.status is second.status is TaskStatus.DONE
    for a, b in zip(first.result, second.result):
        np.testing.assert_array_equal(a, b)
    spans = _prepare_spans(tr)
    assert [e.attrs["path"] for e in spans] == ["fresh", "fresh"]
    assert [e.attrs["calls"] for e in spans] == counted_calls == [2, 2]
    mean_ms = sum(e.dur for e in spans) / 2 * 1e3
    assert _metric().read(SimpleNamespace(events=tr.events())) == (
        pytest.approx(mean_ms))


def test_device_array_buffers_are_cloned_not_donated(shell_of):
    """A bundle whose buffers already live on the region's device (as
    serving threads its KV state in) keeps them: the launch donates
    copies, and the answer equals the host bundle's."""
    shell = shell_of(n_regions=1, chunk_budget=1)
    r = shell.regions[0]
    img = make_image(np.random.default_rng(3), SIZE)
    ref = Task(kernel="MedianBlur", args=_bundle(img))
    _launch(shell, r, ref)
    on_dev = tuple(jax.device_put(b, r.device) for b in
                   (img, np.zeros_like(img)))
    t = Task(kernel="MedianBlur",
             args=get_kernel("MedianBlur").bundle(*on_dev, H=SIZE, W=SIZE,
                                                  iters=2))
    _launch(shell, r, t)
    assert t.status is TaskStatus.DONE
    assert not any(b.is_deleted() for b in on_dev)
    np.testing.assert_array_equal(np.asarray(on_dev[0]), img)
    for a, b in zip(t.result, ref.result):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("engine", ENGINE_MODES)
def test_resume_paths_keep_the_commit_and_report_their_path(
        engine, shell_of, counted_calls):
    """A task preempted at its first chunk boundary resumes on the same
    region (``resume_local``: the bank's committed leaves stay alive for
    a REGION_FAILED recovery) and, preempted again, on the other region
    (``resume_host``); each prepare is one call, and the answer equals an
    uninterrupted run's."""
    tr = Tracer()
    shell = shell_of(n_regions=2, chunk_budget=1, engine=engine, tracer=tr)
    r0, r1 = shell.regions
    img = make_image(np.random.default_rng(4), SIZE)
    ref = Task(kernel="MedianBlur", args=_bundle(img, iters=3))
    _launch(shell, r0, ref)
    t = Task(kernel="MedianBlur", args=_bundle(img, iters=3))
    t.preempt_at_boundary = 1
    _launch(shell, r0, t, until=EventKind.TASK_PREEMPTED)
    committed = r0.bank.restore()
    assert committed.device and committed.owner is r0
    t.preempt_at_boundary = 1
    _launch(shell, r0, t, until=EventKind.TASK_PREEMPTED)
    leaves = jax.tree.leaves((committed.context, committed.payload))
    assert leaves and not any(leaf.is_deleted() for leaf in leaves)
    _launch(shell, r1, t)
    assert t.status is TaskStatus.DONE and t.n_preemptions == 2
    for a, b in zip(t.result, ref.result):
        np.testing.assert_array_equal(a, b)
    spans = _prepare_spans(tr, t.tid)
    assert [e.attrs["path"] for e in spans] == [
        "fresh", "resume_local", "resume_host"]
    assert [e.attrs["calls"] for e in spans] == [2, 1, 1]
    assert counted_calls == [2, 2, 1, 1]
    assert r0.stats.host_spills_avoided == 1
