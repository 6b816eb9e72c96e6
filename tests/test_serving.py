"""Token-serving engine (DESIGN.md §9): sequence lifecycle over the
preemptive scheduler, bit-identity of decode rounds under forced
preemption at every chunk boundary (same-region, cross-region, and
cross-shell migration), oracle identity of the streamed tokens, the
``repro.Client`` facade, and the deprecated ``Controller`` shim."""
import threading
import time

import numpy as np
import pytest

import repro
from repro.controller.kernels import get_kernel
from repro.core.interrupts import EventKind
from repro.core.scheduler import Scheduler, SchedulerConfig
from repro.core.shell import Shell
from repro.core.task import Task, TaskStatus
from repro.serving.engine import ServingConfig, ServingEngine
from repro.serving.kernels import COL_ACTIVE, COL_LAST_TOK, COL_N_EMIT
from repro.serving.kernels import oracle_stream
from repro.serving.sequence import (SamplingParams, SequenceCancelled,
                                    SequenceStatus)

D_MODEL = 32
VOCAB = 257


# ------------------------------------------------------------ direct drive
def _decode_task(rng, S=3, D=D_MODEL, R=4, vocab=VOCAB):
    """A standalone SeqDecode round over arbitrary slot state — preemption
    bit-identity does not depend on how the state was produced."""
    kd = get_kernel("SeqDecode")
    state = rng.integers(-2**31, 2**31, size=(S, D), dtype=np.int64)
    state = state.astype(np.int32)
    slots = np.zeros((S, 8), np.int32)
    slots[:, COL_ACTIVE] = 1
    slots[:, COL_N_EMIT] = R
    slots[:, COL_LAST_TOK] = rng.integers(0, vocab, size=S)
    slots[S - 1, COL_ACTIVE] = 0  # one dead slot: masking must hold
    out = np.zeros((S, R), np.int32)
    return Task(kernel="SeqDecode",
                args=kd.bundle(out, state, slots, S=S, D=D, R=R,
                               vocab=vocab),
                priority=2)


def _drive(shell, task, preempt_at=None, resume_region=None, timeout=60.0):
    """Like tests/test_chunk_pipeline._drive, but the boundary count is
    relative to the shell's current chunk total so one shell can be
    reused across the whole preemption matrix."""
    regions = shell.regions
    target = regions[0]
    base = sum(r.stats.chunks for r in regions)
    target.enqueue_reconfig(task)
    target.enqueue_launch(task)
    armed = preempt_at is not None
    preemptions = 0
    total = lambda: sum(r.stats.chunks for r in regions) - base
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
    for r in regions:
        r.cancel_preempt()
    return preemptions


def _round_out(task):
    return tuple(np.asarray(b) for b in task.result[:3])


def test_decode_round_preempt_every_boundary_bit_identical():
    """A decode round checkpoint-preempted at EVERY chunk boundary —
    resumed on the same region and on the other region — streams the
    same tokens and leaves the same slot state as the uninterrupted
    round, bit for bit."""
    R = 4
    shell = Shell(n_regions=2, chunk_budget=1, prefetch=False)
    for r in shell.regions:
        r.slowdown_s = 0.02
    try:
        ref_task = _decode_task(np.random.default_rng(0), R=R)
        _drive(shell, ref_task)
        ref = _round_out(ref_task)
        assert np.any(ref[0][:2] != 0)  # live rows actually emitted
        assert np.all(ref[0][2] == 0)   # the dead slot stayed masked
        for resume in (None, shell.regions[1]):
            for k in range(R):
                t = _decode_task(np.random.default_rng(0), R=R)
                _drive(shell, t, preempt_at=k, resume_region=resume)
                got = _round_out(t)
                where = "same" if resume is None else "cross"
                assert all(np.array_equal(a, b)
                           for a, b in zip(got, ref)), \
                    f"{where}-region resume diverged at boundary {k}"
    finally:
        shell.shutdown()


def test_cross_shell_migration_mid_decode_bit_identical():
    """Checkpoint-migrating a RUNNING decode round to another shell
    (host-materialised spill, different region set) must not perturb the
    token stream."""
    from repro.cluster import ClusterFrontend

    ref_shell = Shell(n_regions=1, chunk_budget=1, prefetch=False)
    try:
        ref_task = _decode_task(np.random.default_rng(1), R=6)
        _drive(ref_shell, ref_task)
        ref = _round_out(ref_task)
    finally:
        ref_shell.shutdown()

    fe = ClusterFrontend(n_shells=2, regions_per_shell=1, chunk_budget=1,
                         rebalance=False)
    for node in fe.nodes:
        for r in node.shell.regions:
            r.slowdown_s = 0.02
    try:
        t = _decode_task(np.random.default_rng(1), R=6)
        h = fe.submit(t)
        deadline = time.perf_counter() + 20.0
        migrated = False
        while time.perf_counter() < deadline and not migrated:
            if t.status is TaskStatus.RUNNING and fe.migrate(tid=t.tid):
                migrated = True
                break
            time.sleep(0.002)
        assert migrated, "forced migration never completed"
        out = h.result(timeout=60.0)
        assert h.n_migrations == 1
        got = tuple(np.asarray(b) for b in out[:3])
        assert all(np.array_equal(a, b) for a, b in zip(got, ref))
    finally:
        rep = fe.shutdown()
    assert rep["stranded_handles"] == 0 and rep["lost_tasks"] == 0


# ---------------------------------------------------------- engine lifecycle
@pytest.fixture
def served_shell():
    shell = Shell(n_regions=2, chunk_budget=2, prefetch=False)
    sched = Scheduler(shell, SchedulerConfig())
    th = threading.Thread(target=sched.run_forever, daemon=True)
    th.start()
    sched.wait_until_serving(timeout=10.0)
    yield shell, sched
    sched.drain(timeout=30.0)
    shell.shutdown()


def _cfg(**kw):
    kw.setdefault("d_model", D_MODEL)
    kw.setdefault("vocab_size", VOCAB)
    return ServingConfig(**kw)


def test_sequence_lifecycle_matches_oracle(served_shell):
    """prefill -> slot insert -> N decode rounds -> eviction, with the
    streamed tokens bit-identical to the NumPy oracle for every sequence,
    regardless of batch composition."""
    shell, sched = served_shell
    engine = ServingEngine(sched, _cfg(max_slots=2, round_tokens=3)).start()
    rng = np.random.default_rng(2)
    specs = []
    handles = []
    for i in range(4):  # 4 seqs through 2 slots: forced admission waves
        prompt = [int(x) for x in rng.integers(0, VOCAB, size=2 + i)]
        mx = 2 + 2 * i
        specs.append((prompt, i, mx))
        handles.append(engine.submit(
            prompt, SamplingParams(max_new_tokens=mx, seed=i)))
    for h, (prompt, sd, mx) in zip(handles, specs):
        got = h.result(timeout=120.0)
        assert got == oracle_stream(prompt, sd, mx, D_MODEL, VOCAB)
        assert h.status is SequenceStatus.FINISHED
        assert h.sequence.time_to_first_token is not None
    rep = engine.drain(timeout=30.0)
    assert rep["n_finished"] == 4 and rep["n_failed"] == 0
    assert rep["stranded_sequences"] == 0
    assert rep["prefill_tasks"] == 4
    assert rep["slot_inserts"] == 4 and rep["slot_evictions"] == 4
    assert rep["max_slots_used"] == 2
    assert rep["tokens_out"] == sum(mx for _, _, mx in specs)
    assert rep["decode_rounds"] >= 2  # waves: the batch recomposed


def test_streaming_iterator_yields_incrementally(served_shell):
    shell, sched = served_shell
    engine = ServingEngine(sched, _cfg(round_tokens=2)).start()
    try:
        prompt = [5, 4, 3]
        h = engine.submit(prompt, SamplingParams(max_new_tokens=6, seed=9))
        got = list(h)  # blocking iterator, token by token
        assert got == oracle_stream(prompt, 9, 6, D_MODEL, VOCAB)
    finally:
        engine.shutdown(timeout=30.0)


def test_cancel_waiting_sequence(served_shell):
    shell, sched = served_shell
    engine = ServingEngine(sched, _cfg())  # not started: stays WAITING
    h = engine.submit([1, 2, 3], SamplingParams(max_new_tokens=4))
    assert engine.cancel(h.sid)
    assert h.status is SequenceStatus.CANCELLED
    with pytest.raises(SequenceCancelled):
        h.result(timeout=1.0)
    rep = engine.shutdown(timeout=5.0)
    assert rep["n_cancelled"] == 1 and rep["stranded_sequences"] == 0


def test_engine_forced_preemption_streams_bit_identical():
    """The engine's preempt probe checkpoint-preempts live decode rounds;
    every stream must still match the oracle exactly."""
    shell = Shell(n_regions=2, chunk_budget=1, prefetch=False)
    for r in shell.regions:
        r.slowdown_s = 0.02
    sched = Scheduler(shell, SchedulerConfig())
    th = threading.Thread(target=sched.run_forever, daemon=True)
    th.start()
    sched.wait_until_serving(timeout=10.0)
    engine = ServingEngine(sched, _cfg(
        round_tokens=4, preempt_probe_every=1,
        decode_regions=(shell.regions[1].rid,))).start()
    try:
        rng = np.random.default_rng(3)
        handles, specs = [], []
        for i in range(3):
            prompt = [int(x) for x in rng.integers(0, VOCAB, size=3)]
            specs.append((prompt, i))
            handles.append(engine.submit(
                prompt, SamplingParams(max_new_tokens=8, seed=i)))
        for h, (prompt, sd) in zip(handles, specs):
            assert h.result(timeout=120.0) == oracle_stream(
                prompt, sd, 8, D_MODEL, VOCAB)
        rep = engine.drain(timeout=30.0)
        assert rep["decode_preemptions"] >= 1
        assert rep["stranded_sequences"] == 0
    finally:
        sched.drain(timeout=30.0)
        shell.shutdown()


# ------------------------------------------------------------ client facade
def test_client_submit_and_stream_uniformly():
    """One Client, both verbs: classic task submission and token
    streaming ride the same scheduler loop."""
    from repro.kernels.blur.tasks import make_image

    with repro.Client(n_regions=2, chunk_budget=2,
                      serving=_cfg()) as client:
        rng = np.random.default_rng(4)
        img = make_image(rng, 24)
        h = client.launch("MedianBlur", (img, np.zeros_like(img)),
                          priority=2, H=24, W=24, iters=1)
        out = h.result(timeout=60.0)
        assert np.asarray(out[1]).shape == img.shape
        prompt = [7, 1, 7]
        toks = client.stream(prompt, max_new_tokens=5, seed=2).result(
            timeout=120.0)
        assert toks == oracle_stream(prompt, 2, 5, D_MODEL, VOCAB)
        rep = client.report()
        assert rep["report_version"] == 1
        srep = client.serving_report()
        assert srep["n_finished"] == 1 and srep["stranded_sequences"] == 0


def test_controller_shim_is_deprecated():
    from repro.controller.controller import Controller

    shell = Shell(n_regions=1, chunk_budget=2, prefetch=False)
    try:
        with pytest.warns(DeprecationWarning, match="repro.Client"):
            Controller(shell)
    finally:
        shell.shutdown()


# ------------------------------------------------------ several devices
_ACROSS_DEVICES = """
from repro.core.shell import Shell
from repro.launch.serve import serve_decode

shell = Shell(n_regions=2)
devs = [r.device.id for r in shell.regions]
shell.shutdown()
assert devs == [0, 2], devs
rep = serve_decode(lm={lm!r}, n_sequences=8, preempt_every=1, quiet=True)
assert rep["n_finished"] == 8 and rep["slot_inserts"] > 4, rep
"""


@pytest.mark.parametrize("lm", ["surrogate", "attention"])
def test_disaggregated_serving_across_devices_of_one_shell(lm):
    """A two-region shell over four devices runs prefill on device 0 and
    decode on device 2: every prefill's state must be brought over to the
    device the decode state lives on.  Eight sequences through four slots,
    so inserts follow finished rounds; every stream is oracle-checked.
    A child process, since the device count is fixed when JAX starts."""
    import os
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(root, "src"))
    out = subprocess.run([sys.executable, "-c", _ACROSS_DEVICES.format(lm=lm)],
                         env=env, cwd=root, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
