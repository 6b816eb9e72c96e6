"""Multi-shell cluster fabric (DESIGN.md §7): router policies, the
checkpoint-based cross-shell migration invariant (migrated output ==
uninterrupted single-shell output, bit for bit), whole-node failover with
zero lost tasks, and leak-free teardown."""
import threading
import time

import jax
import numpy as np
import pytest

try:  # property tests degrade to deterministic variants without the dep
    from hypothesis import HealthCheck, given, settings
    from hypothesis import strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover - exercised on minimal containers
    HAVE_HYPOTHESIS = False

from repro.cluster import (ClusterFrontend, ClusterNode, NodePowerModel,
                           make_router_policy)
from repro.cluster.router import (ROUTER_NAMES, BitstreamAffinity,
                                  LeastLoaded, PowerAware)
from repro.controller.kernels import get_kernel
from repro.core.scheduler import Scheduler, SchedulerConfig
from repro.core.shell import Shell
from repro.core.task import Task, TaskStatus
from repro.kernels.blur.tasks import make_image

SIZE = 30
SLOWDOWN = 0.02


@pytest.fixture(autouse=True)
def no_thread_leaks():
    """Frontend/node teardown must not leave any background thread behind
    (monitor, node loops, region workers, prefetchers)."""
    before = set(threading.enumerate())
    yield
    deadline = time.perf_counter() + 8.0
    extra = []
    while time.perf_counter() < deadline:
        extra = [t for t in threading.enumerate()
                 if t not in before and t.is_alive()]
        if not extra:
            break
        time.sleep(0.05)
    assert not extra, f"threads leaked by the test: {extra}"


def _blur_task(rng, iters=1, priority=2, img=None, kernel="MedianBlur"):
    if img is None:
        img = make_image(rng, SIZE)
    kd = get_kernel(kernel)
    return Task(kernel=kernel,
                args=kd.bundle(img, np.zeros_like(img), H=SIZE, W=SIZE,
                               iters=iters),
                priority=priority)


def _make_frontend(n_shells=2, **kw):
    fe = ClusterFrontend(n_shells=n_shells, regions_per_shell=1,
                         chunk_budget=2, **kw)
    for node in fe.nodes:
        node.shell.region_slowdown_s = SLOWDOWN
        for r in node.shell.regions:
            r.slowdown_s = SLOWDOWN
    return fe


def _single_shell_reference(task_factory, iters, img):
    """Uninterrupted single-shell run of the same payload (the bit-for-bit
    reference for migration equivalence)."""
    shell = Shell(n_regions=1, chunk_budget=2)
    for r in shell.regions:
        r.slowdown_s = SLOWDOWN
    try:
        t = task_factory(iters=iters, img=img)
        sched = Scheduler(shell, SchedulerConfig(preemption=False))
        rep = sched.run([t], quiet=True)
        assert rep["n_done"] == 1
        return np.asarray(t.result[0])
    finally:
        shell.shutdown()


# -------------------------------------------------------------- routers
class _FakeNode:
    def __init__(self, node_id, load=0.0, warm=False,
                 power=None, n_regions=1):
        self.node_id = node_id
        self._load = load
        self._warm = warm
        self.power = power or NodePowerModel()
        self._n = n_regions

    def load(self):
        return self._load

    def has_bitstream(self, task):
        return self._warm

    def n_dispatchable(self):
        return self._n


def test_make_router_policy_registry():
    for name in ROUTER_NAMES:
        assert make_router_policy(name).name == name
    with pytest.raises(ValueError, match="unknown router policy"):
        make_router_policy("round-robin")
    with pytest.raises(ValueError):
        BitstreamAffinity(max_load_gap=0)


def test_least_loaded_router_ties_break_low_id():
    r = LeastLoaded()
    nodes = [_FakeNode(0, load=2.0), _FakeNode(1, load=0.5),
             _FakeNode(2, load=0.5)]
    assert r.choose(None, nodes).node_id == 1


def test_affinity_router_prefers_warm_cache_with_hotspot_guard():
    r = BitstreamAffinity(max_load_gap=3.0)
    # warm shell wins despite moderate extra load...
    nodes = [_FakeNode(0, load=2.0, warm=True), _FakeNode(1, load=0.0)]
    assert r.choose(None, nodes).node_id == 0
    # ...but not when it is a hot spot (gap above the guard)
    nodes = [_FakeNode(0, load=5.0, warm=True), _FakeNode(1, load=0.0)]
    assert r.choose(None, nodes).node_id == 1
    # no warm shell anywhere: falls back to least-loaded
    nodes = [_FakeNode(0, load=2.0), _FakeNode(1, load=1.0)]
    assert r.choose(None, nodes).node_id == 1


def test_power_aware_router_prefers_efficient_shell():
    r = PowerAware()
    hungry = _FakeNode(0, load=0.0, power=NodePowerModel(idle_w=60,
                                                         active_w=40))
    frugal = _FakeNode(1, load=0.0, power=NodePowerModel(idle_w=10,
                                                         active_w=8))
    assert r.choose(None, [hungry, frugal]).node_id == 1
    # heavy backlog on the frugal shell eventually tips the scale
    frugal._load = 20.0
    assert r.choose(None, [hungry, frugal]).node_id == 0


# ------------------------------------------------- submit/route/cancel
def test_cluster_spreads_load_and_reports(rng):
    fe = _make_frontend()
    try:
        handles = [fe.submit(_blur_task(rng)) for _ in range(4)]
        for h in handles:
            assert h.result(timeout=120.0) is not None
        rep = fe.report()
        assert rep["n_done"] == 4 and rep["lost_tasks"] == 0
        assert rep["n_shells"] == 2 and rep["router"] == "least-loaded"
        assert set(rep["per_shell"]) == {0, 1}
        assert sum(s["n_done"] for s in rep["per_shell"].values()) == 4
        # the least-loaded router spread the burst over both shells
        assert all(s["n_done"] >= 1 for s in rep["per_shell"].values())
        assert rep["turnaround_p99_s"] >= rep["turnaround_p50_s"] > 0
    finally:
        rep = fe.shutdown()
        assert rep["stranded_handles"] == 0


def test_cluster_cancel_while_queued(rng):
    fe = _make_frontend()
    try:
        blocker = [fe.submit(_blur_task(rng, iters=6)) for _ in range(2)]
        victim = fe.submit(_blur_task(rng, priority=4))
        assert victim.cancel()
        assert victim.cancelled() and victim.done()
        for h in blocker:
            h.result(timeout=120.0)
    finally:
        rep = fe.shutdown()
        assert rep["cancelled"] == 1 and rep["stranded_handles"] == 0


def test_submit_after_shutdown_rejected(rng):
    fe = _make_frontend()
    fe.shutdown()
    with pytest.raises(RuntimeError, match="closed"):
        fe.submit(_blur_task(rng))
    # idempotent: a second shutdown is a no-op returning the same report
    assert fe.shutdown() is fe.last_report


def test_shell_shutdown_idempotent(rng):
    shell = Shell(n_regions=2)
    shell.shutdown()
    assert not any(r.alive for r in shell.regions)
    shell.shutdown()  # second call must be a clean no-op


# ------------------------------------------------------------ migration
def _run_migration_equivalence(iters, seed):
    rng = np.random.default_rng(seed)
    img = make_image(rng, SIZE)
    ref = _single_shell_reference(
        lambda iters, img: _blur_task(rng, iters=iters, img=img),
        iters, img)
    fe = _make_frontend()
    try:
        t = _blur_task(rng, iters=iters, img=img)
        h = fe.submit(t)
        deadline = time.perf_counter() + 30.0
        while (h.status is not TaskStatus.RUNNING
               and time.perf_counter() < deadline):
            time.sleep(0.002)
        moved = fe.migrate(tid=t.tid, prefer="running", timeout=20.0)
        out = np.asarray(h.result(timeout=120.0)[0])
        if moved:  # it may legitimately finish before the preempt lands
            assert h.n_migrations == 1
            assert len(set(h.node_history)) == 2
            assert h.task.n_preemptions >= 1
        np.testing.assert_array_equal(out, ref)
        rep = fe.shutdown()
        assert rep["lost_tasks"] == 0 and rep["stranded_handles"] == 0
        return moved
    finally:
        fe.shutdown()


if HAVE_HYPOTHESIS:

    @settings(max_examples=4, deadline=None,
              suppress_health_check=[HealthCheck.function_scoped_fixture,
                                     HealthCheck.too_slow])
    @given(iters=st.integers(4, 10), seed=st.integers(0, 2**20))
    def test_migration_equivalence_property(iters, seed):
        """A task checkpoint-preempted on shell A and resumed on shell B
        produces output bit-identical to an uninterrupted single-shell
        run (checkpoint resume is deterministic replay)."""
        _run_migration_equivalence(iters, seed)

else:  # deterministic fallback

    @pytest.mark.parametrize("iters,seed", [(4, 0), (9, 17)])
    def test_migration_equivalence_property(iters, seed):
        _run_migration_equivalence(iters, seed)


def test_forced_running_migration_carries_checkpoint(rng):
    """Long task migrated mid-run: it must resume (not restart) on the
    target — its context made the checksummed disk round trip."""
    img = make_image(rng, SIZE)
    ref = _single_shell_reference(
        lambda iters, img: _blur_task(rng, iters=iters, img=img), 12, img)
    fe = _make_frontend()
    try:
        t = _blur_task(rng, iters=12, img=img)
        h = fe.submit(t)
        while h.status is not TaskStatus.RUNNING:
            time.sleep(0.002)
        time.sleep(4 * SLOWDOWN)  # run a few chunks before the move
        assert fe.migrate(tid=t.tid, prefer="running", timeout=20.0)
        out = np.asarray(h.result(timeout=120.0)[0])
        np.testing.assert_array_equal(out, ref)
        assert h.task.saved_context is None  # consumed by the resume
        assert h.task.run_s > 0
        rep = fe.report()
        assert rep["migrations_completed"] == 1
        # the migrated-out task vanished from shell A's books and
        # completed on shell B; nothing stranded anywhere
        src, dst = h.node_history
        assert rep["per_shell"][src]["migrated_out"] == 1
        assert rep["per_shell"][dst]["migrated_out"] == 0
    finally:
        rep = fe.shutdown()
        assert rep["stranded_handles"] == 0


def test_migrate_queued_task_and_drain_node(rng):
    """drain_node moves every outstanding task off a shell (queued tasks
    cancel-resubmit; running tasks checkpoint-preempt) and stops routing
    to it."""
    fe = _make_frontend()
    try:
        handles = [fe.submit(_blur_task(rng, iters=4)) for _ in range(6)]
        time.sleep(0.05)
        moved = fe.drain_node(0, timeout=20.0)
        # whatever was outstanding on shell 0 moved to shell 1
        for h in handles:
            h.result(timeout=120.0)
        rep = fe.report()
        assert rep["migrations_completed"] == moved
        if moved:  # everything that moved finished on shell 1
            assert all(h.node_history[-1] == 1 for h in handles
                       if h.n_migrations)
        assert rep["lost_tasks"] == 0
    finally:
        rep = fe.shutdown()
        assert rep["stranded_handles"] == 0


def test_migration_with_single_shell_degrades_to_noop(rng):
    fe = _make_frontend(n_shells=1)
    try:
        h = fe.submit(_blur_task(rng, iters=6))
        # nowhere to go: the task must neither fail nor cancel
        assert fe.migrate(prefer="any") is False
        assert h.result(timeout=120.0) is not None
    finally:
        rep = fe.shutdown()
        assert rep["lost_tasks"] == 0 and rep["stranded_handles"] == 0


# ------------------------------------------------------------- failover
def test_node_failure_readmits_everything(rng):
    img = make_image(rng, SIZE)
    ref = _single_shell_reference(
        lambda iters, img: _blur_task(rng, iters=iters, img=img), 6, img)
    fe = _make_frontend()
    try:
        tasks = [_blur_task(rng, iters=6, img=img) for _ in range(4)]
        handles = [fe.submit(t) for t in tasks]
        time.sleep(0.1)  # let work start on both shells
        fe.nodes[0].inject_failure()
        outs = [np.asarray(h.result(timeout=120.0)[0]) for h in handles]
        for out in outs:
            np.testing.assert_array_equal(out, ref)
        rep = fe.report()
        assert rep["failovers"] == 1
        ev = rep["failover_events"][0]
        assert ev["node"] == 0 and ev["readmitted"] >= 1
        assert rep["lost_tasks"] == 0
        assert not fe.nodes[0].healthy and fe.nodes[1].healthy
        assert rep["per_shell"][0]["crash"]  # recorded, not a traceback
        # dead shell takes no new work; the survivor does
        h = fe.submit(_blur_task(rng, img=img, iters=1))
        assert h.node_history == [1]
        h.result(timeout=120.0)
    finally:
        rep = fe.shutdown()
        assert rep["stranded_handles"] == 0


def test_failover_resumes_from_migration_checkpoint(rng):
    """Migrate A->B (leaves a verified spill checkpoint), then kill B:
    the failover re-admission on A resumes from that checkpoint and the
    final output still matches the uninterrupted reference."""
    img = make_image(rng, SIZE)
    ref = _single_shell_reference(
        lambda iters, img: _blur_task(rng, iters=iters, img=img), 14, img)
    fe = _make_frontend()
    try:
        t = _blur_task(rng, iters=14, img=img)
        h = fe.submit(t)
        while h.status is not TaskStatus.RUNNING:
            time.sleep(0.002)
        time.sleep(4 * SLOWDOWN)
        assert fe.migrate(tid=t.tid, prefer="running", timeout=20.0)
        dst = h.node_history[-1]
        # let it run a bit on the target, then kill the target
        time.sleep(4 * SLOWDOWN)
        fe.nodes[dst].inject_failure()
        out = np.asarray(h.result(timeout=120.0)[0])
        np.testing.assert_array_equal(out, ref)
        rep = fe.report()
        assert rep["failovers"] == 1
        assert rep["failover_events"][0]["resumed_from_checkpoint"] >= 1
        assert h.n_failovers == 1 and rep["lost_tasks"] == 0
    finally:
        rep = fe.shutdown()
        assert rep["stranded_handles"] == 0


def test_all_shells_dead_fails_loudly_not_silently(rng):
    from repro.cluster import ClusterError

    fe = _make_frontend()
    try:
        h = fe.submit(_blur_task(rng, iters=4))
        for node in fe.nodes:
            node.inject_failure()
        assert h.wait(timeout=60.0)
        with pytest.raises(RuntimeError):
            h.result(timeout=1.0)
        with pytest.raises(ClusterError):
            fe.submit(_blur_task(rng))
    finally:
        fe.shutdown()


def test_node_death_during_migration_does_not_orphan_task(rng):
    """The batch failover skips records owned by an in-flight migrator;
    once the migrator lets go, the monitor must still re-admit them —
    the handle may never hang until shutdown."""
    fe = _make_frontend()
    try:
        t = _blur_task(rng, iters=6)
        h = fe.submit(t)
        rec = fe._records[t.tid]
        with fe._lock:
            rec.migrating = True   # simulate a migrator holding the task
        fe.nodes[rec.node.node_id].inject_failure()
        # wait until the batch failover ran and skipped the record
        deadline = time.perf_counter() + 20.0
        while not fe.failover_events and time.perf_counter() < deadline:
            time.sleep(0.01)
        assert fe.failover_events and fe.failover_events[0]["readmitted"] == 0
        assert not h.done()
        with fe._lock:
            rec.migrating = False  # migrator gives up (its source died)
        assert h.result(timeout=120.0) is not None  # re-admitted, finished
        rep = fe.report()
        assert rep["lost_tasks"] == 0 and h.n_failovers == 1
    finally:
        rep = fe.shutdown()
        assert rep["stranded_handles"] == 0


def test_migrate_to_too_narrow_target_refused(rng):
    """An explicit migration target narrower than the task's footprint
    must be refused up front — not detach the task and let the target's
    admission destroy it."""
    wide = ClusterNode(0, shell=Shell(n_regions=1,
                                      devices=[object(), object()],
                                      chunk_budget=2))
    narrow = ClusterNode(1, shell=Shell(n_regions=1, devices=[object()],
                                        chunk_budget=2))
    fe = ClusterFrontend(nodes=[wide, narrow])
    try:
        t = _blur_task(rng, iters=4)
        t.footprint = 2
        h = fe.submit(t)
        assert h.node_history == [0]   # only the wide shell fits it
        assert fe.migrate(tid=t.tid, target=1, timeout=5.0) is False
        assert h.result(timeout=120.0) is not None
        rep = fe.report()
        assert rep["lost_tasks"] == 0 and rep["migrations_completed"] == 0
    finally:
        rep = fe.shutdown()
        assert rep["stranded_handles"] == 0


def test_armed_migration_carries_checkpoint_without_racing(rng):
    """``_migrate_at_boundary(tid, 1)`` right after submit: the task is not
    cancelled while queued, its first launch stops at chunk boundary 1,
    and it resumes from that checkpoint on the other shell."""
    img = make_image(rng, SIZE)
    ref = _single_shell_reference(
        lambda iters, img: _blur_task(rng, iters=iters, img=img), 6, img)
    fe = ClusterFrontend(n_shells=2, regions_per_shell=1, chunk_budget=2,
                         rebalance=False)
    try:
        t = _blur_task(rng, iters=6, img=img)
        h = fe.submit(t)
        assert fe._migrate_at_boundary(t.tid, 1, timeout=60.0)
        np.testing.assert_array_equal(
            np.asarray(h.result(timeout=120.0)[0]), ref)
        assert h.n_migrations == 1 and len(set(h.node_history)) == 2
        assert h.task.n_preemptions == 1
    finally:
        rep = fe.shutdown()
        assert rep["stranded_handles"] == 0


@pytest.mark.parametrize("n_devices,own", [(3, True), (2, False)])
def test_each_shell_owns_a_device_when_there_are_enough(monkeypatch,
                                                         n_devices, own):
    """One shell per device when the host has one for every shell (each
    replica on its own chip); otherwise every shell spans the grid."""
    devs = [object() for _ in range(n_devices)]
    monkeypatch.setattr(jax, "devices", lambda *a: devs)
    fe = ClusterFrontend(n_shells=3, regions_per_shell=1, start=False)
    try:
        for i, node in enumerate(fe.nodes):
            assert node.shell.devices == ([devs[i]] if own else devs)
    finally:
        fe.shutdown()


# ------------------------------------------------------------ rebalance
def test_rebalancer_moves_work_off_hot_shell(rng):
    """Stack every task on shell 0 (drain shell 1 from routing first,
    then re-open it): the monitor's rebalancer must migrate some of the
    backlog to the idle shell."""
    fe = _make_frontend(rebalance=True, rebalance_threshold=2.0,
                        rebalance_cooldown_s=0.05)
    try:
        fe._no_route.add(1)  # route the whole burst to shell 0
        handles = [fe.submit(_blur_task(rng, iters=4)) for _ in range(8)]
        fe._no_route.discard(1)  # shell 1 is back; imbalance is huge
        for h in handles:
            h.result(timeout=120.0)
        rep = fe.report()
        assert rep["migrations_completed"] >= 1
        assert any(h.n_migrations for h in handles)
        assert rep["lost_tasks"] == 0
    finally:
        rep = fe.shutdown()
        assert rep["stranded_handles"] == 0


# --------------------------------------------------------- power model
def test_power_aware_cluster_routes_to_frugal_shell(rng):
    nodes = [
        ClusterNode(0, n_regions=1, chunk_budget=2,
                    power=NodePowerModel(idle_w=60.0, active_w=40.0)),
        ClusterNode(1, n_regions=1, chunk_budget=2,
                    power=NodePowerModel(idle_w=10.0, active_w=8.0)),
    ]
    fe = ClusterFrontend(nodes=nodes, router="power-aware")
    try:
        h = fe.submit(_blur_task(rng))
        assert h.node_history == [1]  # the frugal shell wins at equal load
        h.result(timeout=120.0)
        rep = fe.report()
        assert rep["energy_j_total"] > 0
    finally:
        rep = fe.shutdown()
        assert rep["stranded_handles"] == 0
