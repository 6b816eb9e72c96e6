"""``repro.Client`` — the unified front door (DESIGN.md §9.1).

One object, one pair of verbs, every substrate:

    with repro.Client(n_regions=2) as client:          # one shell
        h = client.launch("MedianBlur", (img, img), H=128, W=128, iters=2)
        out = h.result(timeout=60)
        s = client.stream([5, 9, 2], max_new_tokens=8)  # token serving
        print(list(s))                                  # iterate tokens

    repro.Client(n_shells=3)            # multi-shell cluster fabric
    repro.Client(backend=my_scheduler)  # adopt an existing scheduler
    repro.Client(backend=my_frontend)   # ... or an existing cluster

``submit(task) -> handle`` and ``stream(prompt) -> SequenceHandle`` bind
uniformly: the handle API is identical whether the work lands on a
single shell, an elastic pool, or a cluster — the Client hides which.
The old entry points (``Controller``, hand-rolled
``Scheduler.run_forever`` threads) keep working but are deprecated
shims over this facade.
"""
from __future__ import annotations

import threading
from typing import Optional, Sequence as Seq

from repro.core.scheduler import Scheduler, SchedulerConfig
from repro.core.shell import Shell
from repro.core.task import Task


class Client:
    """Uniform submission facade over Shell / Scheduler / cluster.

    Exactly one backend is bound per Client:

    - ``backend=None`` (default): builds a ``Shell(n_regions, ...)`` +
      ``Scheduler`` (``n_shells=1``) or a ``ClusterFrontend``
      (``n_shells > 1``); the Client owns their lifecycle.
    - ``backend=Shell``: wraps it in a ``Scheduler`` (Client owns the
      loop, not the shell).
    - ``backend=Scheduler``: adopts it; if its loop is not serving, the
      Client starts (and owns) a ``run_forever`` thread.
    - ``backend=ClusterFrontend`` (anything with ``submit`` +
      ``shutdown``): adopts it as-is.

    ``serving`` (a ``ServingConfig``, or a kwargs dict for one — e.g.
    ``serving={"lm": "attention"}`` to stream from the paged-KV attention
    backend) configures the lazily-created token-serving engine behind
    ``stream()``.
    """

    def __init__(self, backend=None, *, n_regions: int = 2,
                 n_shells: int = 1,
                 scheduler_config: Optional[SchedulerConfig] = None,
                 serving=None, **shell_kwargs):
        self._own_shell = False
        self._own_loop = False
        self._own_cluster = False
        self._loop_thread: Optional[threading.Thread] = None
        self._serving_cfg = serving
        self._engine = None
        self._engine_lock = threading.Lock()
        self.shell: Optional[Shell] = None
        self.scheduler: Optional[Scheduler] = None
        self.cluster = None

        if backend is None:
            if n_shells > 1:
                from repro.cluster.frontend import ClusterFrontend

                self.cluster = ClusterFrontend(
                    n_shells=n_shells, regions_per_shell=n_regions,
                    config=scheduler_config, **shell_kwargs)
                self._own_cluster = True
            else:
                self.shell = Shell(n_regions=n_regions, **shell_kwargs)
                self._own_shell = True
                self.scheduler = Scheduler(self.shell, scheduler_config)
                self._start_loop()
        elif isinstance(backend, Shell):
            self.shell = backend
            self.scheduler = Scheduler(backend, scheduler_config)
            self._start_loop()
        elif isinstance(backend, Scheduler):
            self.scheduler = backend
            self.shell = backend.shell
            if not backend.serving:
                self._start_loop()
        elif hasattr(backend, "submit") and hasattr(backend, "shutdown"):
            self.cluster = backend
        else:
            raise TypeError(
                f"backend must be a Shell, Scheduler, cluster frontend, or "
                f"None; got {type(backend).__name__}")

    def _start_loop(self):
        self._own_loop = True
        self._loop_thread = threading.Thread(
            target=self.scheduler.run_forever, name="client-scheduler",
            daemon=True)
        self._loop_thread.start()
        if not self.scheduler.wait_until_serving(10.0):
            raise RuntimeError("scheduler loop failed to start")

    # -- task submission -------------------------------------------------
    @property
    def backend(self):
        """Whatever ``submit`` goes to: the cluster frontend or the
        scheduler."""
        return self.cluster if self.cluster is not None else self.scheduler

    def submit(self, task: Task):
        """Submit a prepared ``Task``; returns its future (a
        ``TaskHandle`` or ``ClusterTaskHandle`` — same wait/result/cancel
        surface either way)."""
        return self.backend.submit(task)

    def launch(self, kernel: str, hittiles: Seq = (), priority: int = 4,
               tenant: str = "default", **scalars):
        """Convenience: build the ``Task`` from a registered kernel's
        declared argument names (the old ``Controller.launch``) and
        submit it immediately."""
        from repro.controller.hittile import HitTile
        from repro.controller.kernels import get_kernel

        kd = get_kernel(kernel)
        # a HitTile carries its array in .data; a bare array's own .data
        # is its raw memoryview, which must not stand in for it
        bufs = tuple(h.data if isinstance(h, HitTile) else h
                     for h in hittiles)
        task = Task(kernel=kernel, args=kd.bundle(*bufs, **scalars),
                    priority=priority, tenant=tenant)
        return self.submit(task)

    # -- token serving ---------------------------------------------------
    @property
    def serving(self):
        """The lazily-started ``ServingEngine`` behind ``stream()``."""
        with self._engine_lock:
            if self._engine is None:
                from repro.serving.engine import ServingConfig, ServingEngine

                cfg = self._serving_cfg or ServingConfig()
                if isinstance(cfg, dict):
                    cfg = ServingConfig(**cfg)
                self._engine = ServingEngine(self.backend, cfg).start()
            return self._engine

    def stream(self, prompt, params=None, tenant: str = "default",
               **param_kwargs):
        """Submit one generation sequence; returns a ``SequenceHandle``
        (iterate it for tokens as they stream, or ``result()`` for the
        full list).  ``prompt`` is a token-id sequence or a prepared
        ``Sequence``; sampling knobs come as a ``SamplingParams`` or as
        keywords (``max_new_tokens=...``, ``seed=...``)."""
        from repro.serving.sequence import SamplingParams, Sequence

        if isinstance(prompt, Sequence):
            if params is not None or param_kwargs:
                raise ValueError(
                    "pass sampling params inside the Sequence, not both")
            return self.serving.submit_sequence(prompt)
        if params is None:
            params = SamplingParams(**param_kwargs)
        elif param_kwargs:
            raise ValueError("pass params= or keywords, not both")
        return self.serving.submit(prompt, params, tenant=tenant)

    # -- observability ---------------------------------------------------
    @property
    def tracer(self):
        """The flight recorder threaded through the backend (``tracer=``
        shell kwarg), or ``None`` when tracing is off."""
        return getattr(self.backend, "tracer", None)

    @property
    def metrics(self):
        """The live metrics registry threaded through the backend
        (``metrics=`` shell kwarg), or ``None`` when telemetry is off."""
        return getattr(self.backend, "metrics", None)

    @property
    def alerts(self) -> list:
        """Currently-firing alerts from the attached ``TelemetryMonitor``
        (empty when telemetry is off or no monitor is sampling)."""
        reg = self.metrics
        mon = getattr(reg, "monitor", None) if reg is not None else None
        return mon.alerts() if mon is not None else []

    def report(self) -> dict:
        """The backend's versioned report (layer ``scheduler`` or
        ``cluster``; see ``core/reporting.py``)."""
        return self.backend.report()

    def serving_report(self) -> Optional[dict]:
        """The serving engine's report (layer ``serving``), or ``None``
        if ``stream()`` was never used."""
        with self._engine_lock:
            return self._engine.report() if self._engine else None

    # -- lifecycle -------------------------------------------------------
    def drain(self, timeout: Optional[float] = None) -> dict:
        """Graceful stop: finish all streamed sequences and submitted
        tasks, then stop whatever this Client owns.  Returns the final
        backend report."""
        with self._engine_lock:
            engine = self._engine
        if engine is not None:
            engine.drain(timeout)
        if self.cluster is not None:
            if self._own_cluster:
                return self.cluster.shutdown() or self.report()
            return self.cluster.drain(timeout) or self.report()
        rep = None
        if self._own_loop:
            rep = self.scheduler.drain(timeout)
        if self._own_shell:
            self.shell.shutdown()
        return rep if rep is not None else self.report()

    def shutdown(self, timeout: Optional[float] = None) -> Optional[dict]:
        """Stop now: cancel queued work, let running tasks finish, tear
        down owned resources."""
        with self._engine_lock:
            engine = self._engine
        if engine is not None:
            engine.shutdown(timeout)
        rep = None
        if self.cluster is not None:
            if self._own_cluster:
                rep = self.cluster.shutdown()
        elif self._own_loop:
            rep = self.scheduler.shutdown(timeout)
        if self._own_shell:
            self.shell.shutdown()
        return rep

    def __enter__(self) -> "Client":
        return self

    def __exit__(self, exc_type, exc, tb):
        self.shutdown()
        return False
