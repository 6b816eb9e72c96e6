"""Driver: the paper's tasks through ``Scheduler.submit`` on one ``Shell``
(one shell, its regions on one chip)."""
from __future__ import annotations

import threading

from bench.blurmix import BlurWorkload, output_image, wait_all

# tasks whose outputs are checked besides the seeded sample: preempted or
# migrated ones, which went through the commit and resume path
RESUMED_CHECKED = 256


def region_totals(regions) -> dict:
    keys = ("chunks", "busy_s", "reconfigs", "preemptions", "kernels_run")
    return {k: sum(getattr(r.stats, k) for r in regions) for k in keys}


class Driver:
    def __init__(self, cell):
        self.cell = cell
        self.cfg = cell.config
        self.handles: dict = {}
        self.due: dict = {}

    # -- set-up --------------------------------------------------------------
    def setup(self):
        import jax

        self.work = BlurWorkload(self.cell.requests, self.cell.seed,
                                 self.cell.traffic)
        self.pending = {r["i"]: self.work.task(r)
                        for r in self.cell.requests}
        self.build(jax.devices()[:self.cell.chips])
        self.warm()

    def build(self, devices):
        from repro.core.scheduler import Scheduler
        from repro.core.shell import Shell

        c = self.cfg
        self.shell = self.no_slowdown(Shell(
            n_regions=c["regions_per_shell"], devices=devices,
            **self.shell_kwargs()))
        self.sched = Scheduler(self.shell, self.scheduler_config())
        self.front = self.sched
        self._loop = threading.Thread(target=self.sched.run_forever,
                                      name="bench-scheduler", daemon=True)
        self._loop.start()
        if not self.sched.wait_until_serving(30.0):
            raise RuntimeError("scheduler did not start serving")

    def shell_kwargs(self) -> dict:
        c = self.cfg
        return dict(engine=c["engine"], prefetch=c["prefetch"],
                    chunk_budget=c["chunk_budget"],
                    simulate_partial_s=c["simulate_partial_s"],
                    simulate_full_s=c["simulate_full_s"],
                    tracer=self.cell.tracer)

    def no_slowdown(self, shell):
        """The configuration's ``slowdown_s`` (0: no injected sleeps) on
        every region the shell has or adds."""
        shell.region_slowdown_s = self.cfg["slowdown_s"]
        for r in shell.regions:
            r.slowdown_s = self.cfg["slowdown_s"]
        return shell

    def scheduler_config(self):
        from repro.core.scheduler import SchedulerConfig

        return SchedulerConfig(policy=self.cfg["policy"],
                               preemption=self.cfg["preemption"])

    def shells(self) -> list:
        return [self.shell]

    def regions(self) -> list:
        """Every region the shells ever had (retired ones keep stats)."""
        return [r for shell in self.shells()
                for r in shell._by_rid.values()]

    def warm_fronts(self) -> list:
        """Where the warm-up submits: each entry point whose programs the
        window will load."""
        return [self.sched]

    def warm(self):
        """Every program of the window, with a preemption at the first
        chunk boundary so the commit and resume path runs too."""
        hs = [front.submit(self.work.task(r, preempt_at_boundary=1))
              for front in self.warm_fronts()
              for r in self.work.warm_requests()]
        for h in hs:
            h.result(600.0)

    # -- the window ----------------------------------------------------------
    def counters(self) -> dict:
        return region_totals(self.regions())

    def submit(self, req: dict, due: float):
        task = self.pending.pop(req["i"])
        self.due[req["i"]] = due
        self.handles[req["i"]] = self.front.submit(task)

    def wait(self, deadline: float):
        wait_all(self.handles, deadline)

    def records(self) -> list:
        out = []
        for i, h in self.handles.items():
            t = h.task      # after a migration, the incarnation that ran last
            done = h.done() and t.t_done is not None
            out.append({"i": i, "due": self.due[i], "priority": t.priority,
                        "t_arrived": t.t_arrived,
                        "t_first": t.t_first_served,
                        "t_done": t.t_done if done else None,
                        "preemptions": t.n_preemptions,
                        "migrations": t.n_migrations})
        return out

    def answers(self) -> dict:
        """Outputs to compare: the seeded sample, and resumed tasks."""
        reqs = self.work.requests_by_i
        out, n_resumed = {}, 0
        for i in sorted(self.handles):
            h = self.handles[i]
            t = h.task
            pick = reqs[i]["checked"]
            if (not pick and (t.n_preemptions or t.n_migrations)
                    and n_resumed < RESUMED_CHECKED):
                pick = True
                n_resumed += 1
            if pick:
                ok = h.done() and t.result is not None
                out[i] = (output_image(t.result, reqs[i]["iters"])
                          if ok else None)
        return out

    def close(self):
        self.sched.shutdown(timeout=60.0)
        self._loop.join(timeout=60.0)
        self.shell.shutdown()

    def check(self, answers: dict) -> dict:
        return self.work.compare(answers)
