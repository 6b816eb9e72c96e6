"""Driver: the paper's tasks through ``ClusterFrontend.submit`` over
several one-chip shells: the router picks a shell per task, and the
rebalancer migrates work between shells (a task preempted earlier carries
its checkpoint across chips through the checksummed spill)."""
from __future__ import annotations

from bench.drivers.scheduler import Driver as ShellDriver


class Driver(ShellDriver):
    def build(self, devices):
        from repro.cluster.frontend import ClusterFrontend

        c = self.cfg
        # with a chip per shell, shell i runs on chip i (the harness has
        # checked the chips); with fewer devices, as on a CPU, they share
        self.fe = ClusterFrontend(
            n_shells=c["shells"], regions_per_shell=c["regions_per_shell"],
            router=c["router"], config=self.scheduler_config(),
            rebalance=c["rebalance"],
            rebalance_threshold=c["rebalance_threshold"],
            rebalance_cooldown_s=c["rebalance_cooldown_s"],
            **self.shell_kwargs())
        for node in self.fe.nodes:
            self.no_slowdown(node.shell)
        self.front = self.fe

    def shells(self) -> list:
        return [node.shell for node in self.fe.nodes]

    def warm_fronts(self) -> list:
        # every shell compiles its own programs, for its own chip
        return list(self.fe.nodes)

    def counters(self) -> dict:
        out = super().counters()
        out["migrations"] = self.fe.migrations_completed
        return out

    def close(self):
        self.fe.shutdown(timeout=60.0)
