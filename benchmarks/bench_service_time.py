"""Paper Fig. 3: service time per priority queue, +-preemption, 1 vs 2 RRs,
three arrival rates (largest size, 30 tasks) — plus a policy arm comparing
fcfs vs edf vs wfq on the same task stream (p50/p99 turnaround, deadline
misses, fairness), an elastic arm comparing static-1RR / static-2RR /
autoscaled pools on a bursty open-loop trace (p99 turnaround vs
region-seconds consumed), and a cluster arm comparing 1-shell / 2-shell /
2-shell-with-forced-migration fabrics on the same trace (DESIGN.md §7),
asserting migrated outputs stay bit-identical to the 1-shell reference."""
from __future__ import annotations

import json
import os

import numpy as np


def rows(sweep, size=256):
    out = []
    for rate in ("busy", "medium", "idle"):
        for n_regions in (1, 2):
            for preemption in (False, True):
                cells = [r for r in sweep
                         if r["cfg"]["size"] == size
                         and r["cfg"]["rate"] == rate
                         and r["cfg"]["n_regions"] == n_regions
                         and r["cfg"]["preemption"] == preemption
                         and not r["cfg"]["full_reconfig"]]
                by_prio = {p: [] for p in range(5)}
                for c in cells:
                    for t in c["service_times"].values():
                        if t["service_s"] is not None:
                            by_prio[t["priority"]].append(t["service_s"])
                for p in range(5):
                    v = by_prio[p]
                    out.append({
                        "rate": rate, "rr": n_regions,
                        "preemptive": preemption, "priority": p,
                        "mean_service_s": float(np.mean(v)) if v else 0.0,
                        "std_service_s": float(np.std(v)) if v else 0.0,
                        "n": len(v),
                    })
    return out


def emit(sweep, printer=print):
    printer("# Fig3: service time by priority "
            "(name,us_per_call,derived)")
    for r in rows(sweep):
        name = (f"fig3/svc_{r['rate']}_rr{r['rr']}"
                f"_{'pre' if r['preemptive'] else 'nopre'}_p{r['priority']}")
        printer(f"{name},{r['mean_service_s']*1e6:.0f},"
                f"std_us={r['std_service_s']*1e6:.0f};n={r['n']}")
    # headline: urgent(p0/p1) mean with vs without preemption at busy rate
    urgent_pre = [r for r in rows(sweep)
                  if r["preemptive"] and r["priority"] <= 1
                  and r["rate"] == "busy"]
    urgent_nop = [r for r in rows(sweep)
                  if not r["preemptive"] and r["priority"] <= 1
                  and r["rate"] == "busy"]
    mp = np.mean([r["mean_service_s"] for r in urgent_pre if r["n"]])
    mn = np.mean([r["mean_service_s"] for r in urgent_nop if r["n"]])
    printer(f"fig3/urgent_speedup_busy,{mp*1e6:.0f},"
            f"nonpreemptive_us={mn*1e6:.0f};speedup={mn/max(mp,1e-9):.2f}x")


# ------------------------------------------------------------- policies
def run_policy_cell(policy: str, *, n_tasks: int = 18, n_regions: int = 2,
                    size: int = 128, rate_s: float = 1.0, seed: int = 7,
                    slowdown: float = 0.02) -> dict:
    """One policy arm: the SAME seeded task stream (2 tenants, deadlines)
    served under ``policy``; returns the scheduler report."""
    from repro.controller.kernels import get_kernel
    from repro.core.scheduler import Scheduler, SchedulerConfig
    from repro.core.shell import Shell
    from repro.core.task import generate_random_tasks
    from repro.kernels.blur.tasks import make_image

    rng = np.random.default_rng(seed)

    def arg_factory(r, k):
        img = make_image(r, size)
        kd = get_kernel(k)
        return kd.bundle(img, np.zeros_like(img), H=size, W=size, iters=1)

    tasks = generate_random_tasks(
        rng, ["MedianBlur", "GaussianBlur"], n_tasks, rate_s, arg_factory,
        tenants=["tenantA", "tenantB"], deadline_slack=(0.5, 2.0))
    shell = Shell(n_regions=n_regions, chunk_budget=2)
    for kname in ("MedianBlur", "GaussianBlur"):
        shell.engine.prewarm(kname, tasks[0].args,
                             shell.regions[0].geometry)
    for r in shell.regions:
        r.slowdown_s = slowdown
    sched = Scheduler(shell, SchedulerConfig(policy=policy))
    rep = sched.run(tasks, quiet=True)
    shell.shutdown()
    rep["cfg"] = {"policy": policy, "n_tasks": n_tasks,
                  "n_regions": n_regions, "size": size, "rate": rate_s,
                  "seed": seed}
    return rep


def measure_policies(printer=print, cache_path: str = "bench_policies.json",
                     use_cache: bool = True, **cell_kwargs):
    """fcfs vs edf vs wfq on one identical stream: p50/p99 turnaround,
    deadline misses, fairness ratio; cached into the benchmark JSON."""
    if use_cache and os.path.exists(cache_path):
        with open(cache_path) as f:
            results = json.load(f)
    else:
        results = [run_policy_cell(p, **cell_kwargs)
                   for p in ("fcfs", "edf", "wfq")]
        keep = ("cfg", "policy", "n_done", "wall_s", "throughput_tps",
                "turnaround_p50_s", "turnaround_p99_s", "deadline_tasks",
                "deadline_misses", "per_tenant", "fairness_ratio",
                "preemptions", "reconfigs", "coalesced_dispatches",
                "stranded_handles")
        results = [{k: r[k] for k in keep} for r in results]
        with open(cache_path, "w") as f:
            json.dump(results, f)
    printer("# policy arm: fcfs vs edf vs wfq on the same stream "
            "(name,us_per_call,derived)")
    for r in results:
        printer(f"policy/{r['policy']}_turnaround,"
                f"{r['turnaround_p50_s']*1e6:.0f},"
                f"p99_us={r['turnaround_p99_s']*1e6:.0f};"
                f"deadline_miss={r['deadline_misses']}/"
                f"{r['deadline_tasks']};"
                f"fairness={r['fairness_ratio']:.2f};"
                f"n_done={r['n_done']};preempt={r['preemptions']};"
                f"reconfigs={r.get('reconfigs')};"
                f"coalesced={r.get('coalesced_dispatches')}")
    return results


# ------------------------------------------------------------- elastic pool
def run_elastic_cell(arm: str, *, n_bursts: int = 3, burst: int = 6,
                     gap_s: float = 2.5, size: int = 48, seed: int = 11,
                     slowdown: float = 0.02, max_regions: int = 2) -> dict:
    """One arm of the elastic comparison under a deterministic bursty
    open-loop trace: ``burst`` tasks arrive back-to-back, then the line
    goes idle for ``gap_s`` — repeated ``n_bursts`` times.

    ``arm`` is ``static1`` / ``static2`` (fixed shells, the paper's two
    builds), ``static2-nc`` (static2 with same-bitstream coalescing
    disabled — the reconfig-count control arm, DESIGN.md §8.3) or
    ``elastic`` (1 region + autoscaler bounded at ``max_regions``).
    Returns the scheduler report with the run config and region-seconds
    attached.
    """
    import threading
    import time as _time

    from repro.controller.kernels import get_kernel
    from repro.core.pool import Autoscaler, AutoscalerConfig, RegionPool
    from repro.core.scheduler import Scheduler, SchedulerConfig
    from repro.core.shell import Shell
    from repro.core.task import Task
    from repro.kernels.blur.tasks import make_image

    rng = np.random.default_rng(seed)
    kernels = ["MedianBlur", "GaussianBlur"]

    def make_task(i):
        # kernels alternate within a burst (the executable-churn worst
        # case); serving bursts carry one priority class, so the reconfig
        # pressure is real FIFO alternation — exactly what same-bitstream
        # coalescing (DESIGN.md §8.3) exists to absorb
        k = kernels[i % len(kernels)]
        img = make_image(rng, size)
        kd = get_kernel(k)
        return Task(kernel=k,
                    args=kd.bundle(img, np.zeros_like(img), H=size, W=size,
                                   iters=1),
                    priority=2)

    tasks = [make_task(i) for i in range(n_bursts * burst)]

    pool = None
    if arm == "elastic":
        shell = Shell(n_regions=1, chunk_budget=2)
        pool = RegionPool(shell, autoscaler=Autoscaler(AutoscalerConfig(
            min_regions=1, max_regions=max_regions,
            grow_queue_depth=1.5, cooldown_s=0.25, idle_grace_s=0.3)))
    else:
        shell = Shell(n_regions={"static1": 1, "static2": 2,
                                 "static2-nc": 2}[arm],
                      chunk_budget=2)
    for kname in kernels:
        shell.engine.prewarm(kname, tasks[0].args, shell.regions[0].geometry)
    shell.region_slowdown_s = slowdown  # grown regions inherit the same
    for r in shell.regions:             # deterministic per-chunk cost
        r.slowdown_s = slowdown

    sched = Scheduler(shell,
                      SchedulerConfig(coalescing=(arm != "static2-nc")),
                      pool=pool)
    server = threading.Thread(target=sched.run_forever, daemon=True)
    server.start()
    sched.wait_until_serving(timeout=10.0)
    handles = []
    for b in range(n_bursts):
        for i in range(burst):
            handles.append(sched.submit(tasks[b * burst + i]))
        if b < n_bursts - 1:
            _time.sleep(gap_s)
    for h in handles:
        h.wait(timeout=120.0)
    rep = sched.drain(timeout=60.0)
    server.join(timeout=10.0)
    shell.shutdown()
    rep["cfg"] = {"arm": arm, "n_bursts": n_bursts, "burst": burst,
                  "gap_s": gap_s, "size": size, "seed": seed,
                  "max_regions": max_regions}
    rep["region_seconds"] = rep["pool"]["region_seconds"]
    return rep


# ------------------------------------------------------------- cluster
def run_cluster_cell(arm: str, *, n_bursts: int = 3, burst: int = 8,
                     gap_s: float = 1.0, size: int = 48, seed: int = 23,
                     slowdown: float = 0.02, iters: int = 2):
    """One arm of the cluster comparison on the deterministic bursty
    trace: ``1shell`` / ``2shell`` (router spreads, no migration) /
    ``2shell-migrate`` (additionally checkpoint-migrates one *running*
    task per burst off the busiest shell).  Returns ``(cell, outputs)``
    where ``outputs[i]`` is task i's result buffer — the migrate arm's
    migrated outputs are compared bit-for-bit against the 1shell arm's.
    """
    import time as _time

    from repro.cluster import ClusterFrontend
    from repro.controller.kernels import get_kernel
    from repro.core.task import Task
    from repro.kernels.blur.tasks import make_image

    rng = np.random.default_rng(seed)
    kernels = ["MedianBlur", "GaussianBlur"]

    def make_task(i):
        k = kernels[i % len(kernels)]
        img = make_image(rng, size)
        kd = get_kernel(k)
        return Task(kernel=k,
                    args=kd.bundle(img, np.zeros_like(img), H=size, W=size,
                                   iters=iters),
                    priority=int(rng.integers(5)))

    tasks = [make_task(i) for i in range(n_bursts * burst)]
    fe = ClusterFrontend(n_shells=1 if arm == "1shell" else 2,
                         regions_per_shell=1, rebalance=False,
                         chunk_budget=2)
    for node in fe.nodes:
        node.shell.region_slowdown_s = slowdown
        for r in node.shell.regions:
            r.slowdown_s = slowdown
        for kname in kernels:
            ex = next(t for t in tasks if t.kernel == kname)
            for geom, devs in node.shell.placements():
                node.shell.engine.prewarm(kname, ex.args, geom,
                                          devices=devs)

    handles = []
    forced = 0
    for b in range(n_bursts):
        for i in range(burst):
            handles.append(fe.submit(tasks[b * burst + i]))
        if arm == "2shell-migrate":
            # one deterministic checkpoint-migration per burst: preempt a
            # running task on the busiest shell, resume it on the other
            t0 = _time.perf_counter()
            while _time.perf_counter() - t0 < 5.0:
                if fe.migrate(prefer="running"):
                    forced += 1
                    break
                _time.sleep(0.005)
        if b < n_bursts - 1:
            _time.sleep(gap_s)
    for h in handles:
        h.wait(timeout=180.0)
    outputs = [np.asarray(h.result(timeout=1.0)[0]) for h in handles]
    migrated = [i for i, h in enumerate(handles) if h.n_migrations > 0]
    rep = fe.shutdown()
    cell = {k: rep[k] for k in (
        "n_shells", "router", "wall_s", "throughput_tps",
        "turnaround_p50_s", "turnaround_p99_s", "lost_tasks",
        "stranded_handles", "migrations_completed", "failovers")}
    cell["n_done"] = rep["n_done"]
    cell["region_seconds"] = sum(s["region_seconds"]
                                 for s in rep["per_shell"].values())
    cell["cfg"] = {"arm": arm, "n_bursts": n_bursts, "burst": burst,
                   "gap_s": gap_s, "size": size, "seed": seed,
                   "iters": iters}
    cell["migrated_tasks"] = migrated
    return cell, outputs


def measure_cluster(printer=print, cache_path: str = "bench_cluster.json",
                    use_cache: bool = True, **cell_kwargs):
    """1-shell vs 2-shell vs 2-shell-with-migration on the same bursty
    trace: the 2-shell fabric should hold p99 well under the 1-shell
    build (the acceptance bar is <= 0.75x), and every migrated task's
    output must match the 1-shell reference bit-for-bit (checkpoint
    resume is deterministic replay)."""
    if use_cache and os.path.exists(cache_path):
        with open(cache_path) as f:
            results = json.load(f)
    else:
        results = []
        reference = None
        for arm in ("1shell", "2shell", "2shell-migrate"):
            cell, outputs = run_cluster_cell(arm, **cell_kwargs)
            if arm == "1shell":
                reference = outputs
            migrated = cell["migrated_tasks"]
            cell["migrated_bit_identical"] = (
                bool(migrated)
                and all(np.array_equal(outputs[i], reference[i])
                        for i in migrated))
            results.append(cell)
        with open(cache_path, "w") as f:
            json.dump(results, f)
    printer("# cluster arm: 1shell vs 2shell vs 2shell-migrate on the "
            "same bursty trace (name,us_per_call,derived)")
    for r in results:
        arm = r["cfg"]["arm"]
        printer(f"cluster/{arm}_turnaround,"
                f"{r['turnaround_p50_s']*1e6:.0f},"
                f"p99_us={r['turnaround_p99_s']*1e6:.0f};"
                f"n_done={r['n_done']};"
                f"migrations={r['migrations_completed']};"
                f"lost={r['lost_tasks']};"
                f"region_s={r['region_seconds']:.2f}")
    by_arm = {r["cfg"]["arm"]: r for r in results}
    if "1shell" in by_arm and "2shell" in by_arm:
        s1, s2 = by_arm["1shell"], by_arm["2shell"]
        ratio = (s2["turnaround_p99_s"] /
                 max(s1["turnaround_p99_s"], 1e-9))
        mig = by_arm.get("2shell-migrate", {})
        printer(f"cluster/headline,{s2['turnaround_p99_s']*1e6:.0f},"
                f"p99_vs_1shell={ratio:.2f}x;"
                f"migrations={mig.get('migrations_completed', 0)};"
                f"migrated_bit_identical="
                f"{mig.get('migrated_bit_identical', False)}")
    return results


def measure_elastic(printer=print, cache_path: str = "bench_elastic.json",
                    use_cache: bool = True, **cell_kwargs):
    """Static-1RR vs static-2RR vs autoscaled pool on the same bursty
    open-loop trace: turnaround p99 against region-seconds consumed.  The
    elastic pool should hold p99 near static-2RR while consuming fewer
    region-seconds (it sheds the second region between bursts)."""
    if use_cache and os.path.exists(cache_path):
        with open(cache_path) as f:
            results = json.load(f)
    else:
        results = [run_elastic_cell(a, **cell_kwargs)
                   for a in ("static1", "static2", "static2-nc", "elastic")]
        keep = ("cfg", "n_done", "wall_s", "throughput_tps",
                "turnaround_p50_s", "turnaround_p99_s", "preemptions",
                "region_seconds", "pool", "reconfigs",
                "coalesced_dispatches", "stranded_handles")
        results = [{k: r[k] for k in keep} for r in results]
        with open(cache_path, "w") as f:
            json.dump(results, f)
    printer("# elastic arm: static-1RR vs static-2RR (+/- coalescing) vs "
            "autoscaled pool on a bursty trace (name,us_per_call,derived)")
    for r in results:
        p = r["pool"]
        printer(f"elastic/{r['cfg']['arm']}_turnaround,"
                f"{r['turnaround_p50_s']*1e6:.0f},"
                f"p99_us={r['turnaround_p99_s']*1e6:.0f};"
                f"region_s={r['region_seconds']:.2f};"
                f"resizes={p.get('resizes', 0)};"
                f"util={p.get('utilization', 0.0):.2f};"
                f"reconfigs={r.get('reconfigs')};"
                f"coalesced={r.get('coalesced_dispatches')};"
                f"stranded={r.get('stranded_handles')};"
                f"n_done={r['n_done']}")
    by_arm = {r["cfg"]["arm"]: r for r in results}
    if "static2" in by_arm and "static2-nc" in by_arm:
        co, nc = by_arm["static2"], by_arm["static2-nc"]
        printer(f"elastic/coalescing_headline,{co.get('reconfigs', 0)},"
                f"reconfigs_without={nc.get('reconfigs', 0)};"
                f"coalesced={co.get('coalesced_dispatches', 0)};"
                f"stranded={co.get('stranded_handles', 0)}")
        # the §8.3 acceptance gate: coalescing must measurably cut the
        # reconfiguration count on the same bursty trace, strand nothing,
        # and lose no work
        assert co.get("stranded_handles", 0) == 0, co
        assert co["n_done"] == nc["n_done"], (co, nc)
        assert co.get("reconfigs", 0) < nc.get("reconfigs", 0), (
            f"coalescing did not reduce reconfigs: "
            f"{co.get('reconfigs')} vs {nc.get('reconfigs')}")
    if "static2" in by_arm and "elastic" in by_arm:
        s2, el = by_arm["static2"], by_arm["elastic"]
        ratio = (el["turnaround_p99_s"] /
                 max(s2["turnaround_p99_s"], 1e-9))
        saved = s2["region_seconds"] - el["region_seconds"]
        printer(f"elastic/headline,{el['turnaround_p99_s']*1e6:.0f},"
                f"p99_vs_static2={ratio:.2f}x;"
                f"region_s_saved={saved:.2f}")
    return results
