"""Benchmark orchestrator — one function per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--fast]

Prints ``name,us_per_call,derived`` CSV rows.  The scheduler sweep (paper §6)
runs the full busy/medium/idle x size x RRs x preemption grid and caches to
bench_sweep.json; roofline terms come from the dry-run artifacts (see
benchmarks/roofline.py, run in its own process because it needs 512 virtual
devices).
"""
from __future__ import annotations

import argparse
import warnings

warnings.filterwarnings("ignore")

# The consolidated summary sweeps up every ``bench_*.json`` on disk (see
# ``write_summary``), so a new bench arm only has to write its artifact —
# no registration list to keep in sync, and a ``--fast`` run that skips
# most arms still republishes every previously-cached artifact instead of
# shrinking the summary to the one bench it ran.


def _headline(d, prefix="", depth=0):
    """Flatten a bench artifact's scalar headlines: top-level numbers,
    booleans and short strings, plus one nested level (enough to pull
    ``gate.pass`` and per-arm ratios without dumping whole sweeps)."""
    out = {}
    if not isinstance(d, dict):
        return out
    for k, v in d.items():
        key = f"{prefix}{k}"
        if isinstance(v, bool) or isinstance(v, (int, float)):
            out[key] = v
        elif isinstance(v, str) and len(v) <= 64:
            out[key] = v
        elif isinstance(v, dict) and depth < 1:
            out.update(_headline(v, prefix=f"{key}.", depth=depth + 1))
    return out


def write_summary(path: str = "BENCH_SUMMARY.json",
                  printer=print) -> dict:
    """Consolidate every ``bench_*.json`` on disk into one artifact.

    A ``--fast`` run only regenerates a subset of benches; globbing (vs a
    fixed artifact list) republishes every cached artifact too, so the
    summary never shrinks to ``n_benches: 1``.  Each entry carries its
    own provenance — the artifact's embedded git sha/timestamp when it
    recorded one, its file mtime otherwise — so a summary mixing a fresh
    arm with stale cached ones says exactly which is which."""
    import glob
    import json
    import subprocess
    import time

    def _utc(epoch: float) -> str:
        return time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime(epoch))

    sha = None
    try:
        sha = subprocess.run(
            ["git", "rev-parse", "HEAD"], capture_output=True, text=True,
            timeout=10).stdout.strip() or None
    except (OSError, subprocess.SubprocessError):
        pass
    benches = {}
    for name in sorted(glob.glob("bench_*.json")):
        try:
            import os
            mtime = os.path.getmtime(name)
            with open(name) as f:
                data = json.load(f)
        except (OSError, ValueError):
            continue
        if isinstance(data, list):  # the sweep is a row list: count only
            entry = {"n_rows": len(data)}
            embedded_sha = embedded_ts = None
        else:
            entry = _headline(data)
            embedded_sha = data.get("git_sha")
            embedded_ts = data.get("timestamp")
        entry["artifact_git_sha"] = embedded_sha or sha
        entry["artifact_timestamp"] = embedded_ts or _utc(mtime)
        benches[name] = entry
    summary = {
        "git_sha": sha,
        "timestamp": _utc(time.time()),
        "n_benches": len(benches),
        "benches": benches,
    }
    with open(path, "w") as f:
        json.dump(summary, f, indent=1, default=str)
    printer(f"# consolidated summary: {path} "
            f"({len(benches)} bench artifacts, sha={sha and sha[:9]})")
    return summary


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="skip the full scheduler sweep if not cached")
    ap.add_argument("--no-cache", action="store_true")
    args = ap.parse_args()

    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    print("name,us_per_call,derived")

    # kernel microbenches first (cheap)
    from benchmarks import bench_kernels
    bench_kernels.emit()

    # reconfiguration costs (paper §6.3 partial-vs-full)
    from benchmarks import bench_reconfig
    bench_reconfig.measure()
    # async bitstream prefetch vs synchronous baseline
    bench_reconfig.measure_prefetch()

    # the paper's scheduler experiments
    from benchmarks import bench_overhead, bench_service_time, bench_throughput
    from benchmarks.harness import full_sweep
    import os

    # chunk-pipeline microbench (sync vs pipelined per-chunk dispatch
    # overhead + bit-identity gate); same fast-mode caching contract
    if args.fast and not os.path.exists("bench_chunk_pipeline.json"):
        print("chunk_pipeline/skipped,0,fast-mode")
    else:
        bench_overhead.measure_chunk_pipeline(use_cache=not args.no_cache)

    # flight-recorder overhead gate (traced vs untraced dispatch,
    # DESIGN.md §11); same fast-mode caching contract
    if args.fast and not os.path.exists("bench_tracer_overhead.json"):
        print("tracer_overhead/skipped,0,fast-mode")
    else:
        bench_overhead.measure_tracer_overhead(use_cache=not args.no_cache)

    # live-metrics registry overhead gate (metered vs bare dispatch,
    # DESIGN.md §12); same fast-mode caching contract
    if args.fast and not os.path.exists("bench_metrics_overhead.json"):
        print("metrics_overhead/skipped,0,fast-mode")
    else:
        bench_overhead.measure_metrics_overhead(use_cache=not args.no_cache)

    # scheduling-policy arm (fcfs vs edf vs wfq on one stream); like the
    # sweep, fast mode only reports it when already cached
    if args.fast and not os.path.exists("bench_policies.json"):
        print("policy/skipped,0,fast-mode")
    else:
        bench_service_time.measure_policies(use_cache=not args.no_cache)

    # elastic region-pool arm (static-1RR vs static-2RR vs autoscaled on a
    # bursty open-loop trace); same fast-mode caching contract
    if args.fast and not os.path.exists("bench_elastic.json"):
        print("elastic/skipped,0,fast-mode")
    else:
        bench_service_time.measure_elastic(use_cache=not args.no_cache)

    # cluster fabric arm (1-shell vs 2-shell vs 2-shell-with-migration on
    # the same bursty trace, DESIGN.md §7); same fast-mode caching contract
    if args.fast and not os.path.exists("bench_cluster.json"):
        print("cluster/skipped,0,fast-mode")
    else:
        bench_service_time.measure_cluster(use_cache=not args.no_cache)

    # token-serving arm (single-region vs prefill/decode-disaggregated
    # continuous batching, DESIGN.md §9); same fast-mode caching contract
    if args.fast and not os.path.exists("bench_decode.json"):
        print("decode/skipped,0,fast-mode")
    else:
        from benchmarks import bench_decode
        bench_decode.measure_decode(use_cache=not args.no_cache)

    if args.fast and not os.path.exists("bench_sweep.json"):
        print("sweep/skipped,0,fast-mode")
        write_summary()
        return
    sweep = full_sweep(repeats=2, use_cache=not args.no_cache)
    bench_service_time.emit(sweep)
    bench_throughput.emit(sweep)
    bench_overhead.emit(sweep)

    # roofline summary (if the extraction has been run)
    import json
    if os.path.exists("roofline_all.json"):
        with open("roofline_all.json") as f:
            rl = json.load(f)
        print("# roofline terms per (arch x shape) — seconds per step")
        for r in rl:
            if r.get("status") != "ok":
                continue
            t = r["terms_s"]
            print(f"roofline/{r['arch']}_{r['shape']},"
                  f"{max(t.values())*1e6:.0f},"
                  f"compute_ms={t['compute_s']*1e3:.3f};"
                  f"mem_ms={t['memory_s']*1e3:.3f};"
                  f"coll_ms={t['collective_s']*1e3:.3f};"
                  f"dominant={r['dominant'].split('_')[0]};"
                  f"useful={r['useful_flops_ratio']};"
                  f"frac={r['roofline_fraction']}")

    write_summary()


if __name__ == "__main__":
    main()
