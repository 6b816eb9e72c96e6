"""The harness is driven by data: a cell, a traffic mix and a metric added
as new files plus ``BENCHMARK.json`` entries run with no edit to a file
that is there.  And ``bench/run.py`` refuses a machine without a TPU,
printing no result.  Runs on the CPU (Pallas interprets) at a tiny size.
"""
import hashlib
import json
import os
import shutil
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import harness  # noqa: E402

TINY = {"rate_per_s": 6.0, "size_px": [100, 128], "image_bank": 2,
        "check_sample": 2}


def digest(root):
    out = {}
    for d, _, files in os.walk(os.path.join(root, "bench")):
        for f in files:
            p = os.path.join(d, f)
            if "__pycache__" not in p:
                with open(p, "rb") as fh:
                    out[os.path.relpath(p, root)] = hashlib.sha256(
                        fh.read()).hexdigest()
    return out


def add_tiny_cell(root, name, traffic_name="tiny", config="paper-blur-2rr",
                  **traffic):
    """A new traffic file and a new cell under ``root``, as a later PR
    would add them."""
    with open(os.path.join(ROOT, "bench", "traffic", "prio5.json")) as f:
        t = json.load(f)
    t.update(TINY, **traffic)
    with open(os.path.join(root, "bench", "traffic",
                           traffic_name + ".json"), "w") as f:
        json.dump(t, f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["workloads"].append({"name": name, "config": config,
                               "traffic": traffic_name, "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"].append(name)
    with open(path, "w") as f:
        json.dump(bench, f)
    return bench


def copy_root(tmp_path):
    root = str(tmp_path / "checkout")
    shutil.copytree(os.path.join(ROOT, "bench"), os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    return root


def run_tiny(root, name, trace=False, seconds=0.5):
    # a cache directory of the caller's: the harness then turns JAX's
    # persistent cache on nowhere in this test process
    old = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(root, "jax-cache")
    try:
        return harness.run(name, 2 ** 32 + 7, seconds, trace,
                           time.perf_counter(), root=root, require_tpu=False,
                           log=lambda msg: None)
    finally:
        if old is None:
            del os.environ["JAX_COMPILATION_CACHE_DIR"]
        else:
            os.environ["JAX_COMPILATION_CACHE_DIR"] = old


def test_cell_traffic_and_metric_added_as_files_only(tmp_path):
    root = copy_root(tmp_path)
    before = digest(root)
    bench = add_tiny_cell(root, "paper-blur-2rr.tiny")
    with open(os.path.join(root, "bench", "metrics", "tasks_done.py"),
              "w") as f:
        f.write("def read(cell):\n    return cell.n_done()\n")
    bench["end_to_end"].append({
        "name": "tasks_done", "unit": "tasks", "better": "higher",
        "bound": 0.01, "source": "host_clock",
        "workloads": ["paper-blur-2rr.tiny"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    after = digest(root)
    assert {k: after[k] for k in before} == before   # nothing edited

    res = run_tiny(root, "paper-blur-2rr.tiny")
    assert res["correct"] is True, res["checks"]
    assert res["failed"] == 0 and res["attempted"] == 3
    m = res["metrics"]
    assert m["tasks_done"] == {"value": 3.0, "unit": "tasks"}
    assert m["setup_s"]["value"] > 0
    assert set(m) >= {"turnaround_p50_ms", "urgent_wait_p50_ms", "setup_s"}
    assert list(res)[-1] == "checks"
    assert res["checks"]["median_max_abs_err"]["limit"] == 0.0


def test_run_without_a_tpu_prints_no_result(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    for root in (ROOT, copy_root(tmp_path)):
        p = subprocess.run(
            [sys.executable, os.path.join(root, "bench", "run.py"),
             "--workload", "paper-blur-2rr.prio5", "--seed", "1",
             "--seconds", "1", "--trace", "0"],
            cwd=root, env=env, capture_output=True, text=True, timeout=300)
        assert p.returncode != 0
        assert "{" not in p.stdout
