"""The five readers of the program's spans (``bench/spans.py``): the four
that read the tracer's ring on a synthetic cell, the device one on a
small profiler trace written in the layout a TPU v5e session records,
none of them raising on a program that records no such span, and all
four ring readers in the result line of a tiny traced run on the CPU."""
import json
import os
import shutil
import sys
import time
from types import SimpleNamespace

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import harness  # noqa: E402
from bench.harness import load_module  # noqa: E402
from bench.spans import overlap_ns  # noqa: E402
from bench.trace import Trace  # noqa: E402
from repro.obs.tracer import TraceEvent  # noqa: E402

CELLS = ["paper-blur-2rr.prio5", "paper-blur-2rr.prio1"]
RING = {"issue_ms_per_chunk.paper": 2.0, "wait_ms_per_chunk.paper": 5.0,
        "readback_ms_per_task.paper": 3.0, "event_lag_p95_ms.paper": 19.0}
DEVICE = "idle_with_work_share.paper"


def reader(name):
    return load_module(os.path.join(ROOT, "bench", "metrics", name + ".py"),
                       "t_" + name.replace(".", "_"))


def ev(kind, track, dur=0.0, **attrs):
    return TraceEvent(0.0, kind, track, 1, dur, attrs or None)


def ring_events():
    r0, r1, s = ("region", 0), ("region", 1), ("sched", 0)
    evs = [ev("issue", r0, 0.001), ev("issue", r1, 0.003),
           ev("wait", r0, 0.004), ev("wait", r1, 0.006),
           ev("readback", r0, 0.003), ev("run", r0, 0.5),
           ev("dispatch", s, 0.0002), ev("done", r0)]
    # handle lags 1..20 ms: the nearest-rank 95th is the 19th
    evs += [ev("handle", s, 0.0001, lag_s=i / 1e3, event="task_done")
            for i in range(20, 0, -1)]
    # a span of another timeline under a region kind counts nowhere
    evs.append(ev("wait", ("cluster", 0), 9.0))
    return evs


@pytest.mark.parametrize("name", sorted(RING))
def test_ring_reader_known_value(name):
    cell = SimpleNamespace(events=ring_events(), trace=None)
    assert reader(name).read(cell) == pytest.approx(RING[name])


def event(meta, start_ns, dur_ns):
    return (f"events {{ metadata_id: {meta} offset_ps: {start_ns * 1000} "
            f"duration_ps: {dur_ns * 1000} }}")


def xspace(spans=True) -> str:
    # window [1000, 11000) ns; the chip runs [2000,4000) and [6000,7000)
    mods = [event(1, 2000, 2000), event(1, 6000, 1000)]
    md = {1: "jit_chunk(1)", 2: "bench.window", 3: "region0.issue",
          4: "region1.wait", 5: "region0.readback", 6: "sched0.handle",
          7: "region0.prepare", 8: "np.asarray(jax.Array)"}
    # region spans: [1500,2500) [3000,5000) [4500,5900) [10500,12000);
    # idle while one is open: 500 + 1900 + 500 of the window's 10000
    r0 = [event(3, 1500, 1000), event(5, 4500, 1400), event(7, 10500, 1500)]
    r1 = [event(4, 3000, 2000)]
    sched = [event(6, 8000, 1000)]
    if not spans:
        r0, r1, sched = [event(8, 4500, 1400)], [], []

    def meta(keys):
        return "\n".join(f'event_metadata {{ key: {k} value {{ id: {k} '
                         f'name: "{md[k]}" }} }}' for k in keys)

    return f"""
planes {{ id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Modules" timestamp_ns: 0 {' '.join(mods)} }}
  {meta([1])}
}}
planes {{ id: 2 name: "/host:CPU"
  lines {{ id: 3 name: "python3" timestamp_ns: 0 {event(2, 1000, 10000)} }}
  lines {{ id: 4 name: "region-0" timestamp_ns: 0 {' '.join(r0)} }}
  lines {{ id: 5 name: "region-1" timestamp_ns: 0 {' '.join(r1)} }}
  lines {{ id: 6 name: "bench-scheduler" timestamp_ns: 0 {' '.join(sched)} }}
  {meta([2, 3, 4, 5, 6, 7, 8])}
}}
"""


def trace_of(text):
    from jax.profiler import ProfileData

    return Trace(ProfileData.from_text_proto(text).planes)


def test_device_reader_known_value():
    trace = trace_of(xspace())
    cell = SimpleNamespace(events=[], trace=trace)
    assert reader(DEVICE).read(cell) == pytest.approx(29.0)
    # idle_share.paper reads 70 %: 29 points of it had a region at work
    assert reader("idle_share.paper").read(cell) == pytest.approx(70.0)
    # the idle gaps are named by the program's spans
    gaps = trace.idle_gaps()
    assert gaps[0] == ["sched0.handle@bench-scheduler", pytest.approx(4e-6)]
    assert gaps[1] == ["region0.readback@region-0", pytest.approx(2e-6)]


def test_overlap_of_merged_intervals():
    assert overlap_ns([(0, 10), (20, 30)], [(5, 25)]) == 10
    assert overlap_ns([(0, 10)], [(10, 20)]) == 0
    assert overlap_ns([], [(0, 5)]) == 0


@pytest.mark.parametrize("name", sorted(RING) + [DEVICE])
def test_reader_reads_nothing_without_the_spans(name):
    """The parent's program records no such span: nothing to read, and no
    exception."""
    old = [ev("chunk", ("region", 0), 0.002), ev("dispatch", ("sched", 0)),
           ev("run", ("region", 0), 0.01)]
    for trace in (None, trace_of(xspace(spans=False))):
        cell = SimpleNamespace(events=old, trace=trace)
        assert reader(name).read(cell) is None


def test_entries_name_the_paper_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    for name in list(RING) + [DEVICE]:
        m = per_layer[name]
        assert m["workloads"] == CELLS and m["moves"] == "turnaround_p50_ms"
        assert os.path.isfile(os.path.join(ROOT, "bench", "metrics",
                                           name + ".py"))


def test_tiny_traced_run_reports_the_ring_readers(tmp_path):
    """A traced run of a tiny cell on the CPU: the ring readers report,
    the device ones find no chip and are left out."""
    root = str(tmp_path / "checkout")
    shutil.copytree(os.path.join(ROOT, "bench"), os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    with open(os.path.join(ROOT, "bench", "traffic", "prio5.json")) as f:
        traffic = json.load(f)
    traffic.update(rate_per_s=6.0, size_px=[100, 128], image_bank=2,
                   check_sample=2)
    with open(os.path.join(root, "bench", "traffic", "tiny.json"), "w") as f:
        json.dump(traffic, f)
    name = "paper-blur-2rr.tiny"
    bench["workloads"].append({"name": name, "config": "paper-blur-2rr",
                               "traffic": "tiny", "chips": 1, "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"].append(name)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    old = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(root, "jax-cache")
    try:
        res = harness.run(name, 2 ** 32 + 11, 1.0, True, time.perf_counter(),
                          root=root, require_tpu=False, log=lambda msg: None)
    finally:
        if old is None:
            del os.environ["JAX_COMPILATION_CACHE_DIR"]
        else:
            os.environ["JAX_COMPILATION_CACHE_DIR"] = old
    assert res["correct"] is True, res["checks"]
    m = res["metrics"]
    for k in RING:
        assert m[k]["unit"] == "ms" and m[k]["value"] >= 0.0, k
    assert DEVICE not in m      # no chip: no device to be idle
