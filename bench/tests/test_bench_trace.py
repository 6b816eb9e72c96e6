"""The trace reduction (``bench/trace.py``) and the blur roofline reader
on a small profiler trace: an XSpace written in the layout a TPU v5e
session records (``XLA Modules`` / ``XLA Ops`` lines of a
``/device:TPU:0`` plane, host threads under ``/host:CPU``), with op names
as they appear there."""
import os
import sys
from types import SimpleNamespace

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import work  # noqa: E402
from bench.harness import load_module  # noqa: E402
from bench.trace import Trace, merge, op_label  # noqa: E402

BLUR_512 = ('%blur_block.3 = f32[32,512]{1,0:T(8,128)S(1)} custom-call('
            'f32[34,514]{1,0:T(8,128)S(1)} %constant_dynamic-slice_fusion.1), '
            'custom_call_target=\\"tpu_custom_call\\"')
WHILE = '%while.68 = (s32[8]{0:T(128)}, f32[514,514]{1,0:T(8,128)}) while(...)'


def event(meta, start_ns, dur_ns, line_ts=0):
    return (f"events {{ metadata_id: {meta} offset_ps: "
            f"{(start_ns - line_ts) * 1000} duration_ps: {dur_ns * 1000} }}")


def xspace() -> str:
    # window [1000, 11000) ns; programs [2000,4000) and [3000,5000)
    # overlap, [9000,12000) runs past the window's end
    mods = [event(1, 2000, 2000), event(1, 3000, 2000), event(2, 9000, 3000)]
    ops = [event(3, 2100, 1500), event(4, 2200, 1000), event(4, 3300, 500),
           event(5, 9100, 2000)]
    host = [event(6, 1000, 10000), event(7, 5200, 3000), event(8, 5100, 600)]
    md = {1: "jit_chunk(1)", 2: "jit_chunk(2)", 3: WHILE, 4: BLUR_512,
          5: "%copy.1 = f32[8]{0} copy(f32[8]{0} %p)", 6: "bench.window",
          7: "CommonPjRtLoadedExecutable::Execute", 8: "bench.submit"}

    def meta(keys):
        return "\n".join(f'event_metadata {{ key: {k} value {{ id: {k} '
                         f'name: "{md[k]}" }} }}' for k in keys)

    return f"""
planes {{ id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Modules" timestamp_ns: 0 {' '.join(mods)} }}
  lines {{ id: 2 name: "XLA Ops" timestamp_ns: 0 {' '.join(ops)} }}
  {meta([1, 2, 3, 4, 5])}
}}
planes {{ id: 2 name: "/host:CPU"
  lines {{ id: 3 name: "python" timestamp_ns: 0 {event(6, 1000, 10000)} }}
  lines {{ id: 4 name: "region-0" timestamp_ns: 0 {host[1]} {host[2]} }}
  {meta([6, 7, 8])}
}}
"""


@pytest.fixture(scope="module")
def trace():
    from jax.profiler import ProfileData

    return Trace(ProfileData.from_text_proto(xspace()).planes)


def test_busy_idle_and_window(trace):
    assert trace.window == (1000, 11000)
    assert trace.window_s == pytest.approx(1e-5)
    assert trace.busy_intervals(0) == [(2000, 5000), (9000, 11000)]
    assert trace.devices_used() == [0]
    assert trace.busy_s() == pytest.approx(5000e-9)
    assert 1 - trace.busy_s() / trace.window_s == pytest.approx(0.5)


def test_kernel_time_by_name(trace):
    calls = trace.op_events(work.BLUR_CALL)
    assert len(calls) == 2
    assert sum(e - s for s, e, _, _ in calls) == 1500
    assert work.blur_call_shape(calls[0][2]) == (32, 512)


def test_breakdown(trace):
    ops = dict(trace.top_ops())
    # the while is not a leaf: its body's blur calls are
    assert ops == {"blur_block f32[32,512]": pytest.approx(1500e-9),
                   "copy f32[8]": pytest.approx(1900e-9)}
    gaps = trace.idle_gaps()
    assert gaps[0] == ["CommonPjRtLoadedExecutable::Execute@region-0",
                       pytest.approx(4000e-9)]
    assert gaps[1][1] == pytest.approx(1000e-9)


def test_merge_and_labels():
    assert merge([(5, 6), (1, 3), (2, 4)]) == [(1, 4), (5, 6)]
    assert op_label(BLUR_512.replace('\\"', '"')) == "blur_block f32[32,512]"


def test_blur_roofline_reader(trace):
    reader = load_module(os.path.join(ROOT, "bench", "metrics",
                                      "blur_roofline.py"), "t_roofline")
    # one finished task, 480 px padded to 512, one pass
    cell = SimpleNamespace(
        trace=trace, peaks={"hbm_bytes_per_s": 819e9},
        requests=[{"i": 0, "size": 480, "iters": 1}],
        records=[{"i": 0, "t_done": 1.0}])
    live = work.blur_live_bytes(480) / work.blur_pass_bytes(512)
    need = 2 * work.blur_call_bytes(32, 512) * live
    want = need / 819e9 / 1500e-9 * 100
    assert reader.read(cell) == pytest.approx(want)
    assert 0 < want < 100
    cell.trace = None
    assert reader.read(cell) is None
