"""``correct`` comes out false when the timed path is broken, and the
control (the reference in a lower precision, put in the program's place)
fails the comparison.  The tests run on the CPU at a tiny size; the
harness skips its look for a chip and drives the rest of a run.

The same faults read at a cell's own size on the chip:

    python3 bench/tests/test_bench_faults.py --fault unchanged \\
        --workload paper-blur-2rr.prio5 --seed 1 --seconds 20

prints the run's result line, with the planted fault in the program.
"""
import argparse
import contextlib
import json
import os
import sys
import threading
import time
from unittest import mock

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src"), os.path.dirname(
    os.path.abspath(__file__))]

from test_bench_harness import add_tiny_cell, copy_root, run_tiny  # noqa

from bench import harness  # noqa: E402
from bench.blurmix import BlurWorkload  # noqa: E402
from bench.gen import paper_mix  # noqa: E402

# tasks a planted ``exchange`` fault sends to another shell, each at the
# first chunk boundary of its first launch, so the fault has a migration
# with a checkpoint to act on
FORCED_MIGRATIONS = 8


# -- the faults ------------------------------------------------------------
def _unchanged(stack):
    """Every blur step returns its state unchanged."""
    from repro.controller import kernels as K

    def unchanged(ctx, bufs, ints, floats):
        return ctx.finish(), bufs

    stack.enter_context(mock.patch.dict(K._REGISTRY, {
        name: K.KernelDef(**{**K.get_kernel(name).__dict__, "fn": unchanged})
        for name in ("MedianBlur", "GaussianBlur")}))


def _altered(stack):
    """A finished task's answer is altered where the region produces it."""
    from repro.core import region as R

    finish = R.Region._finish_done

    def altered(self, task, kd, bufs, t_busy0):
        finish(self, task, kd, bufs, t_busy0)
        result = [b.copy() for b in task.result]
        for b in result:
            b[1, 1] += 1e-3
        task.result = tuple(result)

    stack.enter_context(mock.patch.object(R.Region, "_finish_done", altered))


def _exchange(stack):
    """The exchange between chips left out: a migrated task resumes from
    its checkpoint's position, but the partial outputs never arrive (the
    spill reads back zeros).  The first ``FORCED_MIGRATIONS`` tasks are
    each moved at their first chunk boundary, so migrations happen."""
    from repro.cluster import frontend as F

    spill = F.ClusterFrontend._spill_roundtrip

    def lost(self, task, kind):
        c = spill(self, task, kind)
        if c is None:
            return None
        import jax

        zero = jax.tree_util.tree_map(np.zeros_like, c.payload)
        return F.Committed(c.seqno, c.context, zero, tid=c.tid)

    stack.enter_context(mock.patch.object(F.ClusterFrontend,
                                          "_spill_roundtrip", lost))
    stack.enter_context(mock.patch.object(harness, "open_loop",
                                          _migrating_open_loop))


def _migrating_open_loop(cell, driver, _open_loop=harness.open_loop):
    movers = []
    submit = driver.submit

    def submit_and_move(req, due):
        submit(req, due)
        if len(movers) < FORCED_MIGRATIONS:
            t = threading.Thread(target=driver.fe._migrate_at_boundary,
                                 args=(driver.handles[req["i"]].tid, 1, 60.0),
                                 daemon=True)
            t.start()
            movers.append(t)

    driver.submit = submit_and_move
    try:
        _open_loop(cell, driver)
    finally:
        driver.submit = submit
        for t in movers:
            t.join(90.0)


FAULTS = {"unchanged": _unchanged, "altered": _altered,
          "exchange": _exchange}


@contextlib.contextmanager
def planted(fault: str):
    with contextlib.ExitStack() as stack:
        FAULTS[fault](stack)
        yield


# -- the tests ---------------------------------------------------------------
TINY_CELLS = {"2rr": ("paper-blur-2rr.tiny", "paper-blur-2rr"),
              "4shell": ("paper-blur-4shell.tiny", "paper-blur-4shell")}


def tiny_root(tmp_path, which):
    """A checkout with one tiny cell added; the 4-shell cell's chunks are
    one row block, so even a tiny image has chunk boundaries to migrate
    at."""
    r = copy_root(tmp_path)
    name, config = TINY_CELLS[which]
    if which == "4shell":
        path = os.path.join(r, "bench", "configs", "tiny-4shell.json")
        with open(os.path.join(ROOT, "bench", "configs",
                               config + ".json")) as f:
            c = json.load(f)
        c.update(name="tiny-4shell", chunk_budget=1)
        with open(path, "w") as f:
            json.dump(c, f)
        with open(os.path.join(r, "BENCHMARK.json")) as f:
            bench = json.load(f)
        bench["configs"].append(dict(name="tiny-4shell",
                                     file="bench/configs/tiny-4shell.json"))
        with open(os.path.join(r, "BENCHMARK.json"), "w") as f:
            json.dump(bench, f)
        config = "tiny-4shell"
    add_tiny_cell(r, name, config=config,
                  rate_per_s=16.0 if which == "4shell" else 6.0)
    return r, name


def test_control_bf16_reference_fails():
    import jax.numpy as jnp

    traffic = dict(TINY_TRAFFIC)
    reqs = paper_mix.generate(traffic, 3, 2.0)
    work = BlurWorkload(reqs, 3, traffic)
    checked = {r["i"]: np.zeros(1) for r in reqs}
    kinds = {r["kernel"] for r in reqs}
    assert kinds == {"MedianBlur", "GaussianBlur"}
    exact = work.compare(checked, dtype=jnp.float32)
    assert all(v <= lim for v, lim in exact.values())
    control = work.compare(checked, dtype=jnp.bfloat16)
    med, gau = control["median_max_abs_err"], control["gaussian_max_abs_err"]
    assert med[0] > med[1] and gau[0] > gau[1]


TINY_TRAFFIC = {"rate_per_s": 8.0, "burst": 1, "size_px": [100, 128],
                "kernels": [["MedianBlur", 1], ["MedianBlur", 2],
                            ["MedianBlur", 3], ["GaussianBlur", 1]],
                "priorities": [0, 1, 2, 3, 4], "image_bank": 2,
                "check_sample": 16}


@pytest.mark.parametrize("which", ["2rr", "4shell"])
def test_sound_run_is_correct(tmp_path, which):
    root, name = tiny_root(tmp_path, which)
    res = run_tiny(root, name)
    assert res["correct"] is True, res["checks"]
    assert res["failed"] == 0


def unchanged_state_fails(tmp_path, which):
    root, name = tiny_root(tmp_path, which)
    with planted("unchanged"):
        res = run_tiny(root, name)
    assert res["correct"] is False
    assert res["failed"] == 0


def altered_answer_fails(tmp_path, which):
    root, name = tiny_root(tmp_path, which)
    with planted("altered"):
        res = run_tiny(root, name)
    assert res["correct"] is False
    assert res["checks"]["median_max_abs_err"]["value"] > 0


def test_step_that_returns_its_state_unchanged(tmp_path):
    unchanged_state_fails(tmp_path, "2rr")


def test_step_that_returns_its_state_unchanged_4shell(tmp_path):
    unchanged_state_fails(tmp_path, "4shell")


def test_answer_altered_where_it_is_produced(tmp_path):
    altered_answer_fails(tmp_path, "2rr")


def test_answer_altered_where_it_is_produced_4shell(tmp_path):
    altered_answer_fails(tmp_path, "4shell")


def test_exchange_between_chips_left_out(tmp_path):
    root, name = tiny_root(tmp_path, "4shell")
    with planted("exchange"):
        res = run_tiny(root, name, seconds=1.0)
    assert res["correct"] is False
    assert res["failed"] == 0


def control_readings(traffic_name: str, seed: int, seconds: float) -> dict:
    """The control at a cell's own size: every checked request of the
    cell's schedule for ``seed``, its answer computed by the reference in
    bfloat16.  Run on the chip for the readings in PERF.md:

        python3 -c "import sys; sys.path[:0] = ['bench/tests'];
        from test_bench_faults import control_readings;
        print(control_readings('prio5', 1, 20))"
    """
    import jax.numpy as jnp

    with open(os.path.join(ROOT, "bench", "traffic",
                           traffic_name + ".json")) as f:
        traffic = json.load(f)
    reqs = paper_mix.generate(traffic, seed, seconds)
    work = BlurWorkload(reqs, seed, traffic)
    return work.compare({r["i"]: np.zeros(1) for r in reqs if r["checked"]},
                        dtype=jnp.bfloat16)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="one run of a cell with a "
                                 "fault planted in the program")
    ap.add_argument("--fault", choices=sorted(FAULTS), required=True)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    t0 = time.perf_counter()
    try:
        with planted(args.fault):
            res = harness.run(args.workload, args.seed, args.seconds, False,
                              t0)
    except harness.CellError as e:
        print(f"[bench] {e}", file=sys.stderr, flush=True)
        return 2
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
