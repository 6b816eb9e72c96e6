"""The paper's blur tasks as a driver hands them to the program: inputs
made from the seed, ``Task`` objects, the warm-up set, and the comparison
of finished outputs with the plain reference (``bench/refs/blur.py``).

Inputs are crops of a bank of random images made in one call from the
seed, zero-padded as the task layout asks: a ``[H+2, W+2]`` ping buffer
with the image in its top-left ``size x size`` interior, its side rounded
up to a multiple of 128 (the blur kernel's lane tile), and an all-zero
pong buffer.  Iteration ``k`` reads ping when ``k`` is even, so the
output is pong after an odd number of passes and ping after an even one.
"""
from __future__ import annotations

import math
import time

import numpy as np

PAD = 128
KIND = {"MedianBlur": "median", "GaussianBlur": "gaussian"}


def padded_side(size: int) -> int:
    return int(math.ceil(size / PAD) * PAD)


class BlurWorkload:
    def __init__(self, requests: list, seed: int, traffic: dict):
        hi = int(traffic["size_px"][1])
        rng = np.random.default_rng([seed, 2 ** 32 - 1])
        self.bank = rng.random((int(traffic["image_bank"]), hi, hi),
                               dtype=np.float32)
        self.requests = requests
        self.requests_by_i = {r["i"]: r for r in requests}
        self._zeros: dict = {}

    def image(self, req: dict) -> np.ndarray:
        size, side = req["size"], padded_side(req["size"])
        img = np.zeros((side + 2, side + 2), np.float32)
        img[1:size + 1, 1:size + 1] = self.bank[req["bank"], :size, :size]
        return img

    def task(self, req: dict, **fields):
        from repro.controller.kernels import get_kernel
        from repro.core.task import Task

        img = self.image(req)
        pong = self._zeros.setdefault(img.shape, np.zeros_like(img))
        bundle = get_kernel(req["kernel"]).bundle(
            img, pong, H=req["size"], W=req["size"], iters=req["iters"])
        return Task(kernel=req["kernel"], args=bundle,
                    priority=req["priority"], **fields)

    def warm_requests(self) -> list:
        """One request per (kernel, padded side) the traffic uses: the
        shapes, and so the programs, of the window and no others."""
        seen = {}
        for r in self.requests:
            seen.setdefault((r["kernel"], padded_side(r["size"])), r)
        return [dict(r, iters=1, priority=0) for r in seen.values()]

    # -- the comparison that decides ``correct`` --------------------------
    def compare(self, answers: dict, dtype=None) -> dict:
        """Recompute every checked request with the reference and compare.

        ``answers`` maps a request index to the image the program returned
        (None where the answer never came).  ``dtype`` computes the
        reference in another precision (the control).  Returns the numbers
        compared, each ``[value, limit]``."""
        import jax.numpy as jnp

        from bench.refs.blur import blur

        worst = {"median": 0.0, "gaussian": 0.0}
        missing = 0
        for i, got in sorted(answers.items()):
            if got is None:
                missing += 1
                continue
            req = self.requests_by_i[i]
            kind = KIND[req["kernel"]]
            ref = np.asarray(blur(jnp.asarray(self.image(req)), req["iters"],
                                  kind))
            if dtype is not None:
                got = np.asarray(blur(jnp.asarray(self.image(req)),
                                      req["iters"], kind, dtype=dtype))
            err = float(np.max(np.abs(got.astype(np.float64) - ref)))
            worst[kind] = max(worst[kind], err)
        return {"median_max_abs_err": [worst["median"], MEDIAN_LIMIT],
                "gaussian_max_abs_err": [worst["gaussian"], GAUSSIAN_LIMIT],
                "answers_missing": [missing, 0]}


def output_image(result, iters: int) -> np.ndarray:
    """The blurred image in a finished task's ``(ping, pong)`` result."""
    ping, pong = result
    return np.asarray(pong if iters % 2 == 1 else ping)


# A median is a selection: the program's output must equal the reference
# bit for bit.
MEDIAN_LIMIT = 0.0
# Gaussian: f32 weighted sums in the kernel's order.  The program read 0 on
# every seed tried; the bfloat16 control reads 3.2e-3 (PERF.md, section 6).
GAUSSIAN_LIMIT = 1e-5


def wait_all(handles: dict, deadline: float) -> None:
    """Wait for every handle until the ``perf_counter`` deadline."""
    for h in handles.values():
        h.wait(max(0.0, deadline - time.perf_counter()))
