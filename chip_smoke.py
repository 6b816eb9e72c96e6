"""Chip smoke: the preemptive region fabric's main paths, once, on a TPU.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # four chips: the multi-shell cluster

One chip runs three phases in this one process:

1. paper path — the paper's §6 task mix (Median Blur x1/2/3, Gaussian
   Blur x1; 30 tasks, 5 priorities, seed 15) at its image sizes of
   200-600 px through ``Scheduler`` over a two-region ``Shell`` with the
   pipelined engine; every other task is armed to stop at its first chunk
   boundary (a deterministic mid-task preemption) and every output is
   checked against ``kernels/blur/ref.py``;
2. token serving — ``serve_decode(lm="attention")`` at Mistral 7B's
   attention widths (``serving.attention.MISTRAL_7B``: d_model 4096, 32/8
   heads of 128, vocab 32000, 2048 positions in 16-position pages) with a
   checkpoint preemption in every decode round, each stream checked
   against ``attention_oracle_stream``; then the compiled flash-prefill
   and paged-decode kernels at those shapes against their f32 references;
3. megakernel refusal — ``Shell(engine="megakernel")`` must raise a clear
   error on a TPU, chosen from the platform.

``--chips 4`` runs only the paper mix over four shells, one per chip,
with forced cross-chip checkpoint migrations, against the same trace on
one shell.

Earlier lines report each phase; the last line, only when every phase
passed on a TPU, is ``{"ok": true, "device": {...}}``.  With no TPU, or on
any failure, the script exits non-zero without that line.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

import numpy as np  # noqa: E402

# the paper's §6 mix: pseudo-kernel -> (registered kernel, iterations)
MIX = {"MedianBlur": ("MedianBlur", 1), "MedianBlur2": ("MedianBlur", 2),
       "MedianBlur3": ("MedianBlur", 3), "GaussianBlur": ("GaussianBlur", 1)}
N_TASKS, SEED, ARRIVAL_T_S = 30, 15, 1.0
SIZES = (200, 600)          # the paper's image sizes, inclusive
GAUSSIAN_ATOL = 1e-5        # f32 3x3 weighted sum: summation order only
# kernel vs f32 reference, O(1) activations.  The kernels contract at f32
# (HIGHEST); at the default one-bf16-pass precision they missed by ~5e-3.
ATTENTION_ATOL = 1e-4
SERVE_SEQS, SERVE_PROMPT, SERVE_NEW = 6, 1024, 12


def log(msg: str):
    print(f"[chip_smoke] {msg}", flush=True)


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str):
    if not ok:
        raise SmokeFailure(what)


def paper_tasks(seed: int = SEED):
    """The §6 mix as fresh ``Task`` objects: ``(task, image, iters, kind)``
    per task, in arrival order."""
    from repro.controller.kernels import get_kernel
    from repro.core.task import generate_random_tasks
    from repro.kernels.blur.tasks import make_image

    rng = np.random.default_rng(seed)
    meta = {}

    def args(rng, pseudo):
        kernel, iters = MIX[pseudo]
        size = int(rng.integers(SIZES[0], SIZES[1] + 1))
        img = make_image(rng, size)
        bundle = get_kernel(kernel).bundle(img, np.zeros_like(img), H=size,
                                           W=size, iters=iters)
        meta[id(bundle)] = (img, iters, kernel)
        return bundle

    tasks = generate_random_tasks(rng, list(MIX), N_TASKS, ARRIVAL_T_S, args)
    out = []
    for t in tasks:
        img, iters, kernel = meta[id(t.args)]
        t.kernel = kernel
        out.append((t, img, iters, "median" if kernel == "MedianBlur"
                    else "gaussian"))
    return out


def check_modes(modes: set, phase: str):
    """Every region ran its Pallas kernels in the mode this backend
    resolves to (``main`` has already required that to be compiled)."""
    from repro.kernels.pallas_support import pallas_mode

    check(modes == {pallas_mode()}, f"{phase}: region pallas modes {modes}")


def phase_paper():
    import jax
    import jax.numpy as jnp

    from repro.core.scheduler import Scheduler, SchedulerConfig
    from repro.core.shell import Shell
    from repro.kernels.blur.ref import iterated_blur_ref
    from repro.kernels.blur.tasks import result_image

    trace = paper_tasks()
    for i, (t, *_rest) in enumerate(trace):
        if i % 2:
            t.preempt_at_boundary = 1   # stop at the first chunk boundary
    shell = Shell(n_regions=2, simulate_partial_s=0.0)
    try:
        t0 = time.perf_counter()
        rep = Scheduler(shell, SchedulerConfig()).run(
            [t for t, *_ in trace], quiet=True)
        wall = time.perf_counter() - t0
        modes = {r.stats.pallas_mode for r in shell.regions}
        eng = shell.engine.stats
    finally:
        shell.shutdown()
    check(rep["n_done"] == N_TASKS, f"paper: {rep['n_done']}/{N_TASKS} done")
    worst = 0.0
    for t, img, iters, kind in trace:
        got = result_image(t, iters)
        ref = np.asarray(iterated_blur_ref(jnp.asarray(img), iters, kind))
        if kind == "median":
            check(np.array_equal(got, ref),
                  f"paper: task #{t.tid} median x{iters} differs from ref")
        else:
            err = float(np.max(np.abs(got - ref)))
            worst = max(worst, err)
            check(err <= GAUSSIAN_ATOL,
                  f"paper: task #{t.tid} gaussian err {err} > "
                  f"{GAUSSIAN_ATOL}")
    preemptions = sum(t.n_preemptions for t, *_ in trace)
    check(preemptions >= 1, "paper: no mid-task preemption happened")
    check_modes(modes, "paper")
    sizes = sorted({img.shape[0] - 2 for _, img, _, _ in trace})
    log(f"paper: {rep['n_done']}/{N_TASKS} tasks match ref (median "
        f"bitwise, gaussian max err {worst:.3g} <= {GAUSSIAN_ATOL}); "
        f"padded sizes {sizes}; preemptions {preemptions}; pallas_mode "
        f"{sorted(modes)}; cold_compiles {eng.cold_compiles}, "
        f"prefetch_compiles {eng.prefetch_compiles}, total_compile_s "
        f"{eng.total_compile_s:.3f}; wall_s {wall:.3f}; device "
        f"{jax.devices()[0].device_kind}")


def phase_serving():
    import jax
    import jax.numpy as jnp

    from repro.kernels.decode_attention.ops import (gather_kv_pages,
                                                    paged_decode_attention)
    from repro.kernels.decode_attention.ref import decode_attention_ref
    from repro.kernels.flash_attention.ops import flash_attention
    from repro.kernels.flash_attention.ref import attention_ref
    from repro.launch.serve import serve_decode
    from repro.serving.attention import MISTRAL_7B

    p = MISTRAL_7B
    t0 = time.perf_counter()
    rep = serve_decode(lm="attention", attn_params=p, n_sequences=SERVE_SEQS,
                       prompt_len=SERVE_PROMPT, max_new=SERVE_NEW,
                       preempt_every=1, seed=0, verify=True, quiet=True)
    wall = time.perf_counter() - t0
    check(rep["n_finished"] == SERVE_SEQS,
          f"serving: {rep['n_finished']}/{SERVE_SEQS} done")
    check(rep["decode_preemptions"] >= 1,
          "serving: no mid-decode preemption happened")
    rc = rep["reconfig"]
    modes = {r["pallas_mode"] for r in rc["regions"].values()}
    check_modes(modes, "serving")
    log(f"serving: {SERVE_SEQS}/{SERVE_SEQS} attention streams equal the "
        f"oracle at d_model {p.d_model}, {p.n_heads}/{p.kv_heads} heads x "
        f"{p.head_dim}, vocab {p.vocab}, max_ctx {p.max_ctx}, pages of "
        f"{p.block_size}; prompts up to {SERVE_PROMPT} tokens; "
        f"{rep['tokens_out']} tokens, {rep['decode_rounds']} decode rounds, "
        f"{rep['decode_preemptions']} mid-decode preemptions; pallas_mode "
        f"{sorted(modes)}; cold_compiles {rc['cold_compiles']}, "
        f"total_compile_s {rc['total_compile_s']:.3f}; wall_s {wall:.3f}")

    # the two kernels at the serving shapes against their references
    key = jax.random.PRNGKey(SEED)
    kq, kk, kv, kd = jax.random.split(key, 4)
    C, P = p.block_size, p.max_ctx
    q = jax.random.normal(kq, (1, p.n_heads, C, p.head_dim))
    k = jax.random.normal(kk, (1, p.kv_heads, P, p.head_dim))
    v = jax.random.normal(kv, (1, p.kv_heads, P, p.head_dim))
    off = P // 2 - C
    got = flash_attention(q, k, v, causal=True, bq=C, q_offset=off)
    # queries at [off, off + C) are the last C of the first off + C keys
    with jax.default_matmul_precision("float32"):
        ref = attention_ref(q, k[:, :, :off + C], v[:, :, :off + C])
    err_f = float(jnp.max(jnp.abs(got - ref)))
    check(err_f <= ATTENTION_ATOL,
          f"serving: flash prefill err {err_f} > {ATTENTION_ATOL}")

    S, T = 4, p.blocks_per_seq
    nb = S * T + 1
    ks = jax.random.split(kd, 3)
    pool_shape = (nb, p.block_size, p.kv_heads, p.head_dim)
    k_pool = jax.random.normal(ks[0], pool_shape)
    v_pool = jax.random.normal(ks[1], pool_shape)
    tables = jnp.asarray(1 + np.random.default_rng(SEED).permutation(
        nb - 1)[:S * T].reshape(S, T), jnp.int32)
    pos = jnp.asarray([1, C + 1, P // 2 + 3, P], jnp.int32)
    qd = jax.random.normal(ks[2], (S, p.n_heads, 1, p.head_dim))
    got = paged_decode_attention(qd, k_pool, v_pool, tables, pos)
    k_lin = gather_kv_pages(k_pool, tables)
    v_lin = gather_kv_pages(v_pool, tables)
    with jax.default_matmul_precision("float32"):
        ref = jnp.concatenate([
            decode_attention_ref(qd[b:b + 1], k_lin[b:b + 1],
                                 v_lin[b:b + 1], pos[b]) for b in range(S)])
    err_d = float(jnp.max(jnp.abs(got - ref)))
    check(err_d <= ATTENTION_ATOL,
          f"serving: paged decode err {err_d} > {ATTENTION_ATOL}")
    log(f"serving kernels: flash prefill max err {err_f:.3g}, paged decode "
        f"max err {err_d:.3g} (<= {ATTENTION_ATOL})")


def phase_megakernel():
    from repro.core.shell import Shell

    try:
        shell = Shell(n_regions=1, engine="megakernel", prefetch=False)
    except RuntimeError as e:
        check("host-mappable" in str(e), f"megakernel: unclear error {e}")
        log(f"megakernel: refused on this platform ({e})")
        return
    shell.shutdown()
    raise SmokeFailure("megakernel: a megakernel shell was built on a TPU")


def phase_cluster(n_chips: int):
    """The paper mix over one shell per chip, >= 2 forced cross-chip
    checkpoint migrations, bit-identical to the same trace on one shell."""
    import jax

    from repro.cluster.frontend import ClusterFrontend
    from repro.core.scheduler import Scheduler, SchedulerConfig
    from repro.core.shell import Shell

    devices = jax.devices()
    ref_trace = paper_tasks()
    one = Shell(n_regions=1, devices=[devices[0]])
    try:
        rep = Scheduler(one, SchedulerConfig()).run(
            [t for t, *_ in ref_trace], quiet=True)
    finally:
        one.shutdown()
    check(rep["n_done"] == N_TASKS, f"one shell: {rep['n_done']} done")
    want = [t.result for t, *_ in ref_trace]

    trace = paper_tasks()
    t0 = time.perf_counter()
    fe = ClusterFrontend(n_shells=n_chips, regions_per_shell=1,
                         rebalance=False)
    try:
        shell_dev = [n.shell.regions[0].device for n in fe.nodes]
        check(len(set(shell_dev)) == n_chips,
              f"cluster: shells share devices {shell_dev}")
        # the longest tasks are the migration victims: each is armed to
        # stop at its first chunk boundary and moves with its checkpoint
        order = sorted(range(N_TASKS), key=lambda i: -trace[i][2]
                       * trace[i][1].shape[0])
        handles, moved = {}, []
        for i in order:
            t = trace[i][0]
            handles[i] = fe.submit(t)
            if len(moved) < 2 and fe._migrate_at_boundary(t.tid, 1,
                                                          timeout=300.0):
                moved.append(i)
        results = [handles[i].result(timeout=600.0) for i in range(N_TASKS)]
        rep = fe.report()
        hops = [handles[i].node_history for i in moved]
        ran_on = {n.node_id: rep["per_shell"][n.node_id]["n_done"]
                  for n in fe.nodes}
        # the devices each task's result buffers sat on, against the
        # device of the shell that finished it
        placed = {i: (shell_dev[handles[i].node_history[-1]].id,
                      handles[i].task.result_devices)
                  for i in range(N_TASKS)}
    finally:
        fe.shutdown()
    wall = time.perf_counter() - t0
    check(len(moved) >= 2, f"cluster: {len(moved)} migrations completed")
    check(all(len(set(h)) == 2 for h in hops), f"cluster: hops {hops}")
    check(rep["migrations_completed"] >= 2,
          f"cluster: report says {rep['migrations_completed']} migrations")
    check(all(v >= 1 for v in ran_on.values()),
          f"cluster: tasks done per shell {ran_on}")
    off_shell = {i: v for i, v in placed.items() if v[1] != {v[0]}}
    check(not off_shell, f"cluster: (shell device, result devices) of "
          f"tasks off their shell's device: {off_shell}")
    for i, (got, ref) in enumerate(zip(results, want)):
        check(all(np.array_equal(a, b) for a, b in zip(got, ref)),
              f"cluster: task {i} differs from the one-shell trace")
    log(f"cluster: {N_TASKS} tasks over {n_chips} shells on devices "
        f"{[d.id for d in shell_dev]} (done per shell {ran_on}); "
        f"{len(moved)} cross-chip checkpoint migrations, hops {hops}; "
        f"every result buffer on its shell's chip; outputs bit-identical "
        f"to one shell; wall_s {wall:.3f}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    import jax

    from repro.compile_cache import enable_compile_cache
    from repro.kernels.pallas_support import pallas_mode

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"[chip_smoke] no TPU: JAX found {dev.platform} devices",
              file=sys.stderr)
        return 2
    check(pallas_mode() == "compiled", "Pallas would interpret on this TPU")
    if len(devices) < args.chips:
        print(f"[chip_smoke] --chips {args.chips} needs that many TPUs; "
              f"found {len(devices)}", file=sys.stderr)
        return 2
    cache = enable_compile_cache()
    log(f"device {dev.device_kind} x{len(devices)}; compile cache {cache}")
    t0 = time.perf_counter()
    if args.chips == 4:
        phase_cluster(4)
    else:
        phase_paper()
        phase_serving()
        phase_megakernel()
    log(f"all phases passed in {time.perf_counter() - t0:.3f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
