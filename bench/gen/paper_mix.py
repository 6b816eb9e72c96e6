"""The paper's task mix (arXiv 2209.04410 §6) as an open-loop schedule.

A traffic file for this generator holds:

- ``rate_per_s``: mean task arrivals per second;
- ``burst``: tasks that arrive together (1 = Poisson arrivals; n > 1 =
  bursts of n at Poisson burst arrivals of ``rate_per_s / n``);
- ``kernels``: the pseudo-kernels, each ``[registered kernel, iterations]``;
- ``size_px``: ``[lo, hi]``, image side drawn uniformly, inclusive;
- ``priorities``: the priority levels drawn from, uniformly;
- ``image_bank``: distinct random source images a run makes;
- ``check_sample``: tasks whose outputs the reference recomputes.

Every seed gets the same multiset of gaps, sizes, kernels and priorities
(stratified quantiles, each list in its own seeded order), so seeds
change the order of the work and not its amount.  Paper §4.3 draws
arrivals from U(0, T); a Poisson stream over the window is that, with the
task count set by the rate.
"""
from __future__ import annotations

import numpy as np


def _spread(values, n: int) -> list:
    """``n`` items cycling through ``values`` (equal shares)."""
    return [values[i % len(values)] for i in range(n)]


def generate(traffic: dict, seed: int, seconds: float) -> list:
    rate = float(traffic["rate_per_s"])
    burst = int(traffic.get("burst", 1))
    if rate <= 0 or burst < 1 or seconds <= 0:
        raise ValueError(f"bad traffic: rate {rate}, burst {burst}, "
                         f"seconds {seconds}")
    rng = np.random.default_rng(seed)
    n_bursts = max(1, int(round(rate * seconds / burst)))
    q = (np.arange(n_bursts) + 0.5) / n_bursts
    gaps = rng.permutation(-np.log1p(-q) * burst / rate)
    starts = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    due = np.repeat(starts, burst)
    n = len(due)

    lo, hi = traffic["size_px"]
    sizes = lo + np.floor((np.arange(n) + 0.5) / n * (hi - lo + 1))
    sizes = rng.permutation(sizes.astype(int))
    kernels = [tuple(k) for k in traffic["kernels"]]
    kind_of = [kernels[j] for j in rng.permutation(
        _spread(list(range(len(kernels))), n))]
    prio = rng.permutation(_spread(list(traffic["priorities"]), n))
    bank = rng.permutation(_spread(list(range(int(traffic["image_bank"]))),
                                   n))
    n_check = min(n, int(traffic["check_sample"]))
    checked = set(rng.choice(n, size=n_check, replace=False).tolist())
    return [{"i": i, "due_s": float(due[i]), "kernel": kind_of[i][0],
             "iters": int(kind_of[i][1]), "size": int(sizes[i]),
             "priority": int(prio[i]), "bank": int(bank[i]),
             "checked": i in checked}
            for i in range(n) if due[i] < seconds]
