"""The traffic generators are deterministic per seed, and every seed gets
the same work in another order."""
import json
import os
import sys
from collections import Counter

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench.gen import paper_mix  # noqa: E402


def traffic(name):
    with open(os.path.join(ROOT, "bench", "traffic", name + ".json")) as f:
        return json.load(f)


def test_same_seed_same_schedule_large_seed():
    t = traffic("prio5")
    seed = 2 ** 33 + 12345
    a = paper_mix.generate(t, seed, 5.0)
    b = paper_mix.generate(t, seed, 5.0)
    assert a == b
    assert a != paper_mix.generate(t, seed + 1, 5.0)


def test_seeds_share_the_amount_of_work():
    t = traffic("prio5")
    a = paper_mix.generate(t, 1, 5.0)
    b = paper_mix.generate(t, 2, 5.0)
    assert sorted(r["size"] for r in a) == sorted(r["size"] for r in b)
    assert Counter(r["kernel"] + str(r["iters"]) for r in a) == \
        Counter(r["kernel"] + str(r["iters"]) for r in b)
    assert sorted(r["priority"] for r in a) == sorted(r["priority"] for r in b)
    assert [r["size"] for r in a] != [r["size"] for r in b]
    gaps_a = sorted(y["due_s"] - x["due_s"] for x, y in zip(a, a[1:]))
    gaps_b = sorted(y["due_s"] - x["due_s"] for x, y in zip(b, b[1:]))
    assert max(abs(x - y) for x, y in zip(gaps_a, gaps_b)) < 0.05


def test_mix_follows_the_traffic_file():
    t = traffic("prio5")
    reqs = paper_mix.generate(t, 9, 10.0)
    assert abs(len(reqs) - t["rate_per_s"] * 10) <= 2
    assert all(0 <= r["due_s"] < 10.0 for r in reqs)
    lo, hi = t["size_px"]
    assert all(lo <= r["size"] <= hi for r in reqs)
    assert {r["priority"] for r in reqs} == set(t["priorities"])
    assert sum(r["checked"] for r in reqs) == t["check_sample"]
    one = paper_mix.generate(traffic("prio1"), 9, 10.0)
    assert {r["priority"] for r in one} == {4}
    assert [r["size"] for r in one] == [r["size"] for r in reqs]


def test_bursts_arrive_together():
    t = dict(traffic("prio5"), burst=4)
    reqs = paper_mix.generate(t, 5, 4.0)
    dues = Counter(r["due_s"] for r in reqs)
    assert set(dues.values()) == {t["burst"]}
