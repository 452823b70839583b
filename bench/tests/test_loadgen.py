"""The traffic generator is a function of the seed, and every seed gets
the same sizes in the same order."""
import math

import loadgen
import spec as bspec

TRAFFIC = bspec.load_json(bspec.BENCH / "traffic" / "chat-1.json")


def _sizes(plan, n):
    return [plan.size(i) for i in range(n)]


def test_same_seed_same_requests():
    a = loadgen.Plan(TRAFFIC, 2**31 + 7, 32000)
    b = loadgen.Plan(TRAFFIC, 2**31 + 7, 32000)
    assert _sizes(a, 40) == _sizes(b, 40)
    assert all(a.prompt(i) == b.prompt(i) for i in range(5))


def test_seeds_share_the_sizes_in_one_order():
    a = loadgen.Plan(TRAFFIC, 1, 32000)
    b = loadgen.Plan(TRAFFIC, 2**31 + 11, 32000)
    assert a.warm == b.warm
    assert _sizes(a, 40) == _sizes(b, 40)
    # one shuffled order: neither set sorted, so lengths pair across
    # the law's range
    assert [p for p, _ in a.pool] != sorted(p for p, _ in a.pool)
    assert [o for _, o in a.pool] != sorted(o for _, o in a.pool)
    assert a.prompt(3) != b.prompt(3)
    # the KV cache, and so every program's shapes, are sized alike
    assert a.max_total() == b.max_total()


def test_sizes_follow_the_law():
    law = TRAFFIC["prompt_len"]
    q = loadgen.lognormal_quantiles(law, 16)
    assert q == sorted(q)
    assert law["min"] <= q[0] and q[-1] <= law["max"]
    median, _ = loadgen.lognormal(law)
    assert q[7] <= median <= q[8]
    # the lognormal has the published mean and deviation
    _, sigma = loadgen.lognormal(law)
    assert abs(median * math.exp(sigma ** 2 / 2) - law["mean"]) < 1e-9
    plan = loadgen.Plan(TRAFFIC, 5, 32000)
    p = plan.prompt(0)
    assert len(p) == plan.size(0)[0] and min(p) >= 1 and max(p) < 32000
