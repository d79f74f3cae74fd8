"""The generator: one seed, one stream; another seed, the same work in
another order."""
import statistics

import numpy as np

from bench import traffic

CHAT = {"prompt_len": {"dist": "exponential", "mean": 69.5, "min": 1,
                       "max": 512, "buckets": [64, 128, 256, 512]},
        "output_len": {"dist": "exponential", "mean": 214.5, "min": 1,
                       "max": 1024},
        "arrival": {"process": "poisson", "rate_per_s": 9.0},
        "population": 40}
SEED = 2 ** 40 + 3            # a seed past 32 bits


def _key(s):
    return [(r["uid"], r.get("arrival_s"), r["max_new"], r["prompt"].tolist())
            for r in s]


def test_same_seed_same_requests():
    a = traffic.serve_schedule(CHAT, 10, SEED, 151936)
    b = traffic.serve_schedule(CHAT, 10, SEED, 151936)
    assert _key(a) == _key(b)
    assert _key(traffic.population(CHAT, SEED, 151936)) == \
        _key(traffic.population(CHAT, SEED, 151936))


def test_other_seed_same_work_other_order():
    a = traffic.serve_schedule(CHAT, 10, SEED, 151936)
    b = traffic.serve_schedule(CHAT, 10, SEED + 1, 151936)
    assert _key(a) != _key(b)
    assert len(a) == len(b) == 90
    for field in ("max_new",):
        assert sorted(r[field] for r in a) == sorted(r[field] for r in b)
    assert sorted(len(r["prompt"]) for r in a) == \
        sorted(len(r["prompt"]) for r in b)
    gaps_a = np.diff([r["arrival_s"] for r in a] + [10.0])
    gaps_b = np.diff([r["arrival_s"] for r in b] + [10.0])
    np.testing.assert_allclose(sorted(gaps_a), sorted(gaps_b), atol=1e-9)
    pa = traffic.population(CHAT, SEED, 151936)
    pb = traffic.population(CHAT, SEED + 1, 151936)
    assert _key(pa) != _key(pb)
    assert sorted((len(r["prompt"]), r["max_new"]) for r in pa) != []
    assert sorted(r["max_new"] for r in pa) == \
        sorted(r["max_new"] for r in pb)


def test_mix_follows_its_file():
    s = traffic.serve_schedule(CHAT, 100, 5, 1000)
    lens = [len(r["prompt"]) for r in s]
    # exponential, mean 69.5, rounded, then up to the buckets; by hand, 900
    # times 1 - e^(-64.5/69.5), then the next two bands and the rest:
    # 544.2, 214.1, 119.2, 22.5
    assert [lens.count(v) for v in (64, 128, 256, 512)] == [544, 214, 120, 22]
    outs = sorted(r["max_new"] for r in s)
    assert outs[0] >= 1 and outs[-1] == 1024
    assert abs(statistics.mean(outs) - 214.5) < 3.0          # clipped a bit
    assert outs[len(outs) // 2] in range(146, 152)   # median 214.5 ln 2
    assert all(0 <= t < 1000 for r in s for t in r["prompt"])
    assert s[0]["arrival_s"] == 0.0 and s[-1]["arrival_s"] < 100.0
    assert traffic.longest(CHAT["prompt_len"]) == 512
    assert traffic.lengths({"dist": "exponential", "median": 13, "min": 1,
                            "max": 128}, 1001)[500] == 13


def test_population_is_the_mix_in_flight():
    pop = traffic.population(CHAT, 7, 1000)
    assert len(pop) == 40
    assert min(r["uid"] for r in pop) == traffic.POPULATION_UID0
    assert {len(r["prompt"]) for r in pop} <= {64, 128, 256, 512}
    # an exponential's residual is the exponential again (memoryless)
    left = traffic.remaining(CHAT["output_len"], 2000)
    assert left[0] >= 1 and left[-1] < 1024
    assert abs(statistics.mean(left) - 214.5) < 12.0
    # a fixed length: in flight with 1 .. n - 1 tokens to go, alike
    fixed = {"dist": "exponential", "mean": 1e9, "min": 1, "max": 5}
    assert traffic.remaining(fixed, 8) == [1, 1, 2, 2, 3, 3, 4, 4]
    assert all(r["max_new"] >= 2 for r in pop)
    assert traffic.population(dict(CHAT, population=0), 7, 1000) == []
