"""The replaying serve runner: every seed gets the same work in the same
order, with its own tokens, and ``serve`` is left as it was."""
import numpy as np
import pytest

from bench import harness, traffic
from bench.runners import serve, serve_replay

MIX = {"prompt_len": {"dist": "exponential", "median": 1500, "min": 1,
                      "max": 4096, "buckets": [512, 1024, 2048, 4096]},
       "output_len": {"dist": "exponential", "median": 13, "min": 1,
                      "max": 128},
       "arrival": {"process": "poisson", "rate_per_s": 4.0},
       "population": 5}
SEED = 2 ** 40 + 3            # a seed past 32 bits


def _work(reqs):
    return [(r["uid"], r.get("arrival_s"), r["max_new"], len(r["prompt"]))
            for r in reqs]


@pytest.mark.parametrize("draw", ["serve_schedule", "population"])
def test_every_seed_same_work_in_one_order(draw):
    one = serve_replay.OneOrder(7)

    def get(gen, seed):
        args = (MIX, 30.0, seed, 65536) if draw == "serve_schedule" \
            else (MIX, seed, 65536)
        return getattr(gen, draw)(*args)

    a, b = get(one, SEED), get(one, SEED + 1)
    assert _work(a) == _work(b) == _work(get(traffic, 7))
    assert _work(a) != _work(get(traffic, SEED))
    # the seed still draws the tokens, from the generator's own streams
    assert any(not np.array_equal(x["prompt"], y["prompt"])
               for x, y in zip(a, b))
    for x, y in zip(get(one, 7), get(traffic, 7)):
        np.testing.assert_array_equal(x["prompt"], y["prompt"])
        assert x["prompt"].dtype == np.int32


def test_run_leaves_serve_drawing_from_the_generator(monkeypatch):
    seen = []

    def fake_run(rec, devices, **kw):
        seen.append(serve.traffic)
        raise RuntimeError("stop")

    monkeypatch.setattr(serve, "run", fake_run)
    cell = harness.find_cell("rwkv6-3b.serve-docs")
    rec = harness.Record(cell=cell, seed=SEED, seconds=1.0, trace=False,
                         peak={})
    with pytest.raises(RuntimeError):
        serve_replay.run(rec, [])
    assert isinstance(seen[0], serve_replay.OneOrder)
    assert seen[0].order_seed == cell.traffic["order_seed"]
    assert serve.traffic is traffic
