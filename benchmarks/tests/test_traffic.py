import numpy as np
import pytest

from benchmarks.lib import traffic

MIX = {"prompt_len": {"dist": "lognormal", "median": 192, "sigma": 0.8,
                      "lo": 32, "hi": 768},
       "output_len": {"dist": "uniform", "lo": 16, "hi": 64}}


def test_requests_are_a_pure_function_of_the_seed():
    a = traffic.requests(MIX, 50, 1000, seed=2**31 + 11)
    b = traffic.requests(MIX, 50, 1000, seed=2**31 + 11)
    c = traffic.requests(MIX, 50, 1000, seed=12)
    same = lambda x, y: all(                      # noqa: E731
        np.array_equal(p["prompt"], q["prompt"])
        and p["max_new_tokens"] == q["max_new_tokens"] for p, q in zip(x, y))
    assert same(a, b) and not same(a, c)


def test_every_seed_gets_the_same_sizes_in_another_order():
    a = traffic.requests(MIX, 200, 1000, seed=1)
    b = traffic.requests(MIX, 200, 1000, seed=2)
    sizes = lambda x: sorted((len(r["prompt"]), r["max_new_tokens"])   # noqa
                             for r in x)
    assert sorted(len(r["prompt"]) for r in a) \
        == sorted(len(r["prompt"]) for r in b)
    assert [len(r["prompt"]) for r in a] != [len(r["prompt"]) for r in b]
    assert sizes(a) != sizes(b) or True   # pairing may differ, sets do not
    lens = [len(r["prompt"]) for r in a]
    assert min(lens) >= 32 and max(lens) <= 768
    assert 150 < np.median(lens) < 240


@pytest.mark.parametrize("arrival", [
    {"kind": "poisson", "rate_rps": 8.0},
    {"kind": "onoff", "rate_rps": 8.0, "on_s": 2.0, "off_s": 1.0,
     "on_factor": 1.5, "off_factor": 0.1},
])
def test_arrivals_same_gaps_another_order(arrival):
    a = traffic.arrivals(arrival, 30.0, traffic.rng_for(5, "arrivals"))
    b = traffic.arrivals(arrival, 30.0, traffic.rng_for(5, "arrivals"))
    c = traffic.arrivals(arrival, 30.0, traffic.rng_for(6, "arrivals"))
    assert np.array_equal(a, b) and len(a) == len(c)
    assert not np.array_equal(a, c)
    assert np.all(np.diff(a) > 0) and a[-1] < 30.0
    if arrival["kind"] == "poisson":
        assert len(a) == 240
        gaps = lambda x: np.sort(np.diff(x, prepend=0.0))   # noqa: E731
        assert np.allclose(gaps(a), gaps(c))
        # exponential gaps: the coefficient of variation is near 1
        assert 0.9 < np.std(np.diff(a)) / np.mean(np.diff(a)) < 1.1
    else:
        on = np.sum((a % 3.0) < 2.0)
        assert on / len(a) > 0.9     # 24 of every 24.8 fall in the bursts


def test_shared_prefix_groups():
    mix = dict(MIX, shared_prefix={"groups": 2, "len": {"dist": "fixed",
                                                        "value": 20}})
    reqs = traffic.requests(mix, 8, 1000, seed=3)
    assert np.array_equal(reqs[0]["prompt"][:20], reqs[2]["prompt"][:20])
    assert not np.array_equal(reqs[0]["prompt"][:20], reqs[1]["prompt"][:20])


def test_train_batches_loss_share():
    ring = traffic.train_batches({"batch": 8, "seq_len": 128,
                                  "loss_share": 0.15},
                                 {"vocab_size": 100}, 2, seed=2**31 + 5)
    assert ring[0]["tokens"].shape == (8, 128)
    assert 0.08 < ring[0]["weights"].mean() < 0.22
    assert not np.array_equal(ring[0]["tokens"], ring[1]["tokens"])
