"""The query streams: the same seed gives the same bodies, no body repeats
inside a run (warm-up and the check's fresh queries included), every seed
sends the same set of lengths."""

import json
import os

import numpy as np
import pytest

import queries
import run

TRAFFIC = sorted(f[:-5] for f in os.listdir(os.path.join(run.HERE, "traffic")))


def _stream(name, seed):
    traffic = json.load(open(os.path.join(run.HERE, "traffic",
                                          name + ".json")))
    if traffic["generator"] == "df_rank_band":
        traffic["params"].update(rank_lo=20, rank_hi=3000)
    rng = np.random.default_rng(0)
    df = np.sort(rng.zipf(1.3, 5000))[::-1].astype(np.int64)
    vocab = [f"t{i:07d}" for i in range(len(df))]
    return queries.QueryStream(df, vocab, seed, traffic), traffic


@pytest.mark.parametrize("name", TRAFFIC)
def test_same_seed_same_bodies_and_no_repeat(name):
    a, _ = _stream(name, 2147483777)
    b, _ = _stream(name, 2147483777)
    wa = a.take(600) + a.take(300)
    wb = b.take(900)
    assert [q["body"] for q in wa] == [q["body"] for q in wb]
    twins = [a.permuted(q) for q in wa]     # what the warm-up sends
    assert all(sorted(t["terms"]) == sorted(q["terms"])
               for t, q in zip(twins, wa))
    bodies = [json.dumps(q["body"], sort_keys=True) for q in wa + twins]
    assert len(set(bodies)) == len(bodies)
    a.reseed(5)                             # the check's fresh queries
    fresh = [json.dumps(q["body"], sort_keys=True) for q in a.take(100)]
    assert len(set(fresh + bodies)) == len(fresh) + len(bodies)
    other, _ = _stream(name, 2147483778)
    assert [q["body"] for q in other.take(40)] != [q["body"]
                                                   for q in wa[:40]]


@pytest.mark.parametrize("name", TRAFFIC)
def test_every_seed_sends_the_same_lengths(name):
    counts = []
    for seed in (1, 2, 3000000000):
        s, traffic = _stream(name, seed)
        p = traffic["params"]
        span = p["max_terms"] - p["min_terms"] + 1
        lens = [len(q["terms"]) for q in s.take(span * 20)]
        assert min(lens) == p["min_terms"] and max(lens) == p["max_terms"]
        counts.append(sorted(lens))
        assert all(len(set(q["terms"])) == len(q["terms"])
                   for q in s.take(50))
    assert counts[0] == counts[1] == counts[2]


def test_an_unknown_generator_is_an_error():
    with pytest.raises(SystemExit, match="no query generator"):
        queries.generator("nothing_of_the_kind")
