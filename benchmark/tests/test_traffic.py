"""The dataset copy and the traffic generator: the same seed gives the
same graph, pool and request order, byte for byte; another seed gives
others."""

import hashlib
import io
import json
import os

import pytest

from conftest import BENCH

MIXES = sorted(f[:-5] for f in os.listdir(os.path.join(BENCH, "traffic"))
               if f.endswith(".json"))
# sha256 of generate(scale=1, seed=21_000_000) as tests/golden/dataset.py
# wrote it when the copy was taken (PR 23): the copy has not drifted
SCALE1_SHA256 = "2d7c3057f9613044a00257791d6d61e3e157873d1a094a3ce5ce3919832164c3"


def _graph(movies, scale, seed, variant=""):
    out = io.StringIO()
    facts = movies.write_rdf(out, scale, seed, variant)
    return out.getvalue(), facts


def test_dataset_is_the_golden_suites_at_scale_1(movies):
    text, facts = _graph(movies, 1, 21_000_000)
    assert hashlib.sha256(text.encode()).hexdigest() == SCALE1_SHA256
    assert facts["rdf"] == text.count("\n") == 26718


def test_dataset_same_seed_same_bytes_other_seed_other_bytes(movies):
    big = 2**31 + 11  # a seed past 32 signed bits
    a, fa = _graph(movies, 2, big)
    b, fb = _graph(movies, 2, big)
    c, _ = _graph(movies, 2, big + 1)
    assert a == b and fa == fb
    assert a != c
    assert len(fa["rating"]) == 2 * movies.PER_SCALE["film"]


def test_degraded_variant_changes_ratings_only(movies):
    sound, _ = _graph(movies, 1, 5)
    bad, _ = _graph(movies, 1, 5, "rating-1dp")
    diff = [(x, y) for x, y in zip(sound.split("\n"), bad.split("\n"))
            if x != y]
    assert diff and all("<rating>" in x and "<rating>" in y
                        for x, y in diff)
    with pytest.raises(ValueError):
        _graph(movies, 1, 5, "no-such-variant")


@pytest.mark.parametrize("mix_name", MIXES)
def test_pool_and_order_repeat_for_a_seed_and_differ_for_another(
        traffic, movies, mix_name):
    mix = traffic.load_mix(os.path.join(BENCH, "traffic",
                                        mix_name + ".json"))
    scale = 3
    _, facts = _graph(movies, scale, 77)

    def make(seed):
        pool = traffic.build_pool(mix, movies, scale, facts, seed)
        seq = traffic.Sequence(pool, len(mix["templates"]), seed)
        return (json.dumps(pool).encode(),
                [seq.at(i) for i in range(5 * len(mix["templates"]))],
                pool)

    a, order_a, pool = make(2**31 + 5)
    b, order_b, _ = make(2**31 + 5)
    c, order_c, _ = make(2**31 + 6)
    assert a == b and order_a == order_b
    assert a != c and order_a != order_c
    # every template is in the pool, with 1..bindings distinct bindings
    n = len(mix["templates"])
    per = [sum(1 for e in pool if e["template"] == t) for t in range(n)]
    assert min(per) >= 1 and max(per) <= mix["bindings"]
    assert len({e["query"] for e in pool}) >= n
    # equal counts per template in every round
    for r in range(5):
        sent = [pool[i]["template"] for i in order_a[r * n:(r + 1) * n]]
        assert sorted(sent) == list(range(n))
    # nothing is left unbound, and every uid is inside its class
    for e in pool:
        assert "$" not in e["query"].replace("$/", "")


def test_params_are_drawn_and_uid_literals_stay_in_their_class(
        traffic, movies):
    scale = 2
    _, facts = _graph(movies, scale, 9)
    t = {"name": "t", "text": "after 0x20007 word $w first $n",
         "params": {"w": {"choice": ["storm", "river"]},
                    "n": {"int": [5, 7]}}}
    import random
    q, drawn = traffic.bind(t, random.Random(1), traffic.Zipf(0.99),
                            movies, scale, facts)
    assert set(drawn) == {"w", "n"}
    _, after, _, w, _, n = q.split()
    film = int(after, 16) - movies.BASES["film"] * scale
    assert 0 <= film < movies.PER_SCALE["film"] * scale
    assert w in ("storm", "river") and 5 <= int(n) <= 7
    with pytest.raises(ValueError):
        traffic.bind({"name": "t", "text": "$x", "params": {
            "x": {"float": [0, 1]}}}, random.Random(1),
            traffic.Zipf(0.99), movies, scale, facts)


def test_zipf_is_skewed_and_in_range(traffic):
    import random
    z, rng = traffic.Zipf(0.99), random.Random(3)
    draws = [z.draw(rng, 1000) for _ in range(4000)]
    assert min(draws) >= 0 and max(draws) < 1000
    top = max(set(draws), key=draws.count)
    assert draws.count(top) > 4000 / 1000 * 20  # far over uniform's 4
