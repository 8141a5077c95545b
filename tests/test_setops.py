"""ops/setops: k-way sorted-set algebra — host folds and their device
(uidvec co-sort) variants must agree with the naive numpy oracles on
randomized inputs, including empty/singleton/degenerate shapes."""

import os
from functools import reduce

import numpy as np
import pytest

from dgraph_tpu.ops import setops

pytestmark = pytest.mark.skipif(
    os.environ.get("JAX_PLATFORMS", "") not in ("", "cpu"),
    reason="needs a jax backend for the device variants")


def _rand_sets(rng, k, lo=0, hi=1 << 20, maxlen=4000):
    out = []
    for _ in range(k):
        n = int(rng.integers(0, maxlen))
        out.append(np.unique(
            rng.integers(lo, hi, n).astype(np.uint64)))
    return out


def _oracle_union(parts):
    if not parts:
        return np.empty(0, np.uint64)
    return reduce(np.union1d, parts).astype(np.uint64)


def _oracle_intersect(parts):
    if not parts:
        return np.empty(0, np.uint64)
    return reduce(
        lambda a, b: np.intersect1d(a, b, assume_unique=True),
        parts).astype(np.uint64)


@pytest.mark.parametrize("k", [1, 2, 3, 8, 33])
def test_union_many_host(k):
    rng = np.random.default_rng(k)
    for trial in range(4):
        parts = _rand_sets(rng, k)
        got = setops.union_many(parts)
        assert np.array_equal(got, _oracle_union(parts))


@pytest.mark.parametrize("k", [1, 2, 3, 8, 33])
def test_intersect_many_host(k):
    rng = np.random.default_rng(100 + k)
    for trial in range(4):
        # overlap-heavy sets so intersections are non-trivial
        parts = _rand_sets(rng, k, hi=3000)
        got = setops.intersect_many(parts)
        assert np.array_equal(got, _oracle_intersect(parts))


def test_edge_cases():
    e = np.empty(0, np.uint64)
    a = np.array([1, 5, 9], np.uint64)
    assert len(setops.union_many([])) == 0
    assert len(setops.intersect_many([])) == 0
    assert np.array_equal(setops.union_many([e, a, e]), a)
    assert len(setops.intersect_many([a, e])) == 0
    assert np.array_equal(setops.union_many([a]), a)
    assert np.array_equal(setops.intersect_many([a]), a)
    # lopsided pair takes the galloping branch
    big = np.arange(0, 100000, 3, dtype=np.uint64)
    assert np.array_equal(setops.intersect_pair(a, big),
                          np.intersect1d(a, big))
    assert np.array_equal(setops.difference(big[:50], big[20:]),
                          big[:20])


@pytest.mark.parametrize("need", [1, 2, 5, 8, 17, 18])
def test_count_filter(need):
    rng = np.random.default_rng(need)
    parts = _rand_sets(rng, 17, hi=4000, maxlen=900)
    got = setops.count_filter(parts, need)
    cat = np.concatenate([p for p in parts if len(p)]) \
        if any(len(p) for p in parts) else np.empty(0, np.uint64)
    uids, counts = np.unique(cat, return_counts=True)
    want = uids[counts >= need] if need <= 17 else uids[:0]
    assert np.array_equal(got, want)


def test_device_variants_parity():
    rng = np.random.default_rng(7)
    for k in (2, 5, 9):
        parts = _rand_sets(rng, k, hi=5000, maxlen=800)
        du = setops.union_many_device(parts)
        assert du is not None
        assert np.array_equal(du, _oracle_union(parts))
        di = setops.intersect_many_device(parts)
        assert di is not None
        assert np.array_equal(di, _oracle_intersect(parts))


def _pair(n_a, ratio, overlap, seed=3):
    """Two sorted unique uint32 lists, |b| = n_a * ratio, about
    `overlap` of a's elements shared (the reference's sweep axes,
    algo/uidlist_test.go BenchmarkListIntersect*)."""
    rng = np.random.default_rng(seed)
    b = np.unique(rng.integers(0, 4_000_000_000, n_a * ratio,
                               dtype=np.uint32))
    take = rng.random(len(b)) < (overlap * n_a / max(len(b), 1))
    shared = b[take][:n_a]
    fresh = np.unique(rng.integers(0, 4_000_000_000, n_a,
                                   dtype=np.uint32))
    a = np.unique(np.concatenate([shared, fresh]))[:n_a]
    return a, b


def _skewed_sliver():
    # a clustered inside a sliver of b's range
    rng = np.random.default_rng(11)
    a = np.sort(rng.choice(
        np.arange(1_000_000, 1_050_000, dtype=np.uint32),
        2048, replace=False))
    b = np.unique(rng.integers(0, 4_000_000_000, 64 * 2048,
                               dtype=np.uint32))
    return [(a, b)]


def _dense_subset():
    # every element of a is a hit, b barely bigger than a
    rng = np.random.default_rng(5)
    b = np.unique(rng.integers(0, 1_000_000, 6_000, dtype=np.uint32))
    return [(np.sort(rng.choice(b, 4096, replace=False)), b)]


def _identical_disjoint_empty():
    rng = np.random.default_rng(9)
    a = np.unique(rng.integers(0, 1 << 30, 3000, dtype=np.uint32))
    e = np.empty(0, np.uint32)
    return [(a, a.copy()), (a, a + np.uint32(1 << 30)), (e, a), (a, e)]


def _every_other():
    # shared values everywhere: a run of equal pairs with no gap
    return [(np.arange(0, 4096, 2, dtype=np.uint32),
             np.arange(0, 4096, 1, dtype=np.uint32))]


_TIERS = {"host": setops.intersect_many,
          "device": setops.intersect_many_device}


def _check_pairs(pairs, tier):
    for a, b in pairs:
        got = _TIERS[tier]([a.astype(np.uint64), b.astype(np.uint64)])
        assert got is not None
        assert np.array_equal(
            got, np.intersect1d(a, b, assume_unique=True))


@pytest.mark.parametrize("tier", list(_TIERS))
@pytest.mark.parametrize("n_a,ratio,overlap",
                         [(2048, 1, 0.3), (2048, 8, 0.1),
                          (1024, 16, 0.05), (4096, 2, 0.5)])
def test_intersect_pair_sweep(n_a, ratio, overlap, tier):
    _check_pairs([_pair(n_a, ratio, overlap)], tier)


@pytest.mark.parametrize("pairs", [
    _skewed_sliver, _dense_subset, _identical_disjoint_empty,
    _every_other], ids=lambda f: f.__name__.lstrip("_"))
def test_intersect_pair_shapes(pairs):
    for tier in _TIERS:
        _check_pairs(pairs(), tier)


def test_device_variants_reject_wide_uids():
    wide = np.array([1, 2, 0xFFFFFFFF00], np.uint64)
    other = np.array([1, 2, 3], np.uint64)
    assert setops.union_many_device([wide, other]) is None
    assert setops.intersect_many_device([wide, other]) is None
    # host folds still answer them
    assert np.array_equal(setops.union_many([wide, other]),
                          _oracle_union([wide, other]))
