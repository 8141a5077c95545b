"""Kernel-level numerics tests for uidvec against NumPy oracles.

Mirrors the reference's exhaustive intersect/merge property tests
(algo/uidlist_test.go:290,343) — randomized size/overlap sweeps checked
against np.intersect1d / union1d / setdiff1d.
"""

import numpy as np
import pytest

from dgraph_tpu.ops import (
    SENTINEL,
    from_numpy,
    to_numpy,
    count,
    intersect,
    union,
    difference,
    merge_many,
    intersect_many,
    first_k,
    pad_to,
)


def rand_sorted(rng, n, lo=1, hi=1 << 30):
    return np.sort(rng.choice(np.arange(lo, hi, dtype=np.uint32),
                              size=n, replace=False))


# Sizes chosen so padded shapes collapse onto few buckets (8/128/1024) —
# one XLA compile per bucket pair on this 1-core CI box.
CASES = [(0, 0), (5, 7), (100, 3), (3, 100), (1000, 1000)]


@pytest.mark.parametrize("na,nb", CASES)
def test_intersect_oracle(na, nb):
    rng = np.random.default_rng(na * 1000 + nb)
    a = rand_sorted(rng, na, hi=1 << 16)  # small domain -> real overlap
    b = rand_sorted(rng, nb, hi=1 << 16)
    got = to_numpy(intersect(from_numpy(a), from_numpy(b)))
    want = np.intersect1d(a, b)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("na,nb", CASES)
def test_union_oracle(na, nb):
    rng = np.random.default_rng(na * 997 + nb)
    a = rand_sorted(rng, na, hi=1 << 16)
    b = rand_sorted(rng, nb, hi=1 << 16)
    got = to_numpy(union(from_numpy(a), from_numpy(b)))
    want = np.union1d(a, b)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("na,nb", CASES)
def test_difference_oracle(na, nb):
    rng = np.random.default_rng(na * 31 + nb)
    a = rand_sorted(rng, na, hi=1 << 16)
    b = rand_sorted(rng, nb, hi=1 << 16)
    got = to_numpy(difference(from_numpy(a), from_numpy(b)))
    want = np.setdiff1d(a, b)
    np.testing.assert_array_equal(got, want)


def test_overlap_sweep():
    """Ref algo/uidlist_test.go:290 — size-ratio x overlap sweep."""
    rng = np.random.default_rng(7)
    for ratio in (1, 10, 100, 1000):
        for overlap in (0.0, 0.01, 0.3, 1.0):
            na = 2000
            nb = max(1, na // ratio)
            a = rand_sorted(rng, na)
            take = int(nb * overlap)
            b_over = rng.choice(a, size=take, replace=False)
            b_rest = rand_sorted(rng, nb - take)
            b = np.sort(np.unique(np.concatenate([b_over, b_rest])))
            got = to_numpy(intersect(from_numpy(a), from_numpy(b)))
            np.testing.assert_array_equal(got, np.intersect1d(a, b))


def test_merge_many_oracle():
    rng = np.random.default_rng(3)
    rows = [rand_sorted(rng, rng.integers(0, 500), hi=1 << 14)
            for _ in range(6)]
    size = pad_to(max(len(r) for r in rows))
    mat = np.stack([np.asarray(from_numpy(r, size)) for r in rows])
    got = to_numpy(merge_many(np.asarray(mat)))
    want = np.unique(np.concatenate(rows))
    np.testing.assert_array_equal(got, want)


def test_intersect_many_oracle():
    rng = np.random.default_rng(4)
    base = rand_sorted(rng, 300, hi=1 << 12)
    rows = []
    for _ in range(4):
        extra = rand_sorted(rng, 100, hi=1 << 12)
        rows.append(np.union1d(base, extra))
    size = pad_to(max(len(r) for r in rows))
    mat = np.stack([np.asarray(from_numpy(r, size)) for r in rows])
    got = to_numpy(intersect_many(np.asarray(mat)))
    want = rows[0]
    for r in rows[1:]:
        want = np.intersect1d(want, r)
    np.testing.assert_array_equal(got, want)


def test_count_and_first_k():
    a = np.array([3, 9, 12, 40, 41], dtype=np.uint32)
    v = from_numpy(a, 16)
    assert int(count(v)) == 5
    np.testing.assert_array_equal(to_numpy(first_k(v, 3)), a[:3])
    np.testing.assert_array_equal(to_numpy(first_k(v, 3, offset=2)), a[2:5])
    np.testing.assert_array_equal(to_numpy(first_k(v, 16)), a)


def test_sentinel_padding_is_inert():
    a = from_numpy(np.array([], dtype=np.uint32), 8)
    b = from_numpy(np.array([1, 2], dtype=np.uint32), 8)
    assert to_numpy(intersect(a, b)).size == 0
    np.testing.assert_array_equal(to_numpy(union(a, b)), [1, 2])
    assert to_numpy(difference(a, b)).size == 0
    assert int(count(a)) == 0


def test_sorted_lookup_matches_searchsorted():
    """Co-sort lookup (TPU-friendly) must return exactly
    np.searchsorted left-insertion indices for sorted queries,
    including duplicates between query and table, sentinels, and
    empty-overlap cases."""
    import numpy as np

    from dgraph_tpu.ops.uidvec import from_numpy, sorted_lookup

    rng = np.random.default_rng(11)
    for na, nb in [(8, 8), (64, 1024), (1024, 64), (500, 500)]:
        a = np.unique(rng.integers(0, 5000, na).astype(np.uint32))
        b = np.unique(rng.integers(0, 5000, nb).astype(np.uint32))
        da, db = from_numpy(a), from_numpy(b)
        got = np.asarray(sorted_lookup(db, da))
        want = np.searchsorted(np.asarray(db), np.asarray(da))
        assert np.array_equal(got, want), (na, nb)


# (n_q, n_t) -> does lookup_idx co-sort there on a sort backend: under
# 4,096 queries the scan, above it the co-sort unless the table is 128
# times the query or more
LOOKUP_GRID = {(2048, 4096): False, (2048, 65536): False,
               (4096, 4096): True, (4096, 65536): True,
               (8192, 4096): True, (8192, 65536): True,
               (4096, 524288): False}


def test_lookup_grid_straddles_the_rule():
    """The grid below tests BOTH lowerings only while lookup_idx's
    rule splits it, by the query's size and by the table's."""
    from dgraph_tpu.ops.uidvec import lookup_cosorts

    assert {k: lookup_cosorts(*k) for k in LOOKUP_GRID} == LOOKUP_GRID


@pytest.mark.parametrize("n_q,n_t", sorted(LOOKUP_GRID))
def test_lookup_idx_matches_searchsorted(monkeypatch, n_q, n_t):
    """lookup_idx == np.searchsorted whichever lowering its rule picks
    from the two static sizes, traced as the chip traces it (sort
    backend on): sentinel-padded table and queries, duplicate-free,
    many of the queries absent from the table."""
    from dgraph_tpu.ops import uidvec

    monkeypatch.setattr(uidvec, "_sort_backend", lambda: True)
    rng = np.random.default_rng(n_q * 31 + n_t)
    table = rand_sorted(rng, n_t - 9, hi=1 << 22)
    q = np.union1d(
        rng.choice(table, min(n_q, n_t) // 2, replace=False),
        rand_sorted(rng, n_q, hi=1 << 22))
    q = q[np.sort(rng.choice(len(q), n_q - 5, replace=False))]
    dt, dq = from_numpy(table, n_t), from_numpy(q, n_q)
    got = np.asarray(uidvec.lookup_idx(dt, dq))
    want = np.searchsorted(np.asarray(dt), np.asarray(dq))
    np.testing.assert_array_equal(got, want)
