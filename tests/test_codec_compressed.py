"""Compressed posting plane: property/fuzz tests.

Three contracts, each fuzzed over adversarial uid distributions
(dense runs, singletons, 2^16-block-boundary straddles, max-uid):

  1. round-trip: compress() -> densify() is the identity on every
     sorted-unique uint64 set, whatever block forms were chosen;
  2. set-algebra parity: intersect/union/difference/count_filter on
     compressed packs == the ops/setops host oracles on the dense
     vectors, byte-for-byte (uids, order, dtype);
  3. at-rest stream parity: the numpy group-varint fallback produces
     the BYTE-IDENTICAL stream to the native dgt_gv_* kernels, both
     directions.
"""

import numpy as np
import pytest

from dgraph_tpu.ops import codec, setops

RNG = np.random.default_rng


# ------------------------------------------------- adversarial shapes


def _shapes():
    rng = RNG(7)
    yield "empty", np.empty(0, np.uint64)
    yield "singleton", np.array([0], np.uint64)
    yield "max_uid", np.array([2**64 - 1], np.uint64)
    yield "min_and_max", np.array([0, 2**64 - 1], np.uint64)
    # a dense run crossing a 2^16 block boundary
    yield "block_straddle", np.arange(65530, 65550, dtype=np.uint64)
    # exactly one full block (forces RUN, the 64-bit word-span edge)
    yield "full_block", np.arange(1 << 16, dtype=np.uint64)
    # a full block plus one uid each side
    yield "overfull_block", np.arange((1 << 16) - 1, (1 << 17) + 1,
                                      dtype=np.uint64)
    # word-aligned 64-long run inside one word (the shift-overflow edge)
    yield "word_run", np.arange(128, 192, dtype=np.uint64)
    # one uid per block across many blocks (every block a singleton)
    yield "block_singletons", (np.arange(500, dtype=np.uint64)
                               << np.uint64(16)) + np.uint64(7)
    # clustered like real posting lists
    steps = rng.integers(1, 60, 100_000).astype(np.uint64)
    yield "clustered", np.cumsum(steps)
    # uniform sparse over a huge space
    yield "sparse_u64", np.unique(
        rng.integers(0, 2**63, 50_000, dtype=np.uint64))
    # dense random inside few blocks (bitmap form)
    yield "dense_blocks", np.unique(
        rng.integers(0, 3 << 16, 80_000, dtype=np.uint64))
    # runs + singletons mixed
    parts = [np.arange(s, s + int(rng.integers(1, 300)),
                       dtype=np.uint64)
             for s in rng.integers(0, 1 << 24, 200, dtype=np.uint64)]
    parts.append(rng.integers(0, 1 << 24, 500, dtype=np.uint64))
    yield "runs_and_dust", np.unique(np.concatenate(parts))


@pytest.mark.parametrize("name,uids", list(_shapes()))
def test_roundtrip_adversarial(name, uids):
    pack = codec.compress(uids)
    assert pack.n == len(uids)
    got = pack.densify()
    np.testing.assert_array_equal(got, uids)
    assert got.dtype == np.uint64
    # descriptors are self-consistent
    assert int(pack.counts.sum()) == len(uids)
    assert len(pack.keys) == len(np.unique(uids >> np.uint64(16)))


def test_form_choice_by_density():
    """The adaptive rule picks the byte-smallest container."""
    run = codec.compress(np.arange(1 << 16, dtype=np.uint64))
    assert list(run.forms) == [codec.FORM_RUN]
    dense = codec.compress(np.unique(
        RNG(0).integers(0, 1 << 16, 40_000, dtype=np.uint64)))
    assert list(dense.forms) == [codec.FORM_BITMAP]
    sparse = codec.compress(np.unique(
        RNG(0).integers(0, 1 << 16, 200, dtype=np.uint64)))
    assert list(sparse.forms) == [codec.FORM_PACKED]


def test_compression_ratio_clustered():
    """Clustered posting lists must land well under the dense 8 B/uid
    (the reference's ~13% claim, codec/codec.go:281)."""
    steps = RNG(0).integers(1, 50, 1_000_000).astype(np.uint64)
    uids = np.cumsum(steps)
    pack = codec.compress(uids)
    assert pack.nbytes < 0.3 * uids.nbytes, \
        f"{pack.nbytes} vs dense {uids.nbytes}"


# ------------------------------------------- set-algebra parity (fuzz)


def _fuzz_sets(rng, k):
    space = int(rng.choice([2_000, 90_000, 1 << 22, 1 << 40]))
    sets = []
    for _ in range(k):
        mode = rng.integers(0, 3)
        n = int(rng.integers(0, 8_000))
        if mode == 0:  # uniform
            s = np.unique(rng.integers(0, space, n, dtype=np.uint64))
        elif mode == 1:  # runs
            starts = rng.integers(0, space, max(n // 40, 1),
                                  dtype=np.uint64)
            s = np.unique(np.concatenate(
                [np.arange(st, st + int(rng.integers(1, 90)),
                           dtype=np.uint64) for st in starts]))
        else:  # clustered
            s = (np.cumsum(rng.integers(1, 30, n + 1).astype(np.uint64))
                 + np.uint64(rng.integers(space)))
        sets.append(s)
    shared = np.unique(rng.integers(0, space, 400, dtype=np.uint64))
    return [np.unique(np.concatenate([s, shared])) for s in sets]


@pytest.mark.parametrize("seed", range(12))
def test_setops_parity_fuzz(seed):
    rng = RNG(seed)
    scratch = codec.DecodeScratch()
    k = int(rng.integers(2, 6))
    sets = _fuzz_sets(rng, k)
    packs = [codec.compress(s) for s in sets]
    np.testing.assert_array_equal(
        setops.intersect_packs(packs, scratch=scratch),
        setops.intersect_many(sets))
    np.testing.assert_array_equal(
        setops.union_packs(packs, scratch=scratch),
        setops.union_many(sets))
    np.testing.assert_array_equal(
        setops.difference_pack(packs[0], packs[1], scratch=scratch),
        setops.difference(sets[0], sets[1]))
    need = int(rng.integers(1, k + 1))
    np.testing.assert_array_equal(
        setops.count_filter_packs(packs, need, scratch=scratch),
        setops.count_filter(sets, need))


def test_intersect_disjoint_blocks_never_decodes():
    """Descriptor skipping: key-disjoint packs intersect empty without
    touching a single payload byte."""
    a = codec.compress(np.arange(100, dtype=np.uint64))
    b = codec.compress(np.arange(100, dtype=np.uint64)
                       + np.uint64(1 << 20))
    calls = []
    orig = codec.CompressedPack.block_lows
    codec.CompressedPack.block_lows = \
        lambda self, bi, scratch=None: calls.append(bi) \
        or orig(self, bi, scratch)
    try:
        got = setops.intersect_packs([a, b])
    finally:
        codec.CompressedPack.block_lows = orig
    assert len(got) == 0
    assert not calls, "disjoint blocks must not decode"


def test_intersect_device_parity():
    rng = RNG(3)
    sets = [np.unique(rng.integers(0, 1 << 19, 150_000,
                                   dtype=np.uint64))
            for _ in range(3)]
    packs = [codec.compress(s) for s in sets]
    assert any((p.forms == codec.FORM_BITMAP).any() for p in packs)
    want = setops.intersect_many(sets)
    np.testing.assert_array_equal(
        setops.intersect_packs(packs, device=True), want)


# ------------------------------------------------- gv stream parity


def _gv_cases():
    rng = RNG(11)
    yield np.empty(0, np.uint64)
    yield np.array([0], np.uint64)
    yield np.array([2**64 - 1], np.uint64)
    yield np.array([0, 255, 256, 65_535, 65_536, 2**32 - 1, 2**32,
                    2**64 - 1], np.uint64)  # every width code
    yield np.arange(1000, dtype=np.uint64)
    yield np.unique(rng.integers(0, 2**63, 10_000, dtype=np.uint64))
    yield np.cumsum(rng.integers(1, 2**40, 513).astype(np.uint64))


@pytest.mark.parametrize("i,uids", list(enumerate(_gv_cases())))
def test_gv_numpy_roundtrip(i, uids):
    np.testing.assert_array_equal(
        codec.gv_decode_np(codec.gv_encode_np(uids)), uids)


@pytest.mark.parametrize("i,uids", list(enumerate(_gv_cases())))
def test_gv_native_numpy_byte_parity(i, uids):
    from dgraph_tpu import native
    if not native.available():
        pytest.skip("native runtime unavailable")
    nat = native.gv_encode(uids)
    fal = codec.gv_encode_np(uids)
    assert nat == fal, f"stream divergence on case {i}"
    np.testing.assert_array_equal(native.gv_decode(fal), uids)
    np.testing.assert_array_equal(codec.gv_decode_np(nat), uids)


def test_gv_small_scalar_byte_parity():
    """The short-list scalar encoder (the bulk-ingest snapshot fast
    path) must be byte-identical to gv_encode_np at EVERY length
    through the crossover, including all width codes and group-of-4
    boundary shapes."""
    rng = RNG(23)
    cases = [np.empty(0, np.uint64),
             np.array([0], np.uint64),
             np.array([2**64 - 1], np.uint64),
             np.array([0, 255, 256, 65_535, 65_536, 2**32 - 1,
                       2**32, 2**64 - 1], np.uint64)]
    for n in range(1, 64):
        cases.append(np.unique(
            rng.integers(0, 2**48, n, dtype=np.uint64)))
    for uids in cases:
        small = codec._gv_encode_py_small(uids)
        assert small == codec.gv_encode_np(uids), uids
        np.testing.assert_array_equal(codec.gv_decode_np(small),
                                      uids)
    # the dispatcher picks the scalar path below the crossover and
    # both paths stay on one byte format
    assert codec.gv_encode(cases[3]) == codec.gv_encode_np(cases[3])


def test_gv_decode_rejects_truncation():
    buf = codec.gv_encode_np(np.arange(100, dtype=np.uint64))
    with pytest.raises(ValueError):
        codec.gv_decode_np(buf[:5])
    with pytest.raises(ValueError):
        codec.gv_decode_np(buf[:20])


# ------------------------------------------------- scratch + LRU split


def test_scratch_pool_bounds_and_overflow():
    sc = codec.DecodeScratch(budget_bytes=1 << 12)
    a = sc.take(16, np.uint64)
    a[:] = 7
    assert sc.high_water <= 1 << 12
    big = sc.take(1 << 20, np.uint64)  # over budget: fresh, untracked
    assert sc.overflows == 1
    assert big.nbytes == (1 << 20) * 8
    assert sc.high_water <= 1 << 12
    st = sc.stats()
    assert st["budget"] == 1 << 12 and st["overflows"] == 1


def test_tile_bytes_device_host_split():
    """The satellite fix: numpy (anywhere, incl. dataclass fields)
    counts as HOST bytes, compressed host blocks never charge the HBM
    budget, bare .nbytes objects stay DEVICE."""
    import dataclasses

    from dgraph_tpu.engine.tile_cache import _tile_bytes

    pack = codec.compress(np.arange(1000, dtype=np.uint64))
    assert _tile_bytes(pack) == (0, pack.nbytes)

    arr = np.zeros(10, np.int64)
    assert _tile_bytes(arr) == (0, 80)

    class FakeDevBuf:
        nbytes = 4096
    assert _tile_bytes(FakeDevBuf()) == (4096, 0)

    @dataclasses.dataclass
    class Tile:
        dev: object
        side: np.ndarray
    t = Tile(FakeDevBuf(), np.zeros(4, np.uint8))
    assert _tile_bytes(t) == (4096, 4)
    assert _tile_bytes([t, pack]) == (4096, 4 + pack.nbytes)


def test_lru_budgets_compressed_exports_as_host():
    from dgraph_tpu.engine.tile_cache import DeviceCacheLRU

    class Tab:
        pass

    lru = DeviceCacheLRU(budget_bytes=1 << 20,
                         host_budget_bytes=1 << 30)
    tab = Tab()
    pack = codec.compress(np.arange(5000, dtype=np.uint64))
    tab._tok_packs = pack
    lru.put(tab, "_tok_packs", pack)
    st = lru.stats()
    assert st["bytes"] == 0          # nothing charged to HBM
    assert st["hostBytes"] == pack.nbytes
    assert st["peakHostBytes"] >= pack.nbytes


def test_lru_evicts_on_host_budget():
    from dgraph_tpu.engine.tile_cache import DeviceCacheLRU

    class Tab:
        pass

    pack = codec.compress(np.unique(
        RNG(0).integers(0, 1 << 22, 20_000, dtype=np.uint64)))
    lru = DeviceCacheLRU(budget_bytes=1 << 30,
                         host_budget_bytes=int(pack.nbytes * 2.5))
    tabs = []
    for i in range(4):
        tab = Tab()
        tab._tok_packs = pack
        tab._tok_packs_ts = 5
        tabs.append(tab)
        lru.put(tab, "_tok_packs", pack)
    assert lru.evictions >= 1
    assert lru.stats()["hostBytes"] <= lru.host_budget
    # evicted tablets lost the attr, survivors keep it
    assert tabs[0]._tok_packs is None and tabs[0]._tok_packs_ts == -1
    assert tabs[-1]._tok_packs is pack


# -------------------------------------------- compressed token index


def test_compressed_token_index_probe_parity():
    from dgraph_tpu.storage.tablet import CompressedTokenIndex

    rng = RNG(5)
    index = {
        b"t1": np.unique(rng.integers(0, 1 << 20, 5000,
                                      dtype=np.uint64)),
        b"t2": np.arange(100, dtype=np.uint64),
        b"t3": np.empty(0, np.uint64),
    }
    tix = CompressedTokenIndex(index)
    for t, uids in index.items():
        np.testing.assert_array_equal(tix.probe(t), uids)
    # hybrid split: long lists are packs, the small tail dense slices
    assert tix.probe_operand(b"t1").n == len(index[b"t1"])  # pack
    assert isinstance(tix.probe_operand(b"t2"), np.ndarray)
    assert len(tix.probe(b"absent")) == 0
    assert tix.probe_operand(b"absent") is None
    dense = sum(u.nbytes for u in index.values())
    assert tix.nbytes < dense


@pytest.mark.parametrize("seed", range(6))
def test_mixed_setops_parity_fuzz(seed):
    """The hybrid boundary: dense slices + packs through the mixed
    kernels == the dense oracles."""
    rng = RNG(100 + seed)
    scratch = codec.DecodeScratch()
    k = int(rng.integers(2, 6))
    sets = _fuzz_sets(rng, k)
    # alternate forms across operands (and both all-dense/all-pack)
    ops = [codec.compress(s) if (i + seed) % 2 else s
           for i, s in enumerate(sets)]
    np.testing.assert_array_equal(
        setops.intersect_mixed(ops, scratch=scratch),
        setops.intersect_many(sets))
    np.testing.assert_array_equal(
        setops.union_mixed(ops, scratch=scratch),
        setops.union_many(sets))
    need = int(rng.integers(1, k + 1))
    np.testing.assert_array_equal(
        setops.count_filter_mixed(ops, need, scratch=scratch),
        setops.count_filter(sets, need))


def test_pack_member_block_skipping():
    p = codec.compress(np.arange(1000, dtype=np.uint64))
    probe = np.array([0, 500, 999, 1000, 1 << 30], np.uint64)
    np.testing.assert_array_equal(
        setops.pack_member(p, probe),
        np.array([True, True, True, False, False]))
