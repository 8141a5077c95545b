"""Vector similarity search subsystem: float32vector type + schema,
ops/knn kernels (host/device/two-stage/sharded parity), the
columnar vector store's MVCC overlay semantics, and the similar_to()
query surface end-to-end."""

import numpy as np
import pytest

from dgraph_tpu.engine.db import GraphDB
from dgraph_tpu.gql.lexer import GQLError
from dgraph_tpu.models.types import (
    TypeID, Val, convert, parse_vector, to_json_value,
)
from dgraph_tpu.ops import knn


def _corpus(n, d, seed=0):
    rng = np.random.default_rng(seed)
    return rng.standard_normal((n, d), dtype=np.float32)


# ---------------------------------------------------------------------------
# type system
# ---------------------------------------------------------------------------


def test_float32vector_type_roundtrip():
    v = convert(Val(TypeID.DEFAULT, "[0.5, -1.25, 3]"),
                TypeID.FLOAT32VECTOR)
    assert v.value.dtype == np.float32
    assert to_json_value(v) == [0.5, -1.25, 3.0]
    # -> string -> back is lossless
    s = convert(v, TypeID.STRING)
    v2 = convert(s, TypeID.FLOAT32VECTOR)
    assert np.array_equal(v.value, v2.value)


def test_parse_vector_rejects_junk():
    for bad in ("[]", "", "[1, two]", "[nan]", [[1.0, 2.0]]):
        with pytest.raises((ValueError, TypeError)):
            parse_vector(bad)


def test_schema_vector_forms():
    from dgraph_tpu.models.schema import parse_schema

    preds, _ = parse_schema("embedding: float32vector @index(vector) .")
    ps = preds[0]
    assert ps.value_type == TypeID.FLOAT32VECTOR
    assert ps.indexed and ps.tokenizers == ["vector"]
    assert ps.describe() == "embedding: float32vector @index(vector) ."
    with pytest.raises(ValueError):
        parse_schema("e: [float32vector] .")  # no ragged vector lists
    with pytest.raises(ValueError):
        parse_schema("name: string @index(vector) .")  # wrong type


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n,d,k,metric", [
    *[(100_000, 128, 10, m) for m in knn.METRICS],
    # a small block, below what the two-stage reduce ever takes
    (2048, 64, 8, "dot"), (2048, 64, 8, "cosine")])
def test_host_vs_device_exact_parity(n, d, k, metric):
    """Acceptance: exact top-k parity between the host (numpy f64) and
    device (XLA f32) tiers, on a >= 100k x 128 corpus and a small one."""
    corpus = _corpus(n, d, seed=1)
    rng = np.random.default_rng(2)
    rows = rng.integers(0, len(corpus), 4)
    queries = corpus[rows] + 0.05 * rng.standard_normal(
        (4, d), dtype=np.float32)
    hi, hs = knn.topk_host(corpus, queries, k, metric)
    di, ds = knn.topk_device(corpus, queries, k, metric,
                             two_stage=False)
    assert np.array_equal(hi, di)
    np.testing.assert_allclose(hs, ds, rtol=2e-4, atol=2e-3)


def test_two_stage_recall_100k():
    """Acceptance: the two-stage approximate path keeps recall@k >=
    0.99 against exact on a 100k corpus (and actually engages)."""
    corpus = _corpus(100_000, 128, seed=3)
    queries = _corpus(32, 128, seed=4)
    k = 10
    assert knn.plan_two_stage(len(corpus), k) > 0
    ei, _ = knn.topk_device(corpus, queries, k, "cosine",
                            two_stage=False)
    ai, _ = knn.topk_device(corpus, queries, k, "cosine",
                            two_stage=True)
    hits = sum(len(set(ei[b].tolist()) & set(ai[b].tolist()))
               for b in range(len(queries)))
    recall = hits / float(len(queries) * k)
    assert recall >= 0.99, recall


def test_two_stage_recall_uid_clustered():
    """Adversarial layout: the true top-k are CONSECUTIVE rows (near-
    duplicate embeddings committed under consecutive uids). The
    dispersal permutation must keep them out of one bucket, or recall
    collapses to L/k."""
    corpus = _corpus(50_000, 32, seed=20)
    rng = np.random.default_rng(21)
    q = rng.standard_normal(32).astype(np.float32) * 4
    k = 10
    # rows 30000..30009 are the near-exact neighbors, contiguous
    corpus[30_000:30_000 + k] = q + 0.001 * rng.standard_normal(
        (k, 32)).astype(np.float32)
    assert knn.plan_two_stage(len(corpus), k) > 0
    ai, _ = knn.topk_device(corpus, q[None], k, "cosine",
                            two_stage=True)
    got = set(ai[0].tolist())
    want = set(range(30_000, 30_000 + k))
    assert len(got & want) >= k - 1, sorted(got)


def test_two_stage_falls_back_to_exact():
    """Contract: when the corpus can't sustain the recall target the
    two-stage request silently downgrades to exact."""
    corpus = _corpus(1000, 16)  # below TWO_STAGE_MIN_ROWS
    q = _corpus(2, 16, seed=9)
    assert knn.plan_two_stage(len(corpus), 5) == 0
    i1, _ = knn.topk_device(corpus, q, 5, "dot", two_stage=True)
    i2, _ = knn.topk_device(corpus, q, 5, "dot", two_stage=False)
    assert np.array_equal(i1, i2)
    # huge k relative to bucket count also falls back
    assert knn.plan_two_stage(8192, 5000) == 0


def test_topk_mask_and_merge():
    corpus = _corpus(300, 8, seed=5)
    q = corpus[7][None]
    mask = np.ones(300, bool)
    mask[7] = False
    i, s = knn.topk_host(corpus, q, 3, "cosine", mask=mask)
    assert 7 not in i[0]
    uids, scores = knn.merge_topk(
        [(np.array([3, 9], np.uint64), np.array([0.5, 0.9])),
         (np.array([11], np.uint64), np.array([0.7]))], 2)
    assert uids.tolist() == [9, 11]
    assert scores.tolist() == [0.9, 0.7]


def test_sharded_mesh_merge_parity():
    """Acceptance: per-shard top-k + merge over the 8-device CPU mesh
    returns exactly the single-device exact top-k."""
    from dgraph_tpu.parallel import make_mesh, shard_corpus, sharded_topk

    mesh = make_mesh()
    corpus = _corpus(4096, 32, seed=8)
    q = _corpus(3, 32, seed=9)
    block, n_real = shard_corpus(mesh, corpus)
    si, ss = sharded_topk(mesh, block, q, 6, "cosine", n_real=n_real)
    hi, hs = knn.topk_host(corpus, q, 6, "cosine")
    assert np.array_equal(si, hi)
    np.testing.assert_allclose(ss, hs, rtol=2e-4, atol=2e-3)


@pytest.mark.slow
def test_two_stage_recall_1m():
    """>= 1M-row corpora stay out of tier-1 (timeout budget)."""
    corpus = _corpus(1_000_000, 64, seed=10)
    queries = _corpus(16, 64, seed=11)
    ei, _ = knn.topk_device(corpus, queries, 10, "dot",
                            two_stage=False)
    ai, _ = knn.topk_device(corpus, queries, 10, "dot", two_stage=True)
    hits = sum(len(set(ei[b].tolist()) & set(ai[b].tolist()))
               for b in range(len(queries)))
    assert hits / 160.0 >= 0.99


# ---------------------------------------------------------------------------
# quantized IVF tier (ops/ivf.py)
# ---------------------------------------------------------------------------


def _quant_db(n=500, d=4, seed=40, **kw):
    """A GraphDB whose vector tablet is big enough (past the lowered
    vec_index_min_rows) that rollup trains the quantized index."""
    vecs = _clustered(n, d, centers=16, seed=seed)
    rdf = "\n".join(
        f'<0x{i + 1:x}> <embedding> "{list(map(float, vecs[i]))}"'
        '^^<xs:float32vector> .'
        for i in range(n))
    kw.setdefault("prefer_device", False)
    kw.setdefault("vec_index_min_rows", 100)
    db = GraphDB(**kw)
    db.alter("embedding: float32vector @index(vector(ivf)) .")
    db.mutate(set_nquads=rdf, commit_now=True)
    db.rollup_all()
    return db


def _clustered(n, d, centers=64, sigma=0.3, seed=0):
    """Seeded mixture-of-Gaussians corpus — the embedding-shaped
    workload the IVF coarse quantizer is built for (iid noise has no
    cluster structure and calibration degrades to a full scan)."""
    rng = np.random.default_rng(seed)
    C = rng.standard_normal((centers, d)).astype(np.float32)
    return C[rng.integers(0, centers, n)] + np.float32(sigma) \
        * rng.standard_normal((n, d)).astype(np.float32)


def test_ivf_recall_at_budgeted_config():
    """Acceptance: the quantized tier holds recall@10 >= 0.95 at its
    CALIBRATED budget (nprobe picked at build from the conservative
    0.98 target) on a seeded corpus, while scanning a fraction of the
    rows."""
    from dgraph_tpu.ops import ivf

    corpus = _clustered(60_000, 64, centers=512, seed=30)
    ix = ivf.build(corpus, seed=0)
    rng = np.random.default_rng(31)
    q = corpus[rng.integers(0, len(corpus), 32)] + 0.05 * \
        rng.standard_normal((32, 64), dtype=np.float32)
    hi, hs = knn.topk_host(corpus, q, 10, "cosine")
    qi, qs = ivf.search(ix, corpus, q, 10, "cosine")
    hits = sum(len(set(hi[b].tolist()) & set(qi[b].tolist()))
               for b in range(32))
    assert hits / 320.0 >= 0.95, (hits / 320.0, ix.describe())
    assert ix.scanned_rows() < len(corpus)
    # surviving rows carry the exact float64 score (re-rank runs the
    # host-exact formula)
    for b in range(32):
        common = set(hi[b].tolist()) & set(qi[b].tolist())
        for r in common:
            a = hs[b][hi[b].tolist().index(r)]
            bq = qs[b][qi[b].tolist().index(r)]
            assert abs(a - bq) <= 1e-9 * max(1.0, abs(a))


@pytest.mark.parametrize("metric", list(knn.METRICS))
def test_ivf_metrics_and_keep_mask(metric):
    from dgraph_tpu.ops import ivf

    corpus = _clustered(8_000, 16, centers=64, seed=32)
    ix = ivf.build(corpus, seed=0, calibrate=False)
    q = corpus[123][None] + 0.01
    qi, qs = ivf.search(ix, corpus, q, 5, metric, nprobe=ix.nlist)
    hi, _ = knn.topk_host(corpus, q, 5, metric)
    # full probe + exact re-rank == exact
    assert np.array_equal(qi, hi)
    keep = np.ones(len(corpus), bool)
    keep[qi[0][0]] = False
    qi2, _ = ivf.search(ix, corpus, q, 5, metric, nprobe=ix.nlist,
                        keep=keep)
    assert qi[0][0] not in qi2[0]


def test_ivf_cosine_probe_scale_invariant():
    """Cosine is scale-invariant, so the probe must be too: the SAME
    query directions at 1e-3 and 1e3 magnitude must return the same
    rows (the euclidean list ranking depends on ||q|| and silently
    collapsed recall on rescaled queries)."""
    from dgraph_tpu.ops import ivf

    corpus = _clustered(20_000, 16, centers=64, seed=20)
    ix = ivf.build(corpus, seed=0)
    rng = np.random.default_rng(21)
    q = corpus[rng.integers(0, len(corpus), 8)] + np.float32(0.05) \
        * rng.standard_normal((8, 16), dtype=np.float32)
    base, _ = ivf.search(ix, corpus, q, 10, "cosine")
    for scale in (1e-3, 1e3):
        got, _ = ivf.search(ix, corpus, q * np.float32(scale), 10,
                            "cosine")
        assert np.array_equal(base, got), scale
    hi, _ = knn.topk_host(corpus, q, 10, "cosine")
    hits = sum(len(set(hi[b].tolist()) & set(base[b].tolist()))
               for b in range(8))
    assert hits / 80.0 >= 0.95


def test_ivf_int8_scores_track_the_float64_host():
    """The int8 dequant-and-dot stage at a partial probe: every
    probed row's approximate dot is the float64 dot up to the
    quantization step, and the re-ranked answer is the exact one."""
    import jax.numpy as jnp

    from dgraph_tpu.ops import ivf

    corpus = _clustered(4_096, 64, centers=32, seed=33)
    ix = ivf.build(corpus, seed=0, calibrate=False)
    q = corpus[:3] + np.float32(0.01)
    cs, lists = ivf._probe_jit(jnp.asarray(q),
                               jnp.asarray(ix.centroids), 8,
                               "euclidean")
    slots, dots = ivf._approx_scores_host(
        ix, np.asarray(lists, np.int64), np.asarray(cs), q)
    c64 = corpus.astype(np.float64)
    for qi in range(len(q)):
        assert len(slots[qi])
        want = c64[ix.order[slots[qi]]] @ q[qi].astype(np.float64)
        # one rounding step a component: |q|_1 * scale / 2
        bound = np.abs(q[qi]).sum() * ix.scales[slots[qi]] / 2
        assert np.all(np.abs(dots[qi] - want) <= bound + 1e-3)
    got = ivf.search(ix, corpus, q, 6, "euclidean", nprobe=8)
    want_i, want_s = knn.topk_host(corpus, q, 6, "euclidean")
    assert np.array_equal(got[0], want_i)
    np.testing.assert_allclose(got[1], want_s, rtol=1e-9)


def test_ivf_build_deterministic():
    """Two builds over the same block byte-match — the property the
    snapshot/ingest determinism contract leans on."""
    from dgraph_tpu.ops import ivf

    corpus = _clustered(10_000, 16, centers=64, seed=34)
    a = ivf.build(corpus, seed=0)
    b = ivf.build(corpus, seed=0)
    for f in ("centroids", "order", "starts", "codes", "scales",
              "norms2"):
        assert np.array_equal(getattr(a, f), getattr(b, f)), f
    assert (a.nprobe, a.sample_recall) == (b.nprobe, b.sample_recall)


def test_ivf_sharded_mesh_merge_parity():
    """Acceptance: per-shard quantized candidates + k-way merge over
    the mesh shard count returns exactly the single-device quantized
    result (the shard ranges partition the clustered slots)."""
    from dgraph_tpu.ops import ivf
    from dgraph_tpu.parallel import make_mesh, sharded_ivf_topk

    mesh = make_mesh()
    corpus = _clustered(6_000, 16, centers=64, seed=35)
    # duplicate vectors tie at the re-rank cut: the deterministic
    # (-approx, slot) truncation must keep the SAME tied subset on
    # both paths (real embedding corpora are full of duplicates)
    corpus[100:120] = corpus[99]
    ix = ivf.build(corpus, seed=0)
    q = corpus[:4] + 0.01
    si, ss = sharded_ivf_topk(mesh, ix, corpus, q, 6, "cosine")
    di, ds = ivf.search(ix, corpus, q, 6, "cosine")
    assert np.array_equal(si, di)
    np.testing.assert_allclose(ss, ds, rtol=1e-12)
    # keep-mask flows through the sharded path too
    keep = np.ones(len(corpus), bool)
    keep[di[0][0]] = False
    si2, _ = sharded_ivf_topk(mesh, ix, corpus, q, 6, "cosine",
                              keep=keep)
    di2, _ = ivf.search(ix, corpus, q, 6, "cosine", keep=keep)
    assert np.array_equal(si2, di2)


def test_ivf_snapshot_roundtrip_byte_deterministic():
    """Codebooks persist through the snapshot plane: save -> load ->
    save produces byte-identical FILES, and the restored engine
    serves the quantized tier without retraining."""
    import os
    import tempfile

    from dgraph_tpu.storage.snapshot import load_snapshot, save_snapshot
    from dgraph_tpu.storage.vecstore import (
        ivf_from_payload, ivf_to_payload,
    )

    db = _quant_db(n=500)
    tab = db.tablets["embedding"]
    ix = tab.vector_ivf()
    assert ix is not None
    # payload round-trip is lossless
    ix2 = ivf_from_payload(ivf_to_payload(ix))
    for f in ("centroids", "order", "starts", "codes", "scales",
              "norms2"):
        assert np.array_equal(getattr(ix, f), getattr(ix2, f)), f
    with tempfile.TemporaryDirectory() as td:
        p1, p2 = os.path.join(td, "a.snap"), os.path.join(td, "b.snap")
        save_snapshot(db, p1)
        db2 = load_snapshot(p1)
        rx = db2.tablets["embedding"].vector_ivf()
        assert rx is not None and np.array_equal(rx.codes, ix.codes)
        save_snapshot(db2, p2)
        with open(p1, "rb") as f1, open(p2, "rb") as f2:
            assert f1.read() == f2.read()
        # the restored engine serves quantized with identical rows
        q = ('{ q(func: similar_to(embedding, 4, "[1.0, 0.5, -0.5, '
             '0.25]")) { uid } }')
        assert db2.query(q)["data"]["q"] == db.query(q)["data"]["q"]
        from dgraph_tpu.utils.metrics import snapshot as msnap
        assert msnap()["counters"].get(
            "query_similar_quantized_total", 0) >= 1


def test_ivf_build_failpoint():
    """The index-build seam is a registered failpoint site: an armed
    error kills the build, the exact tiers keep serving."""
    from dgraph_tpu.utils import failpoint

    assert "vecstore.build" in failpoint.SITES
    failpoint.arm("vecstore.build", "error(boom)")
    try:
        db = _quant_db(n=300)
        tab = db.tablets["embedding"]
        assert tab.vector_ivf() is None  # build died at the seam
        q = ('{ q(func: similar_to(embedding, 2, "[1.0, 0.0, 0.0, '
             '0.0]")) { uid } }')
        assert db.query(q)["data"]["q"]  # exact path serves
    finally:
        failpoint.clear()


# ---------------------------------------------------------------------------
# vector store MVCC
# ---------------------------------------------------------------------------


def _vec_db(n=8, d=2, **kw):
    db = GraphDB(prefer_device=False, **kw)
    db.alter("embedding: float32vector @index(vector) .\n"
             "name: string @index(exact) .")
    rdf = "\n".join(
        f'<0x{i:x}> <embedding> "[{i}.0, {i * 2}.0]"'
        f'^^<xs:float32vector> .\n<0x{i:x}> <name> "n{i}" .'
        for i in range(1, n + 1))
    db.mutate(set_nquads=rdf, commit_now=True)
    return db


def test_vector_view_overlay_mvcc():
    """Mutating a vector is visible at the new ts and invisible at the
    old one — the overlay side block, not a base rebuild."""
    db = _vec_db()
    tab = db.tablets["embedding"]
    db.rollup_all()
    old_ts = db.coordinator.max_assigned()
    v_old = tab.vector_view(old_ts)
    assert v_old.base_keep.all() and not len(v_old.extra_uids)

    db.mutate(set_nquads='<0x3> <embedding> "[99.0, 99.0]"'
                         '^^<xs:float32vector> .', commit_now=True)
    new_ts = db.coordinator.max_assigned()
    v_new = tab.vector_view(new_ts)
    assert not v_new.base_keep[v_new.base_uids.tolist().index(3)]
    assert v_new.extra_uids.tolist() == [3]
    assert v_new.extra_vecs[0].tolist() == [99.0, 99.0]
    # the old snapshot still reads the old vector
    v_old2 = tab.vector_view(old_ts)
    assert v_old2.base_keep.all() and not len(v_old2.extra_uids)

    q = ('{ q(func: similar_to(embedding, 1, "[99.0, 99.0]", '
         '"euclidean")) { uid } }')
    assert db.query(q, read_ts=old_ts)["data"]["q"] != \
        db.query(q, read_ts=new_ts)["data"]["q"]
    assert db.query(q, read_ts=new_ts)["data"]["q"] == [{"uid": "0x3"}]

    # deleting the vector drops the row at the new ts
    db.mutate(del_nquads='<0x3> <embedding> * .', commit_now=True)
    v3 = tab.vector_view(db.coordinator.max_assigned())
    assert not len(v3.extra_uids)
    assert not v3.base_keep[v3.base_uids.tolist().index(3)]

    # rollup folds the overlay into a fresh base
    db.rollup_all()
    v4 = tab.vector_view(db.coordinator.max_assigned())
    assert 3 not in v4.base_uids.tolist() and v4.base_keep.all()


def test_vector_mixed_dim_rejected():
    db = _vec_db(n=3)
    db.mutate(set_nquads='<0x9> <embedding> "[1.0, 2.0, 3.0]"'
                         '^^<xs:float32vector> .', commit_now=True)
    with pytest.raises(GQLError, match="dimension"):
        db.query('{ q(func: similar_to(embedding, 2, "[1.0, 2.0]")) '
                 '{ uid } }')


# ---------------------------------------------------------------------------
# similar_to end-to-end
# ---------------------------------------------------------------------------


def test_similar_to_root_order_and_score_var():
    db = _vec_db()
    res = db.query(
        '{ q(func: similar_to(embedding, 3, "[3.1, 6.1]", '
        '"euclidean")) { uid name score: val(similar_to_score) } }')
    rows = res["data"]["q"]
    assert [r["uid"] for r in rows] == ["0x3", "0x4", "0x2"]
    assert rows[0]["score"] > rows[1]["score"] > rows[2]["score"]
    # nearest-first also via the serialized JSON emitter
    js = db.query_json(
        '{ q(func: similar_to(embedding, 2, "[3.1, 6.1]", '
        '"euclidean")) { name } }')
    assert '"q":[{"name":"n3"},{"name":"n4"}]' in js


def test_similar_to_graphql_var_and_list_literal():
    db = _vec_db()
    res = db.query(
        'query nn($v: string) { q(func: similar_to(embedding, 2, $v, '
        '"euclidean")) { uid } }', variables={"v": "[1.0, 2.0]"})
    assert res["data"]["q"][0]["uid"] == "0x1"
    res2 = db.query('{ q(func: similar_to(embedding, 2, '
                    '[1.0, 2.0], "euclidean")) { uid } }')
    assert res2["data"]["q"] == res["data"]["q"]


def test_similar_to_filter_and_pagination():
    db = _vec_db()
    # filter context: k nearest among the filtered candidates only
    res = db.query(
        '{ q(func: eq(name, "n5", "n6", "n7")) '
        '@filter(similar_to(embedding, 2, "[1.0, 2.0]", "euclidean"))'
        ' { uid } }')
    assert [r["uid"] for r in res["data"]["q"]] == ["0x5", "0x6"]
    # pagination pages in SCORE space on a similar_to root
    res2 = db.query(
        '{ q(func: similar_to(embedding, 4, "[1.0, 2.0]", '
        '"euclidean"), first: 2, offset: 1) { uid } }')
    assert [r["uid"] for r in res2["data"]["q"]] == ["0x2", "0x3"]


def test_similar_to_score_var_in_later_block():
    db = _vec_db()
    res = db.query("""{
      var(func: similar_to(embedding, 3, "[1.0, 2.0]", "euclidean"))
      q(func: uid(1, 2, 3), orderdesc: val(similar_to_score)) {
        uid score: val(similar_to_score)
      }
    }""")
    rows = res["data"]["q"]
    assert [r["uid"] for r in rows] == ["0x1", "0x2", "0x3"]


def test_similar_to_errors():
    db = _vec_db()
    db.alter("vecnoidx: float32vector .")
    with pytest.raises(GQLError, match="@index\\(vector\\)"):
        db.query('{ q(func: similar_to(vecnoidx, 2, "[1.0]")) '
                 '{ uid } }')
    with pytest.raises(GQLError, match="float32vector"):
        db.query('{ q(func: has(name)) '
                 '@filter(similar_to(name, 2, "[1.0]")) { uid } }')
    with pytest.raises(GQLError, match="k must be"):
        db.query('{ q(func: similar_to(embedding, 0, "[1.0, 2.0]")) '
                 '{ uid } }')
    with pytest.raises(GQLError, match="metric"):
        db.query('{ q(func: similar_to(embedding, 2, "[1.0, 2.0]", '
                 '"manhattan")) { uid } }')
    with pytest.raises(GQLError, match="query vector"):
        db.query('{ q(func: similar_to(embedding, 2, "nope")) '
                 '{ uid } }')
    with pytest.raises(GQLError, match="not in the schema"):
        db.query('{ q(func: similar_to(nosuch, 2, "[1.0]")) { uid } }')
    # several similar_to calls + a score reader is ambiguous
    with pytest.raises(GQLError, match="ambiguous"):
        db.query("""{
          a(func: similar_to(embedding, 2, "[1.0, 2.0]")) {
            score: val(similar_to_score)
          }
          b(func: similar_to(embedding, 2, "[2.0, 1.0]")) { uid }
        }""")
    # ...but several similar_to calls with NO reader are fine
    res = db.query("""{
      a(func: similar_to(embedding, 1, "[1.0, 2.0]", "euclidean")) { uid }
      b(func: similar_to(embedding, 1, "[8.0, 16.0]", "euclidean")) { uid }
    }""")
    assert res["data"]["a"] == [{"uid": "0x1"}]
    assert res["data"]["b"] == [{"uid": "0x8"}]


def test_similar_to_quantized_e2e_planner():
    """similar_to end-to-end with a trained index: the adaptive
    planner's cold ladder picks the quantized tier (EXPLAIN shows
    it), rows match the exact-path oracle, and vec_quantized=False
    removes the tier."""
    q = ('{ q(func: similar_to(embedding, 5, "[0.5, -0.25, 1.0, '
         '0.0]")) { uid score: val(similar_to_score) } }')
    db = _quant_db()
    res = db.query(q, explain="analyze")
    vd = res["extensions"]["explain"]["tiers"]["vector"]
    assert len(vd) == 1 and vd[0]["tier"] == "quantized"
    assert vd[0]["nprobe"] >= 1 and vd[0]["rerank"] >= 20
    decs = [d for d in res["extensions"]["explain"]["tierDecisions"]
            if d["stage"] == "similar_to"]
    assert decs and decs[0]["tier"] == "quantized"
    oracle = _quant_db(vec_quantized=False)
    res2 = oracle.query(q, explain="analyze")
    assert res2["extensions"]["explain"]["tiers"]["vector"][0]["tier"] \
        == "exact"
    assert res["data"]["q"] == res2["data"]["q"]


def test_similar_to_quantized_overlay_mvcc_parity():
    """MVCC overlay parity with the tier enabled: a mutated vector is
    visible at the new read_ts and invisible at the old one, and both
    snapshots return exactly what the exact-path oracle returns —
    overlay rows ride the exact path and merge after re-rank."""
    dbs = [_quant_db(), _quant_db(vec_quantized=False)]
    assert dbs[0].tablets["embedding"].vector_ivf() is not None
    outs = []
    for db in dbs:
        old_ts = db.coordinator.max_assigned()
        db.mutate(set_nquads='<0x3> <embedding> "[9.0, 9.0, 9.0, 9.0]"'
                             '^^<xs:float32vector> .', commit_now=True)
        new_ts = db.coordinator.max_assigned()
        q = ('{ q(func: similar_to(embedding, 3, "[9.0, 9.0, 9.0, '
             '9.0]")) { uid score: val(similar_to_score) } }')
        outs.append((db.query(q, read_ts=old_ts)["data"]["q"],
                     db.query(q, read_ts=new_ts)["data"]["q"]))
    # quantized == exact oracle at BOTH snapshots, byte-for-byte
    assert outs[0] == outs[1]
    # and the overlay row is the top hit only at the new ts
    assert outs[0][1][0]["uid"] == "0x3"
    assert outs[0][0][0]["uid"] != "0x3" \
        or outs[0][0][0]["score"] != outs[0][1][0]["score"]


def test_similar_to_quantized_filter_context_stays_exact():
    """A filter-context similar_to (candidate subset) never routes
    through the probe — the recall budget doesn't survive arbitrary
    candidate masks."""
    db = _quant_db()
    db.alter("name: string @index(exact) .")
    db.mutate(set_nquads='<0x5> <name> "five" .', commit_now=True)
    res = db.query(
        '{ q(func: eq(name, "five")) @filter(similar_to(embedding, 2,'
        ' "[1.0, 0.0, 0.0, 0.0]")) { uid } }', explain="analyze")
    vd = res["extensions"]["explain"]["tiers"]["vector"]
    assert vd and vd[0]["tier"] == "exact"


def test_similar_to_quantized_sharded_tier():
    """Mesh + trained index routes through the sharded quantized
    path with rows equal to the unsharded engine's."""
    from dgraph_tpu.parallel import make_mesh

    q = ('{ q(func: similar_to(embedding, 4, "[0.5, -0.25, 1.0, '
         '0.0]")) { uid } }')
    want = _quant_db().query(q)["data"]["q"]
    db = _quant_db(mesh=make_mesh(), shard_min_edges=8)
    res = db.query(q, explain="analyze")
    vd = res["extensions"]["explain"]["tiers"]["vector"]
    assert vd and vd[0]["tier"] == "sharded_quantized"
    assert res["data"]["q"] == want


def test_similar_to_host_vs_device_tier_parity():
    """The executor's host and device tiers return identical rows for
    the same query (device engages via device_min_edges=1)."""
    rng = np.random.default_rng(12)
    vecs = rng.standard_normal((64, 8)).astype(np.float32)
    rdf = "\n".join(
        f'<0x{i + 1:x}> <embedding> "{list(map(float, vecs[i]))}"'
        '^^<xs:float32vector> .'
        for i in range(len(vecs)))
    q = ('{ q(func: similar_to(embedding, 5, "%s")) '
         '{ uid score: val(similar_to_score) } }'
         % list(map(float, vecs[17] + 0.01)))
    outs = []
    for prefer in (False, True):
        db = GraphDB(prefer_device=prefer, device_min_edges=1)
        db.alter("embedding: float32vector @index(vector) .")
        db.mutate(set_nquads=rdf, commit_now=True)
        db.rollup_all()
        outs.append(db.query(q)["data"]["q"])
    assert [r["uid"] for r in outs[0]] == [r["uid"] for r in outs[1]]
    for a, b in zip(outs[0], outs[1]):
        assert abs(a["score"] - b["score"]) < 1e-4


def test_similar_to_sharded_tier_parity():
    """With a mesh attached and shard_min_edges low, the executor
    routes scoring through the sharded tier — same rows as host."""
    from dgraph_tpu.parallel import make_mesh

    rng = np.random.default_rng(13)
    vecs = rng.standard_normal((96, 4)).astype(np.float32)
    rdf = "\n".join(
        f'<0x{i + 1:x}> <embedding> "{list(map(float, vecs[i]))}"'
        '^^<xs:float32vector> .'
        for i in range(len(vecs)))
    q = ('{ q(func: similar_to(embedding, 4, "[0.5, 0.5, 0.5, 0.5]"))'
         ' { uid } }')
    host = GraphDB(prefer_device=False)
    host.alter("embedding: float32vector @index(vector) .")
    host.mutate(set_nquads=rdf, commit_now=True)
    want = host.query(q)["data"]["q"]

    db = GraphDB(mesh=make_mesh(), shard_min_edges=8,
                 prefer_device=False)
    db.alter("embedding: float32vector @index(vector) .")
    db.mutate(set_nquads=rdf, commit_now=True)
    db.rollup_all()
    got = db.query(q)["data"]["q"]
    assert got == want
    from dgraph_tpu.utils.metrics import snapshot
    assert snapshot()["counters"].get(
        "query_device_similar_sharded_total", 0) >= 1


def test_similar_to_json_mutation_and_bulk():
    """Vector values arrive as strings in JSON mutations (schema
    converts at commit) and through the bulk loader."""
    db = GraphDB(prefer_device=False)
    db.alter("embedding: float32vector @index(vector) .")
    db.mutate(set_json=[{"uid": "0x1", "embedding": "[1.0, 0.0]"},
                        {"uid": "0x2", "embedding": "[0.0, 1.0]"}],
              commit_now=True)
    res = db.query('{ q(func: similar_to(embedding, 1, "[0.9, 0.1]"))'
                   ' { uid embedding } }')
    assert res["data"]["q"] == [{"uid": "0x1",
                                 "embedding": [1.0, 0.0]}]

    from dgraph_tpu.ingest.bulk import bulk_load
    from dgraph_tpu.gql.nquad import parse_rdf
    nqs = parse_rdf(
        '<0x1> <embedding> "[1.0, 0.0]"^^<xs:float32vector> .\n'
        '<0x2> <embedding> "[-1.0, 0.0]"^^<xs:float32vector> .')
    bdb = bulk_load(nquads=iter([nqs]),
                    schema="embedding: float32vector @index(vector) .")
    out = bdb.query('{ q(func: similar_to(embedding, 1, '
                    '"[1.0, 0.1]")) { uid } }')
    assert out["data"]["q"] == [{"uid": "0x1"}]
