"""Fast ANN smoke gate (tools/check.sh): train + query the quantized
vector tier on a small seeded corpus and assert the contracts that
must never regress silently:

  1. the index trains at rollup (vec_index_min_rows crossed) and the
     engine routes similar_to through the quantized tier;
  2. recall@10 vs the exact-path oracle clears the floor on the
     seeded clustered corpus;
  3. MVCC overlay parity: after a vector mutation, old- and new-ts
     reads are byte-identical to the exact path's (overlay rows ride
     the exact path and merge after re-rank);
  4. the codebook snapshot round-trip is byte-deterministic.

~5 s on CPU. Exit non-zero on any violation.
"""

from __future__ import annotations

import os
import sys
import tempfile

import numpy as np

N, D, K = 4000, 16, 10
RECALL_FLOOR = 0.95


def _db(**kw):
    from dgraph_tpu.engine.db import GraphDB

    rng = np.random.default_rng(7)
    centers = rng.standard_normal((64, D), dtype=np.float32)
    vecs = centers[rng.integers(0, 64, N)] + np.float32(0.3) * \
        rng.standard_normal((N, D), dtype=np.float32)
    rdf = "\n".join(
        f'<0x{i + 1:x}> <embedding> "{list(map(float, vecs[i]))}"'
        '^^<xs:float32vector> .'
        for i in range(N))
    kw.setdefault("prefer_device", False)
    kw.setdefault("vec_index_min_rows", 1000)
    # static planner: the gate asserts the quantized tier's PLUMBING
    # (train -> route -> recall -> overlay -> snapshot), so routing
    # must be deterministic. Adaptive may legitimately route a corpus
    # this small back to host exact once observed cells warm (both
    # engines share the process-global coststore) — that behavior is
    # covered by tests/test_knn.py, not this gate.
    kw.setdefault("planner", "static")
    db = GraphDB(**kw)
    db.alter("embedding: float32vector @index(vector(ivf)) .")
    db.mutate(set_nquads=rdf, commit_now=True)
    db.rollup_all()
    return db, vecs


def main() -> int:
    db, vecs = _db()
    oracle, _ = _db(vec_quantized=False)
    tab = db.tablets["embedding"]
    ix = tab.vector_ivf()
    assert ix is not None, "index did not train at rollup"
    print(f"index: {ix.describe()}")

    # recall + tier routing over 16 seeded queries
    rng = np.random.default_rng(8)
    hits = total = 0
    for qi in rng.integers(0, N, 16):
        qv = list(map(float, vecs[qi] + np.float32(0.05)
                      * rng.standard_normal(D, dtype=np.float32)))
        q = (f'{{ q(func: similar_to(embedding, {K}, "{qv}")) '
             '{ uid } }')
        res = db.query(q, explain="analyze")
        vd = res["extensions"]["explain"]["tiers"]["vector"]
        assert vd and vd[0]["tier"] == "quantized", \
            f"tier routed {vd} instead of quantized"
        got = {r["uid"] for r in res["data"]["q"]}
        want = {r["uid"] for r in oracle.query(q)["data"]["q"]}
        hits += len(got & want)
        total += len(want)
    recall = hits / total
    print(f"recall@{K} vs exact oracle: {recall:.4f}")
    assert recall >= RECALL_FLOOR, f"recall {recall} < {RECALL_FLOOR}"

    # overlay parity at old/new read_ts. Overlay rows ride the EXACT
    # path, so: (a) an in-distribution query (near a base row — the
    # regime the recall budget holds in) is byte-identical to the
    # oracle at BOTH snapshots; (b) the mutated row surfaces through
    # the overlay at the new ts with a byte-identical score.
    for d in (db, oracle):
        d.mutate(set_nquads='<0x2> <embedding> '
                 f'"{[9.0] * D}"^^<xs:float32vector> .',
                 commit_now=True)
    old_ts = db.coordinator.max_assigned() - 1
    new_ts = db.coordinator.max_assigned()
    q_near = ('{ q(func: similar_to(embedding, 3, '
              f'"{list(map(float, vecs[1] + np.float32(0.01)))}")) '
              '{ uid score: val(similar_to_score) } }')
    for ts in (old_ts, new_ts):
        a = db.query(q_near, read_ts=ts)["data"]
        b = oracle.query(q_near, read_ts=ts)["data"]
        assert a == b, f"overlay parity broke at ts={ts}: {a} != {b}"
    assert db.query(q_near, read_ts=old_ts)["data"]["q"][0]["uid"] \
        == "0x2"  # the OLD vector still serves the old snapshot
    q_far = (f'{{ q(func: similar_to(embedding, 3, "{[9.0] * D}")) '
             '{ uid score: val(similar_to_score) } }')
    a = db.query(q_far, read_ts=new_ts)["data"]["q"]
    b = oracle.query(q_far, read_ts=new_ts)["data"]["q"]
    assert a[0]["uid"] == "0x2" and a[0] == b[0], (a, b)
    print("overlay parity: ok (old/new read_ts byte-identical)")

    # codebook snapshot round-trip: save -> load -> save byte-equal
    from dgraph_tpu.storage.snapshot import load_snapshot, save_snapshot
    with tempfile.TemporaryDirectory() as td:
        p1, p2 = os.path.join(td, "a.snap"), os.path.join(td, "b.snap")
        save_snapshot(db, p1)
        db2 = load_snapshot(p1)
        assert db2.tablets["embedding"].vector_ivf() is not None, \
            "restored tablet lost its codebooks"
        save_snapshot(db2, p2)
        with open(p1, "rb") as f1, open(p2, "rb") as f2:
            assert f1.read() == f2.read(), \
                "snapshot round-trip not byte-deterministic"
    print("snapshot round-trip: byte-deterministic, codebooks boot")
    print("ann smoke: ok")
    return 0


if __name__ == "__main__":
    sys.exit(main())
