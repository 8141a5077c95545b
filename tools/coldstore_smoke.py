"""Cold-store smoke gate (tools/check.sh, ~30s): bulk-seed a
multi-group store straight into the cold store (storage/bulkseed —
group-varint blobs, no per-edge apply), reopen it under a tablet budget
smaller than the working set with the async prefetch pipeline on, and
hold the three-arm parity bar while decodes happen cold:

  fused    — whole-plan device executables (query/fusion.py)
  staged   — the same engine, fused tier disabled
  postings — a reopen with every tier pinned off: the exact oracle

Catches bulk-seed blob drift (a synthesized tablet restore_tablet
decodes differently than a rolled-up one), prefetch handover bugs
(stale/duplicate tablets served), and budget-eviction regressions. It
compares bytes and counts; it times nothing.

Per group g (uids dense in [g*U+1, (g+1)*U]):
  score_g  : int    @index(int)   — U postings, 4096 distinct values
  tier_g   : string @index(exact) — U postings, 4 labels
  region_g : string @index(exact) — U postings, 8 labels
  follow_g : [uid]                — follow_srcs x follow_deg
"""

import shutil
import sys
import tempfile

import numpy as np

SCORE_DOMAIN = 4096
TIERS = ["gold", "silver", "bronze", "iron"]
REGIONS = [f"r{i}" for i in range(8)]


def schema_text(groups: int) -> str:
    lines = []
    for g in range(groups):
        lines.append(f"score_{g}: int @index(int) .")
        lines.append(f"tier_{g}: string @index(exact) .")
        lines.append(f"region_{g}: string @index(exact) .")
        lines.append(f"follow_{g}: [uid] .")
    return "\n".join(lines) + "\n"


def seed(store_dir: str, groups: int, uids: int,
         follow_srcs: int, follow_deg: int, base_ts: int = 1) -> int:
    """Synthesize + install every group's tablets; returns the edges
    seeded."""
    from dgraph_tpu.engine.lazy_tablets import TabletStore
    from dgraph_tpu.storage import bulkseed

    follow_srcs = min(follow_srcs, uids)
    schema = schema_text(groups)
    # raw TabletStore, NOT a GraphDB: an engine would re-save its own
    # (zero) high-water ts over the seeded one at close
    store = TabletStore(store_dir)
    for g in range(groups):
        rng = np.random.default_rng(1000 + g)
        base = np.uint64(g) * np.uint64(uids)
        u = base + np.arange(1, uids + 1, dtype=np.uint64)
        scores = rng.integers(0, SCORE_DOMAIN, uids).astype(np.int64)
        tcodes = rng.integers(0, len(TIERS), uids).astype(np.int64)
        rcodes = rng.integers(0, len(REGIONS), uids).astype(np.int64)
        srcs = u[:follow_srcs]
        indptr = np.arange(follow_srcs + 1, dtype=np.int64) * follow_deg
        # each row: sorted sample of in-group uids
        dsts = (base + 1 +
                rng.integers(0, uids, follow_srcs * follow_deg)
                .astype(np.uint64))
        dsts = dsts.reshape(follow_srcs, follow_deg)
        dsts.sort(axis=1)
        # group-varint rows must be strictly ascending: dedup by bump
        dsts = (dsts + np.arange(follow_deg, dtype=np.uint64)
                * np.uint64(uids))
        bulkseed.seed_store(store, schema, [
            (f"score_{g}", bulkseed.int_tablet_blob(
                schema, u, scores, base_ts)),
            (f"tier_{g}", bulkseed.str_tablet_blob(
                schema, u, TIERS, tcodes, base_ts)),
            (f"region_{g}", bulkseed.str_tablet_blob(
                schema, u, REGIONS, rcodes, base_ts)),
            (f"follow_{g}", bulkseed.uid_tablet_blob(
                schema, srcs, indptr, dsts.reshape(-1), base_ts)),
        ], max_ts=base_ts)
    store.compact()  # fold the WAL before the reopen
    store.close()
    return groups * (3 * uids + follow_srcs * follow_deg)


def shapes(g: int) -> dict[str, str]:
    """The summary mix, instantiated for group g. Every shape is an
    order+page block the fused tier covers; filters span rank leaves
    (int ineq/eq/between) and set leaves (string eq)."""
    return {
        "S1-desc-ge": (
            f'{{ q(func: eq(tier_{g}, "gold"), orderdesc: score_{g},'
            f' first: 10) @filter(ge(score_{g}, 2048)) {{ uid }} }}'),
        "S2-asc-offset": (
            f'{{ q(func: eq(tier_{g}, "silver"), orderasc: score_{g},'
            f' first: 20, offset: 40)'
            f' @filter(lt(score_{g}, 3000)) {{ uid }} }}'),
        "S3-setleaf-and": (
            f'{{ q(func: eq(tier_{g}, "silver"), orderdesc: score_{g},'
            f' first: 10) @filter(eq(region_{g}, "r1")'
            f' AND le(score_{g}, 3500)) {{ uid }} }}'),
        "S4-plain-order": (
            f'{{ q(func: eq(tier_{g}, "bronze"), orderasc: score_{g},'
            f' first: 50) {{ uid }} }}'),
        "S5-between-or": (
            f'{{ q(func: eq(tier_{g}, "iron"), orderdesc: score_{g},'
            f' first: 25) @filter(between(score_{g}, 256, 3840)'
            f' OR eq(region_{g}, "r3")) {{ uid }} }}'),
    }


def _answers(db, groups: int) -> dict:
    return {(g, name): [r["uid"] for r in db.query(q)["data"]["q"]]
            for g in range(groups) for name, q in shapes(g).items()}


def main() -> int:
    from dgraph_tpu.engine.db import GraphDB
    from dgraph_tpu.utils import metrics

    groups, uids, budget = 2, 12288, 2 << 20
    d = tempfile.mkdtemp(prefix="coldstore_smoke_")
    try:
        edges = seed(d, groups, uids, follow_srcs=1024, follow_deg=16)
        db = GraphDB(store_dir=d, tablet_budget=budget,
                     prefetch_workers=2, planner="adaptive")
        try:
            # cold pass: first touch of every group decodes from the
            # store; the prefetch pipeline overlaps what it can
            before = metrics.counters_snapshot()
            cold = _answers(db, groups)
            loads = metrics.counters_delta(before).get(
                "tablet_store_loads", 0)
            pf = db.prefetcher.stats()
            fused = _answers(db, groups)
            db.prefer_fused = False
            staged = _answers(db, groups)
        finally:
            db.close()
        oracle_db = GraphDB(store_dir=d, tablet_budget=budget,
                            prefer_device=False, prefer_columnar=False,
                            prefer_compressed=False, prefer_fused=False)
        try:
            oracle = _answers(oracle_db, groups)
        finally:
            oracle_db.close()
        assert any(fused.values()), "every answer is empty"
        assert fused == staged, "fused != staged"
        assert fused == cold, "warm fused != cold pass"
        assert fused == oracle, "fused != postings oracle"
        assert loads > 0, "budget never forced a cold load"
        assert pf.get("scheduled", 0) > 0 and \
            pf.get("hits", 0) + pf.get("waits", 0) > 0, \
            f"prefetch pipeline never engaged: {pf}"
        print(f"coldstore smoke: {edges:,} seeded edges, "
              f"{groups} groups under {budget >> 20}MB budget, "
              f"{loads} cold loads, "
              f"prefetch {pf.get('hits', 0)} hits — "
              f"three-arm parity ok")
        return 0
    finally:
        shutil.rmtree(d, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())
