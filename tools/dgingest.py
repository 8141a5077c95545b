"""dgingest: the distributed-ingest benchmark + CI smoke gate.

Measures the whole ROADMAP-item-3 contract end to end:

  1. ORACLE — the single-core path to a bootable cluster: `bulk_load`
     (one process, map→reduce in RAM) + `bulk_shard_outputs` (the
     second pass that shards + snapshot-encodes). Timed in its own
     subprocess so every arm pays a cold interpreter equally.
  2. CURVE — `ingest.distributed.distributed_load` at a sweep of
     (groups × map workers) configs, each in its own subprocess
     (clean fork conditions), producing bootable group-varint
     snapshots directly out of the reduce.
  3. BOOT + PARITY — the best config's shards boot a real
     ProcessCluster (`node --snapshot` per group + a Zero quorum) and
     the seeded workload's read queries run through the routed
     cluster; every `data` payload must be BYTE-IDENTICAL to the
     single-core oracle's embedded answers (uid assignment parity is
     part of the distributed design — the driver pre-assigns blank
     nodes in file order).

Output: BENCH_INGEST.json (summary + per-config RDF/s curve + reduce
phase breakdowns + parity verdict). Exit 1 on any parity mismatch, a
failed boot, or (with --min-speedup) a speedup floor violation.

  python -m tools.dgingest                      # full curve (~2 min)
  python -m tools.dgingest --smoke              # CI: ~30 s, one config
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def log(msg: str):
    print(f"[dgingest] {msg}", file=sys.stderr, flush=True)


def _sub(code: str, timeout_s: float = 900.0) -> dict:
    """Run `code` in a fresh interpreter; it must print ONE line
    starting with DGINGEST: followed by a JSON payload."""
    # ingest + parity reads are host-only: never let a child reach
    # for the chip
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=_REPO)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         cwd=_REPO, capture_output=True, text=True,
                         timeout=timeout_s)
    for line in out.stdout.splitlines():
        if line.startswith("DGINGEST:"):
            return json.loads(line[len("DGINGEST:"):])
    raise RuntimeError(
        f"subprocess produced no result: rc={out.returncode}\n"
        f"stdout: {out.stdout[-800:]}\nstderr: {out.stderr[-800:]}")


def _gen_workload(persons: int, tmp: str) -> tuple[str, list, int]:
    from dgraph_tpu.bench.workload import Workload, WorkloadConfig
    w = Workload(WorkloadConfig(persons=persons))
    rdf = os.path.join(tmp, "seed.rdf")
    quads = w.quads()
    with open(rdf, "w") as f:
        f.write("\n".join(quads) + "\n")
    reads = []
    seen = set()
    for op in w.ops(200, stream_seed=11):
        if not op.write and op.query not in seen \
                and op.kind != "similar":  # vector order ties are
            seen.add(op.query)             # score-ranked, not uid-
            reads.append(op.query)         # ranked: not a byte oracle
        if len(reads) >= 48:
            break
    return rdf, reads, len(quads)


_ORACLE_CODE = """
import json, os, time
rdf, schema_path, groups, outdir, reads_path = {args!r}
schema = open(schema_path).read()
from dgraph_tpu.ingest.bulk import bulk_load, bulk_shard_outputs
t0 = time.monotonic()
db = bulk_load([rdf], schema=schema)
t_load = time.monotonic() - t0
t0 = time.monotonic()
bulk_shard_outputs(db, groups, outdir)
t_shard = time.monotonic() - t0
answers = {{}}
for q in json.load(open(reads_path)):
    resp = json.loads(db.query_json(q))
    answers[q] = json.dumps(resp["data"], sort_keys=True)
json.dump(answers, open(os.path.join(outdir, "answers.json"), "w"))
print("DGINGEST:" + json.dumps(
    {{"t_load": round(t_load, 3), "t_shard": round(t_shard, 3)}}))
"""

_CONFIG_CODE = """
import json, time
rdf, schema_path, groups, workers, outdir = {args!r}
schema = open(schema_path).read()
from dgraph_tpu.ingest.distributed import distributed_load
t0 = time.monotonic()
m = distributed_load([rdf], schema=schema, groups=groups,
                     workers=workers, outdir=outdir, timeout_s=600)
m["stats"]["wall_s"] = round(time.monotonic() - t0, 3)
print("DGINGEST:" + json.dumps(
    {{"stats": m["stats"], "groups": m["groups"]}}))
"""


def run_boot_parity(outdir: str, groups: int, reads: list,
                    answers: dict, report_dir: str) -> dict:
    """Boot the reduced shards as a real cluster, replay the golden
    reads through the router, byte-compare every data payload."""
    from dgraph_tpu.bench.spawn import ProcessCluster
    snaps = {g: os.path.join(outdir, f"g{g}", "p.snap")
             for g in range(1, groups + 1)}
    t0 = time.monotonic()
    with ProcessCluster(groups=groups, replicas=1, zeros=1,
                        snapshots=snaps,
                        log_dir=os.path.join(report_dir,
                                             "boot-logs")) as cluster:
        cluster.wait_ready(90)
        rc = cluster.routed()
        try:
            # bulk-booted tablets register with zero from a background
            # retry loop; wait for the map to cover the seed tablets
            deadline = time.monotonic() + 30
            while time.monotonic() < deadline:
                if len(rc.tablet_map()["tablets"]) >= 8:
                    break
                time.sleep(0.3)
            boot_s = round(time.monotonic() - t0, 3)
            checked = mismatched = 0
            mismatches = []
            for q in reads:
                got = json.dumps(rc.query(q).get("data"),
                                 sort_keys=True)
                checked += 1
                if got != answers[q]:
                    mismatched += 1
                    if len(mismatches) < 3:
                        mismatches.append({"q": q[:120],
                                           "got": got[:160],
                                           "oracle":
                                           answers[q][:160]})
        finally:
            rc.close()
    return {"boot_s": boot_s, "checked": checked,
            "mismatched": mismatched, "mismatches": mismatches}


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(
        prog="dgingest", description=__doc__.split("\n\n")[0])
    ap.add_argument("--persons", type=int, default=40000)
    ap.add_argument("--groups", type=int, default=2,
                    help="reduce shards for the ORACLE arm (the "
                         "single-core bulk_shard_outputs pass)")
    ap.add_argument("--configs", default="2x1,2x2,2x4,4x4,4x8",
                    help="comma list of GROUPSxWORKERS configs to "
                         "sweep — groups is the unit of reduce "
                         "parallelism (the reference's "
                         "--reduce_shards), workers of map "
                         "parallelism")
    ap.add_argument("--min-speedup", type=float, default=0.0,
                    help="fail unless the best config beats the "
                         "single-core-to-bootable oracle by this "
                         "factor (0 = record only)")
    ap.add_argument("--report-dir", default="bench_ingest_report")
    ap.add_argument("--out", default=os.path.join(
        _REPO, "BENCH_INGEST.json"))
    ap.add_argument("--smoke", action="store_true",
                    help="CI shape: small seed, one 2-group x "
                         "2-worker config, parity-gated")
    return ap


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    if args.smoke:
        args.persons = min(args.persons, 1500)
        args.configs = "2x2"
        args.groups = 2
    os.makedirs(args.report_dir, exist_ok=True)
    tmp = tempfile.mkdtemp(prefix="dgingest-")
    t_run = time.monotonic()

    log(f"generating seeded workload: {args.persons} persons")
    rdf, reads, n_quads = _gen_workload(args.persons, tmp)
    from dgraph_tpu.bench.workload import Workload, WorkloadConfig
    schema_path = os.path.join(tmp, "schema.txt")
    with open(schema_path, "w") as f:
        f.write(Workload(WorkloadConfig(persons=args.persons))
                .schema())
    reads_path = os.path.join(tmp, "reads.json")
    with open(reads_path, "w") as f:
        json.dump(reads, f)

    # ---- oracle: single core to a bootable shard set ----
    oracle_dir = os.path.join(tmp, "oracle")
    log("oracle: single-core bulk_load + shard outputs")
    oracle = _sub(_ORACLE_CODE.format(args=(
        rdf, schema_path, args.groups, oracle_dir, reads_path)))
    answers = json.load(open(os.path.join(oracle_dir,
                                          "answers.json")))
    t_oracle = oracle["t_load"] + oracle["t_shard"]
    oracle.update({
        "quads": n_quads,
        "rdf_per_s_load": round(n_quads / oracle["t_load"], 1),
        "rdf_per_s_bootable": round(n_quads / t_oracle, 1)})
    log(f"oracle: load {oracle['t_load']}s + shard "
        f"{oracle['t_shard']}s = {round(t_oracle, 2)}s")

    # ---- the curve: one subprocess per config ----
    curve = []
    best = None
    for cfg in args.configs.split(","):
        g, wk = (int(x) for x in cfg.strip().split("x"))
        outdir = os.path.join(tmp, f"dist-g{g}-w{wk}")
        log(f"distributed: {g} groups x {wk} workers")
        got = _sub(_CONFIG_CODE.format(args=(
            rdf, schema_path, g, wk, outdir)))
        st = got["stats"]
        row = {
            "groups": g, "workers": wk,
            "wall_s": st["wall_s"], "map_s": st["map_s"],
            "reduce_s": st["reduce_s"],
            "group_stats": st.get("group_stats", {}),
            "chunks": st["chunks"],
            "shuffled_mb": round(st["shuffled_bytes"] / 1e6, 2),
            "rdf_per_s": round(n_quads / st["wall_s"], 1),
            "speedup_vs_bulk_load":
                round(oracle["t_load"] / st["wall_s"], 3),
            "speedup_vs_bootable":
                round(t_oracle / st["wall_s"], 3),
            "outdir": outdir,
            "tablet_groups": got["groups"],
        }
        curve.append(row)
        log(f"  {row['wall_s']}s ({row['rdf_per_s']} RDF/s, "
            f"{row['speedup_vs_bootable']}x vs bootable oracle)")
        if best is None or row["wall_s"] < best["wall_s"]:
            best = row

    # ---- boot the best config's shards + byte parity ----
    log(f"booting best config ({best['groups']}g x "
        f"{best['workers']}w) on a ProcessCluster")
    parity = run_boot_parity(best["outdir"], best["groups"], reads,
                             answers, args.report_dir)
    log(f"parity: {parity['checked'] - parity['mismatched']}/"
        f"{parity['checked']} byte-identical, boot "
        f"{parity['boot_s']}s")

    summary = {
        "metric": "ingest_rdf_per_s",
        "value": best["rdf_per_s"],
        "unit": "rdf/s",
        "quads": n_quads,
        "best_config": f"{best['groups']}gx{best['workers']}w",
        "speedup_vs_bulk_load": best["speedup_vs_bulk_load"],
        "speedup_vs_bootable_oracle": best["speedup_vs_bootable"],
        "speedup_2gx2w": next(
            (r["speedup_vs_bootable"] for r in curve
             if (r["groups"], r["workers"]) == (2, 2)), None),
        "parity_ok": parity["mismatched"] == 0
        and parity["checked"] > 0,
        "smoke": bool(args.smoke),
        "wall_s": round(time.monotonic() - t_run, 1),
    }
    out = {"summary": summary, "oracle": oracle, "curve": curve,
           "parity": parity}
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1, sort_keys=True)
    print(json.dumps(summary))

    bad = []
    if not summary["parity_ok"]:
        bad.append(f"parity: {parity['mismatched']}/"
                   f"{parity['checked']} mismatched "
                   f"{parity['mismatches']}")
    if args.min_speedup and \
            best["speedup_vs_bootable"] < args.min_speedup:
        bad.append(f"speedup {best['speedup_vs_bootable']} < "
                   f"{args.min_speedup}")
    if bad:
        log("INGEST GATE FAILED: " + "; ".join(bad))
        return 1
    log("ingest ok")
    return 0


if __name__ == "__main__":
    sys.exit(main())
