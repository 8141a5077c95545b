"""The served sharded tier on real devices, through the library
boundary the repo has: GraphDB(mesh=make_mesh(n)) over a bulk-loaded
snapshot (there is no CLI flag that hands a mesh to `alpha`).

    python -m tools.mesh_smoke --snapshot store.snap --scale 300 \\
        [--devices 4]

Runs the expand and @recurse queries of chip_smoke.py in ONE process
that owns all n devices, first with the mesh and then with
prefer_device=False on the same engine, and requires:
  * byte-identical `data` for every query,
  * query_sharded_expand_total moved,
  * every sharded adjacency array spans all n devices.
It prints what make_mesh(n) built and, per sharded tile, which devices
hold which slice — that is how the `tablet` axis's treatment of the
tiles (split? replicated?) is read off the run instead of guessed.
One JSON line on stdout; non-zero exit on any failure. The platform
rule is the repo's: JAX_PLATFORMS, else the chip, else an error.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

_QUERIES = ("q001_eq_root_reverse", "q049_ignorereflex",
            "expand_rev_fwd", "x100_recurse_depth3")


def _tile_layout(sadj) -> list[dict]:
    """Per bucket array: how many devices hold it and which slice of
    the leading (shard) dim each device has."""
    out = []
    for b in sadj.buckets:
        for name, arr in (("src", b.src), ("neighbors", b.neighbors)):
            out.append({
                "array": f"deg{b.degree}.{name}",
                "shape": list(arr.shape),
                "spec": str(arr.sharding.spec),
                "devices": len(arr.sharding.device_set),
                "shard_rows_by_device": {
                    str(s.device.id): [s.index[0].start or 0,
                                       s.index[0].stop]
                    for s in arr.addressable_shards}})
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--snapshot", required=True)
    ap.add_argument("--scale", type=int, required=True,
                    help="the dataset scale the snapshot was built at")
    ap.add_argument("--devices", type=int, default=4)
    args = ap.parse_args(argv)

    from dgraph_tpu.utils.backend import (
        configure_compile_cache, device_report, require_devices,
    )
    configure_compile_cache()
    devs = require_devices()
    if len(devs) < args.devices:
        raise SystemExit(f"need {args.devices} devices, have {devs}")

    import chip_smoke
    from dgraph_tpu.engine.db import GraphDB
    from dgraph_tpu.parallel import make_mesh
    from dgraph_tpu.storage.snapshot import load_snapshot
    from dgraph_tpu.utils import metrics

    mesh = make_mesh(args.devices)
    t0 = time.monotonic()
    db = load_snapshot(args.snapshot, GraphDB(mesh=mesh))
    load_s = time.monotonic() - t0
    sys.path.insert(0, os.path.join(_REPO, "tests"))
    from golden.workload import load_workload
    pool = dict(load_workload(args.scale))
    pool.update({n: q for n, q, _ in
                 chip_smoke.build_queries(args.scale)})
    queries = [(n, pool[n]) for n in _QUERIES]

    before = metrics.counters_snapshot()
    sharded = {n: json.dumps(db.query(q)["data"], sort_keys=True)
               for n, q in queries}
    moved = {k: v for k, v in metrics.counters_delta(before).items()
             if k.startswith(("query_sharded_", "query_device_"))
             and v}
    tiles = {}
    for pred, tab in db.tablets.items():
        for attr in ("_device_sadj", "_device_sadj_r"):
            sadj = getattr(tab, attr, None)
            if sadj is not None:
                tiles[pred + ("~" if attr.endswith("_r") else "")] = \
                    _tile_layout(sadj)

    db.prefer_device = False
    host = {n: json.dumps(db.query(q)["data"], sort_keys=True)
            for n, q in queries}

    fails = [f"{n}: sharded and host answers differ"
             for n, _ in queries if sharded[n] != host[n]]
    if not any(k.startswith("query_sharded_expand_total")
               for k in moved):
        fails.append("query_sharded_expand_total stayed flat")
    if not tiles:
        fails.append("no sharded adjacency was built")
    for pred, layout in tiles.items():
        for a in layout:
            if a["devices"] != args.devices:
                fails.append(f"{pred} {a['array']} spans "
                             f"{a['devices']} devices")
    print(json.dumps({
        "ok": not fails, "failures": fails,
        "device": device_report(devs),
        "mesh": {k: int(v) for k, v in mesh.shape.items()},
        "scale": args.scale, "snapshot_load_s": round(load_s, 1),
        "queries": [n for n, _ in queries], "moved": moved,
        "sharded_tiles": tiles, "claim": None}))
    return 1 if fails else 0


if __name__ == "__main__":
    sys.exit(main())
