"""Kernels: roofline share of the k-hop traversal whose adjacency is
split over a mesh's chips, the program ops/bitgraph.py's
bfs_traverse_sharded dispatches (`jit_bfs_traverse_sharded`, every
shape of it), reckoned for ONE chip: calls x the least bytes a chip
moves in a call (costs/jit_bfs_traverse_sharded.py: the levels the
query asks for x (a chip's share of the adjacency's edges, the gauges
`device_bitadj_edges` over `device_bitadj_shards`, x 4 B + two
bitmaps of ALL the graph's vertices)), over ONE chip's HBM bandwidth,
over the program's device time a chip in the trace. The trace's
reduction (trace_reduce.py) gives a program's time averaged over the
chips and its events counted over all of them, so calls are events
over chips. The mix sends its templates equally often, so ONE
traversal's levels are the mean of the templates' `depth` - 1; a call
carries up to eight traversals (`recurse_sharded_lanes_per_call`) and
runs to its deepest lane's depth, so the bytes are a lower bound of a
call and the share cannot pass 100%. It reads far under 1%: a level is
gathers and streamed hub rows, and that is the finding. None where the
program serves no such gauges (a one-chip program, an older one) or
ran no such program."""

import os
import re

PROGRAM = "jit_bfs_traverse_sharded"
EDGES, SHARDS = "device_bitadj_edges", "device_bitadj_shards"
_DEPTH = re.compile(r"@recurse\(\s*depth:\s*(\d+)")


def read(ctx):
    tr = ctx["trace"]
    if not tr or not tr.get("programs") or not ctx["peaks"]:
        return None
    after = ctx["counters_after"]
    edges = sum(v for k, v in after.items() if k.startswith(EDGES))
    shards = max((v for k, v in after.items() if k.startswith(SHARDS)),
                 default=0)
    chips = int(tr.get("chips") or 0)
    seconds = events = 0
    for name, s, n in tr["programs"]:
        if name.startswith(PROGRAM):
            seconds, events = seconds + s, events + n
    hops = {}
    for e in ctx["pool"]:
        m = _DEPTH.search(e["query"])
        if m:
            hops[e["name"]] = int(m.group(1)) - 1
    if edges <= 0 or shards < 2 or chips < 1 or seconds <= 0 \
            or events <= 0 or not hops:
        return None
    cost = ctx["load_module"](os.path.join(
        ctx["bench_dir"], "costs", PROGRAM + ".py"))
    levels = sum(hops.values()) / len(hops)
    calls = events / chips
    each = cost.least_bytes({
        "edges": edges, "vertices": int(ctx["facts"]["vertices"]),
        "chips": shards, "levels": levels})
    least_s = calls * each / ctx["peaks"]["hbm_bytes_per_s"]
    ctx["notes"].append(
        f"roofline: {PROGRAM}: {calls:.0f} calls on {chips} chips, "
        f"{each:.0f} B a chip each at least ({levels:.2f} levels of "
        f"{edges:.0f} edges over {shards:.0f} chips), {seconds:.6f} s a "
        f"chip on the device ({1e3 * seconds / calls:.3f} ms a call), "
        f"{least_s:.6f} s at {ctx['peaks']['hbm_bytes_per_s']:.3g} B/s")
    return 100.0 * least_s / seconds
