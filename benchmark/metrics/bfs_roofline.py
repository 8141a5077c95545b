"""Kernels: roofline share of the device traversal, the program
ops/bitgraph.py's bfs_traverse dispatches (`jit_bfs_traverse`, every
shape of it): calls x the least bytes of a call
(costs/jit_bfs_traverse.py: the levels the query asks for x (the
adjacency's edges, the gauge `device_bitadj_edges`, x 4 B + two
bitmaps of the graph's vertices)), over the chip's HBM bandwidth, over
the program's device time in the trace. The mix sends its templates
equally often, so ONE traversal's levels are the mean of the
templates' `depth` - 1. It multiplies CALLS by one traversal's least
bytes: since PR 34 a call carries up to eight traversals
(`recurse_lanes_per_call`) and runs to its deepest lane's depth, so
the bytes are a lower bound of a call the more so the more it
carries, and the share reads lower than one traversal alone would
give. Memory-bound by statement; the share cannot pass 100%. It reads
far under 1%: a level is gather-bound, an index a descriptor, and
that is the finding. None where the program serves no such gauge or
ran no such program."""

import os
import re

PROGRAM = "jit_bfs_traverse"
GAUGE = "device_bitadj_edges"
_DEPTH = re.compile(r"@recurse\(\s*depth:\s*(\d+)")


def read(ctx):
    tr = ctx["trace"]
    if not tr or not tr.get("programs") or not ctx["peaks"]:
        return None
    edges = sum(v for k, v in ctx["counters_after"].items()
                if k.startswith(GAUGE))
    seconds = calls = 0
    for name, s, n in tr["programs"]:
        if name.startswith(PROGRAM):
            seconds, calls = seconds + s, calls + n
    hops = {}
    for e in ctx["pool"]:
        m = _DEPTH.search(e["query"])
        if m:
            hops[e["name"]] = int(m.group(1)) - 1
    if edges <= 0 or seconds <= 0 or not hops:
        return None
    cost = ctx["load_module"](os.path.join(
        ctx["bench_dir"], "costs", PROGRAM + ".py"))
    levels = sum(hops.values()) / len(hops)
    each = cost.least_bytes({
        "edges": edges, "vertices": int(ctx["facts"]["vertices"]),
        "levels": levels})
    least_s = calls * each / ctx["peaks"]["hbm_bytes_per_s"]
    ctx["notes"].append(
        f"roofline: {PROGRAM}: {calls} calls, {each:.0f} B each at "
        f"least ({levels:.2f} levels of {edges:.0f} edges), "
        f"{seconds:.6f} s on the device "
        f"({1e3 * seconds / calls:.3f} ms a call), {least_s:.6f} s at "
        f"{ctx['peaks']['hbm_bytes_per_s']:.3g} B/s")
    return 100.0 * least_s / seconds
