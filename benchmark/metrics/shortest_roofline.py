"""Kernels: roofline share of the device's shortest-path search, the
program ops/bitgraph.py's bfs_paths dispatches (`jit_bfs_paths`,
every shape of it): calls x the least bytes of a call
(costs/jit_bfs_paths.py: the levels a call RAN x (this seed's edges
x 4 B + two bitmaps of its vertices)), over the chip's HBM
bandwidth, over the program's device time in the trace. The levels
are the window's mean a call (`shortest_levels_run_total` over
`shortest_calls_total`, as deltas: the program counts them, because a
call ends when its lanes have met their sources and not at the
query's depth); edges and vertices are the dataset's own facts. It
multiplies CALLS by one search's least bytes: a call carries up to
eight pairs (`shortest_lanes_per_call`), so the bytes are a lower
bound of a call the more so the more it carries. Memory-bound by
statement; the share cannot pass 100%. None where the program serves
no such counters or ran no such program."""

import os

PROGRAM = "jit_bfs_paths"
LEVELS = "shortest_levels_run_total"
CALLS = "shortest_calls_total"


def read(ctx):
    tr = ctx["trace"]
    if not tr or not tr.get("programs") or not ctx["peaks"]:
        return None
    a, b = ctx["counters_after"], ctx["counters_before"]
    if LEVELS not in a or CALLS not in a:
        return None
    counted = a[CALLS] - b.get(CALLS, 0)
    seconds = calls = 0
    for name, s, n in tr["programs"]:
        if name.startswith(PROGRAM):
            seconds, calls = seconds + s, calls + n
    if counted <= 0 or seconds <= 0:
        return None
    cost = ctx["load_module"](os.path.join(
        ctx["bench_dir"], "costs", PROGRAM + ".py"))
    levels = (a[LEVELS] - b.get(LEVELS, 0)) / counted
    edges = sum(ctx["facts"]["edges"].values())
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
