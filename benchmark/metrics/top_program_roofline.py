"""Kernels: roofline share of the costed device program with most
time in the trace: its least bytes (costs/<program>.py) times its calls,
over the chip's HBM bandwidth, over its device time. Memory-bound by
statement; the bytes are a lower bound, so the share cannot pass 100%.
Which program it was is said on an earlier line of the run."""

import os


def read(ctx):
    tr = ctx["trace"]
    if not tr or not tr.get("programs") or not ctx["peaks"]:
        return None
    costs = ctx["load_module"](os.path.join(ctx["bench_dir"],
                                            "kernel_costs.py"))
    templates = {e["name"] for e in ctx["pool"]}
    for name, seconds, calls in tr["programs"]:
        cost = costs.find(name, ctx["bench_dir"], ctx["load_module"])
        if cost is None or cost.TEMPLATE not in templates or seconds <= 0:
            continue
        each = cost.least_bytes(costs.graph_sizes(
            ctx["dataset"], ctx["scale"], ctx["facts"], ctx["pool"]))
        least_s = each * calls / ctx["peaks"]["hbm_bytes_per_s"]
        ctx["notes"].append(
            f"roofline: {name} ({cost.TEMPLATE}): {calls} calls, "
            f"{each:.0f} B each at least, {seconds:.6f} s on the "
            f"device, {least_s:.6f} s at {ctx['peaks']['hbm_bytes_per_s']:.3g} B/s")
        return 100.0 * least_s / seconds
    return None
