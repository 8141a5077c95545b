"""Kernels: device time per request: the union of the device-op
intervals in the trace over the replies completed in the traced
interval."""


def read(ctx):
    tr = ctx["trace"]
    if not tr or tr.get("busy_s") is None:
        return None
    n = sum(1 for r in ctx["replies"]
            if tr["t_a"] <= r["t_done"] <= tr["t_b"])
    return 1e3 * tr["busy_s"] / n if n else None
