"""Planner + gate: good replies that made at least one device call
(`server_latency.device_calls` >= 1) over good replies: per request,
where `device_ops_per_req` is a process-wide delta. A count, so a CPU
rehearsal reports it too. None where the key is not served."""


def read(ctx):
    calls = [r["server"]["device_calls"] for r in ctx["replies"]
             if r["good"] and "device_calls" in r["server"]]
    if not calls:
        return None
    return 100.0 * sum(1 for c in calls if c >= 1) / len(calls)
