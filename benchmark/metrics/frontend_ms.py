"""Front end (server/http.py): median of client latency minus the
server's own `server_latency.total_ns`: socket, HTTP framing, locks
and queueing outside the engine's clock."""


def read(ctx):
    d = [r["latency_s"] * 1e3 - r["server"]["total_ns"] / 1e6
         for r in ctx["replies"] if r["good"] and "total_ns" in r["server"]]
    return ctx["stats"].percentile(d, 50.0) if d else None
