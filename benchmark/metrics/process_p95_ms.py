"""Executor: 95th percentile of `server_latency.processing_ns`."""


def read(ctx):
    v = [r["server"]["processing_ns"] / 1e6 for r in ctx["replies"]
         if r["good"] and "processing_ns" in r["server"]]
    return ctx["stats"].percentile(v, 95.0) if v else None
