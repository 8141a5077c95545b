"""Encode: median `server_latency.encoding_ns`."""


def read(ctx):
    v = [r["server"]["encoding_ns"] / 1e6 for r in ctx["replies"]
         if r["good"] and "encoding_ns" in r["server"]]
    return ctx["stats"].percentile(v, 50.0) if v else None
