"""Parse + plan cache: median `server_latency.parsing_ns`."""


def read(ctx):
    v = [r["server"]["parsing_ns"] / 1e6 for r in ctx["replies"]
         if r["good"] and "parsing_ns" in r["server"]]
    return ctx["stats"].percentile(v, 50.0) if v else None
