"""Median client latency, request's first byte to reply's last, over
every request sent in the window."""


def read(ctx):
    lat = [r["latency_s"] * 1e3 for r in ctx["replies"]]
    return ctx["stats"].percentile(lat, 50.0) if lat else None
