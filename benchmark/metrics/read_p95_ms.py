"""95th percentile of client latency over every request sent in the
window (those still in flight when it closed are awaited and kept)."""


def read(ctx):
    lat = [r["latency_s"] * 1e3 for r in ctx["replies"]]
    return ctx["stats"].percentile(lat, 95.0) if lat else None
