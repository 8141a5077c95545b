"""Device tiles: bytes of bitmap adjacencies resident on the device
when the window has closed, the gauge `device_bitadj_bytes` summed
over predicates (the in-neighbour matrices, int32 x padded shape,
engine/device_cache.py; 0 once evicted). None where the gauge is not
served."""

GAUGE = "device_bitadj_bytes"


def read(ctx):
    v = [v for k, v in ctx["counters_after"].items() if k.startswith(GAUGE)]
    return sum(v) if v else None
