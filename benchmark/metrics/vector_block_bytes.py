"""Device tiles: bytes of vector blocks resident on the device when
the window has closed, the gauge `device_vector_block_bytes` summed
over predicates (dtype x padded shape, engine/device_cache.py). None
where the gauge is not served."""

GAUGE = "device_vector_block_bytes"


def read(ctx):
    v = [v for k, v in ctx["counters_after"].items() if k.startswith(GAUGE)]
    return sum(v) if v else None
