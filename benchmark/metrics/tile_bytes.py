"""Device tiles: the gauge `device_cache_bytes` when the window has
closed (bytes of posting tiles resident on the device)."""


def read(ctx):
    return ctx["counters_after"].get("device_cache_bytes")
