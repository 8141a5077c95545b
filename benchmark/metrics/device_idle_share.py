"""Device: 1 - busy / traced interval, from the profiler trace of a
slice of the window."""


def read(ctx):
    tr = ctx["trace"]
    if not tr or tr.get("busy_s") is None or not tr.get("window_s"):
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
