"""Front end: the share of the window in which the server's collector
had every request thread stopped for a full collection: the delta of
`process_gc_pause_seconds_total{gen="2"}` (utils/metrics.py watch_gc)
between the counters read before the window and after it, over the
window's seconds. None where the counter is not served."""

KEY = 'process_gc_pause_seconds_total{gen="2"}'


def read(ctx):
    a, b = ctx["counters_after"], ctx["counters_before"]
    if KEY not in a or ctx["window_s"] <= 0:
        return None
    return 100.0 * (a[KEY] - b.get(KEY, 0.0)) / ctx["window_s"]
