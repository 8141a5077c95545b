"""Storage: the gauge `startup_phase_seconds{phase="snapshot_decode"}`
that storage/snapshot.py's load_snapshot sets once: wire decode and the
restore of the posting and value planes, the single-threaded part of
`snapshot_load_s`. None where the gauge is not served."""


def read(ctx):
    return ctx["counters_after"].get(
        'startup_phase_seconds{phase="snapshot_decode"}')
