"""Process start to window start: the chip taken, the snapshot and
the reference built or found, the snapshot loaded, the warm-up pass.
Time spent only waiting for the reference child is left out."""


def read(ctx):
    return ctx["setup_s"]
