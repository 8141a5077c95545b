"""Storage: `alpha --snapshot` told to serve until /health answers."""


def read(ctx):
    return ctx["snapshot_load_s"]
