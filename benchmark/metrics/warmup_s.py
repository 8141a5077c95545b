"""Storage / compile cache: the warm-up pass, every pool query once
on one connection (executables loaded from the persistent cache, or
compiled in a checkout's first run; tiles uploaded)."""


def read(ctx):
    return ctx["warmup_s"]
