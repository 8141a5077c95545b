"""Device: compile-cache entries after the window minus before it.
Must read 0; a run in which it does not is `correct: false`."""


def read(ctx):
    return ctx["compiles_in_window"]
