"""Replies inside the window that were 200, carried no `errors` and
matched the reference, over the window's seconds."""


def read(ctx):
    good = sum(1 for r in ctx["replies"] if r["in_window"] and r["good"])
    return good / ctx["window_s"]
