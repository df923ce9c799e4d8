"""Seconds per training epoch: the window's host-clock time over the
epochs completed in it (each epoch ends on the host reading its losses)."""


def read(ctx):
    return ctx["window_s"] / ctx["epochs"] if ctx["epochs"] else None
