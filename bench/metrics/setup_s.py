"""Seconds from process start to the start of the window (host clock):
graph, plan build, weights, the check epochs and, in a run that
compiles, compilation."""


def read(ctx):
    return ctx["setup_s"]
