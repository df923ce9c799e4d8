"""Device milliseconds per epoch in the collectives of the sharded epoch:
the halo exchange (`collective-permute`), the summed gradients and
losses (`all-reduce`) and any `all-gather`, from the trace, averaged
over chips."""

PATTERN = r"^(collective-permute|all-reduce|all-gather)"


def read(ctx):
    t = ctx["trace"]
    if t is None or not ctx["epochs"]:
        return None
    s = t.op_seconds(PATTERN)
    return 1e3 * s / ctx["epochs"] if s > 0 else None
