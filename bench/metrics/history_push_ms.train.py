"""Device milliseconds per epoch in the history push kernel
(`scatter_rows`, quantizing for int8 stores), from the trace."""

PATTERN = r"scatter_rows"


def read(ctx):
    t = ctx["trace"]
    if t is None or not ctx["epochs"]:
        return None
    s = t.op_seconds(PATTERN)
    return 1e3 * s / ctx["epochs"] if s > 0 else None
