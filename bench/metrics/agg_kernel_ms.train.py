"""Device milliseconds per epoch in the aggregation kernels: the fused
history-gather SpMM (`gather_spmm`) and the block SpMM (`bcsr_spmm`,
forward of the first layer and every backward), from the trace."""

PATTERN = r"gather_spmm|bcsr_spmm"


def read(ctx):
    t = ctx["trace"]
    if t is None or not ctx["epochs"]:
        return None
    s = t.op_seconds(PATTERN)
    return 1e3 * s / ctx["epochs"] if s > 0 else None
