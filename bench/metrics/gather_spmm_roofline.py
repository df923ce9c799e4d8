"""Share of its roofline reached by the fused history-gather SpMM
(`gather_spmm`: the forward aggregation of every layer after the first),
%: the least time the chip needs for the work the aggregation needs
(bench/work.py: 2 E d operations, rows read once, edges, output rows;
never the dense blocks) over the kernel's device time in the trace."""

PATTERN = r"gather_spmm"


def read(ctx):
    t, peaks = ctx["trace"], ctx["peaks"]
    if t is None or peaks is None or not ctx["epochs"]:
        return None
    secs = t.op_seconds(PATTERN)
    if secs <= 0:
        return None
    layers = ctx["work"]["aggregation"][1:]
    flops = sum(w["forward"][0] for w in layers) * ctx["epochs"]
    nbytes = sum(w["forward"][1] for w in layers) * ctx["epochs"]
    least = max(flops / peaks["bf16_flops"], nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least / secs
