"""Share of its roofline reached by the block SpMM (`bcsr_spmm`: the
first layer's forward aggregation and the backward of every later one),
%: the least time the chip needs for the work those aggregations need
(bench/work.py; never the dense blocks) over the kernel's device time."""

PATTERN = r"bcsr_spmm"


def read(ctx):
    t, peaks = ctx["trace"], ctx["peaks"]
    if t is None or peaks is None or not ctx["epochs"]:
        return None
    secs = t.op_seconds(PATTERN)
    if secs <= 0:
        return None
    agg = ctx["work"]["aggregation"]
    flops = (agg[0]["forward"][0]
             + sum(w["backward"][0] for w in agg[1:])) * ctx["epochs"]
    nbytes = (agg[0]["forward"][1]
              + sum(w["backward"][1] for w in agg[1:])) * ctx["epochs"]
    least = max(flops / peaks["bf16_flops"], nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least / secs
