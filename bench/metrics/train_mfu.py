"""Model FLOP/s utilization of training, %: the operations of a
full-graph GCN forward and backward per epoch (bench/work.py, from the
graph's edges and the widths) times epochs per second, over chips times
the chip's bf16 peak."""


def read(ctx):
    if not ctx["epochs"] or ctx["peaks"] is None:
        return None
    rate = ctx["work"]["model_flops"] * ctx["epochs"] / ctx["window_s"]
    return 100.0 * rate / (ctx["chips"] * ctx["peaks"]["bf16_flops"])
