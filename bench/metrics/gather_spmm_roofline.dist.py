"""Share of its roofline reached by the fused history-gather SpMM on the
chips of the sharded epoch, %: the least time the whole graph's work
needs (the one-chip reader `gather_spmm_roofline`, bench/work.py) split
over the chips, over the kernel's device time averaged over chips."""
from bench.harness import load_reader

_whole = load_reader("gather_spmm_roofline")


def read(ctx):
    share = _whole(ctx)
    return None if share is None else share / ctx["chips"]
