"""Device milliseconds per epoch in the aggregation kernels of the
sharded epoch (`gather_spmm`, `bcsr_spmm`), averaged over chips: the
one-chip reader `agg_kernel_ms.train`, since the trace's `Summary`
averages op times over its chips."""
from bench.harness import load_reader

read = load_reader("agg_kernel_ms.train")
