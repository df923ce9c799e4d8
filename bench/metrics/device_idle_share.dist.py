"""Share of the traced window in which no op ran, %, busy averaged over
the cell's chips: the one-chip reader `device_idle_share.train`, since
`Summary.busy_s` averages over the trace's chips."""
from bench.harness import load_reader

read = load_reader("device_idle_share.train")
