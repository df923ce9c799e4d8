"""Model FLOP/s utilization of sharded training, %: the one-chip reader
`train_mfu`, which already divides by the cell's chips times the chip's
bf16 peak."""
from bench.harness import load_reader

read = load_reader("train_mfu")
