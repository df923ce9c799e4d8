"""Device milliseconds per epoch in the history push kernel
(`scatter_rows`) of the sharded epoch, each chip into its own shard,
averaged over chips: the one-chip reader `history_push_ms.train`."""
from bench.harness import load_reader

read = load_reader("history_push_ms.train")
