#!/usr/bin/env python3
"""Readings of a sharded cell's planted exchange fault, beside
`calibrate.py`'s.

    python3 bench/calibrate_dist.py --workload arxiv-gcn-dist4.train \
        --seeds 101 102 [--out readings.json]

For each seed, the reference with the exchange between chips left out
(the op's `drop_exchange`: every history row another rank owns reads as
zeros) against the reference, from the same inputs: an upper reading,
with the harness's verdict against the configuration's limits, which has
to read false. The check is one epoch from zeroed tables, so the fault
shows only through rows that the epoch's earlier supersteps pushed.
Prints one JSON line per reading; not part of a benchmark run.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax
    from bench import harness

    found = harness.load_cell(args.workload)
    config, n_check = found["config"], found["traffic"]["check_epochs"]
    op = harness.load_op(config)
    # both sides are the reference, which runs on one device: one chip
    # of the cell's host, or a host with one chip, will do
    harness.require_chips(1)
    jax.config.update("jax_default_matmul_precision",
                      config["matmul_precision"])
    rows = []
    for seed in args.seeds:
        t = time.perf_counter()
        inp = harness.make_inputs(config, seed)
        want = op.outputs(config, inp, n_check)
        fault = op.outputs(config, inp, n_check, drop_exchange=True)
        vals = harness.compare(fault, want, inp.params)
        vals["correct"] = harness.is_correct(
            harness.checks(vals, config["limits"]), 0)
        row = {"kind": "drop_exchange", "seed": seed, **vals,
               "seconds": time.perf_counter() - t}
        rows.append(row)
        print(json.dumps(row), flush=True)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(rows, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
