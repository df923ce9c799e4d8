#!/usr/bin/env python3
"""Run one benchmark cell once on the chips of this machine.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

From the root of a checkout. The cell, its configuration, traffic mix and
metrics, the driver of its traffic's kind and its op are found by name
from `BENCHMARK.json` (see `bench/harness.py`).
The last line of standard output is one JSON object: `correct`,
`attempted`, `failed`, `metrics` (the cell's end-to-end metrics, or with
`--trace 1` its per-layer ones), `device`, with `--trace 1` `breakdown`,
and last `checks`, each compared number beside its limit; the same
numbers are the last lines of standard error. Exits 2 without a result
where JAX finds no TPU or fewer chips than the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def _finite(v):
    return v if isinstance(v, (int, float)) and math.isfinite(v) else None


def main(argv=None) -> int:
    args = parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax
    from bench import harness
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    # every program of the run in the cache, so only a checkout's first
    # run compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    try:
        out = harness.run(args.workload, args.seed, args.seconds,
                          bool(args.trace), T_START)
    except harness.NoChip as e:
        print(f"no result: {e}", file=sys.stderr)
        return 2
    print(f"correct: {out['correct']}", file=sys.stderr)
    for k, c in out["checks"].items():
        c["value"] = _finite(c["value"])
        print(f"check {k}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
