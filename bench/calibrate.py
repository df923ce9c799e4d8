#!/usr/bin/env python3
"""Readings that a train cell's check limits are set from, in one process.

    python3 bench/calibrate.py --workload <name> --seeds 1 2 ... \
        --control-seeds 101 102 103 [--out readings.json]

For each `--seeds` seed, the program (through the same `check_epochs` as a
run, on one plan built once: a seed changes the node data, the weights
and the batch order, never the graph) against the reference: the lower
readings. For each `--control-seeds` seed, the control (the reference at
the configuration's `control` precision, put in the program's place) and
a planted fault (the reference with half of each batch's training nodes
left out, the mean taken over the rest) against the reference: what
the upper readings come from, and a witness: the reference from weights
one ulp apart (every first-layer weight moved to its next float) against
the reference, which shows how far rounding alone carries over the check
epochs. Each reading also gives the per-batch loss gaps of the first
epoch (`look`), which show where in the epoch two runs part, the median
leaf's gaps of `grad` and `change` (`grad_med`, `change_med`: steadier
candidates, not compared by a run), and `correct`: the harness's own
verdict on the reading against the configuration's limits, which has to
read false for every control and fault seed. Prints one JSON line per
reading; not part of a benchmark run.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax
    import jax.numpy as jnp
    import numpy as np
    from bench import harness

    found = harness.load_cell(args.workload)
    driver = harness.load_module("drivers", found["traffic"]["kind"])
    config, n_check = found["config"], found["traffic"]["check_epochs"]
    op = harness.load_op(config)
    harness.require_chips(found["cell"]["chips"])
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_default_matmul_precision",
                      config["matmul_precision"])
    rows = []

    def look(got_b, want_b):
        """Relative loss gaps of batches 0, 1, 2, 5, 11, 23, 47 of the
        first epoch."""
        g, w = np.asarray(got_b, np.float64), np.asarray(want_b, np.float64)
        gap = np.abs(g - w) / np.abs(w)
        return [float(gap[i]) for i in (0, 1, 2, 5, 11, 23, len(gap) - 1)
                if i < len(gap)]

    def median_gap(got, want) -> float:
        """The median leaf's |‖got‖ - ‖want‖| / ‖want‖."""
        return float(np.median([
            abs(np.linalg.norm(g) - np.linalg.norm(w))
            / max(np.linalg.norm(w), 1e-30) for g, w in zip(got, want)]))

    def readings(got, want, params0):
        vals = harness.compare(got, want, params0)
        p0 = harness.leaves(params0)
        vals["grad_med"] = median_gap(harness.leaves(got["m"]),
                                      harness.leaves(want["m"]))
        vals["change_med"] = median_gap(
            [a - b for a, b in zip(harness.leaves(got["params"]), p0)],
            [a - b for a, b in zip(harness.leaves(want["params"]), p0)])
        vals["correct"] = harness.is_correct(
            harness.checks(vals, config["limits"]), 0)
        return vals

    def emit(kind, seed, values, t, lk=None):
        row = {"kind": kind, "seed": seed, **values, "seconds": t,
               "look": lk}
        rows.append(row)
        print(json.dumps(row), flush=True)

    plan = None
    for seed in args.seeds:
        t = time.perf_counter()
        inp = harness.make_inputs(config, seed)
        if plan is None:
            plan, state = driver.build(config, inp)
        else:
            plan.x = jnp.asarray(inp.x)
            plan.y = jnp.concatenate([jnp.asarray(inp.y),
                                      jnp.zeros((1,), jnp.int32)])
            plan.train_mask = jnp.asarray(np.concatenate([inp.train,
                                                          [False]]))
            plan.config = dataclasses.replace(plan.config,
                                              seed=inp.order_seed)
            state = driver.runtime_state(plan, inp)
        state, got = driver.check_epochs(plan, state, n_check)
        # the first epoch once more through the compiled epoch itself,
        # for its per-batch losses (the look)
        state = driver.runtime_state(plan, inp)
        state, m = plan._epoch(state, plan.batch_stack,
                               jnp.asarray(inp.orders(1)[0]), plan.x,
                               plan.y, plan.train_mask)
        prog_b = np.asarray(m["loss"])
        del state
        want = op.outputs(config, inp, n_check)
        lk = look(prog_b, want["batch_loss"][0])
        emit("program", seed, readings(got, want, inp.params),
             time.perf_counter() - t, lk)
    del plan

    for seed in args.control_seeds:
        t = time.perf_counter()
        inp = harness.make_inputs(config, seed)
        want = op.outputs(config, inp, n_check)
        wb = want["batch_loss"][0]
        ctl = op.outputs(config, inp, n_check, control=True)
        emit("control", seed, readings(ctl, want, inp.params),
             time.perf_counter() - t, look(ctl["batch_loss"][0], wb))
        half = dataclasses.replace(inp, train=inp.train & (
            np.arange(len(inp.train)) % 2 == 0))
        fault = op.outputs(config, half, n_check)
        emit("half_batch", seed,
             readings(fault, want, inp.params),
             time.perf_counter() - t, look(fault["batch_loss"][0], wb))
        nudged = jax.tree_util.tree_map(lambda a: a, inp.params)
        w0 = nudged["layers"][0]["w"]
        nudged["layers"][0]["w"] = np.nextafter(w0, np.inf).astype(w0.dtype)
        ulp = op.outputs(config, dataclasses.replace(inp, params=nudged),
                         n_check)
        emit("one_ulp", seed, readings(ulp, want, inp.params),
             time.perf_counter() - t, look(ulp["batch_loss"][0], wb))
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(rows, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
