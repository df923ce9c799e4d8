"""Driver of the sharded train mixes (`"kind": "dist"`):
`runtime.train_epoch` with `GASConfig(ranks=R)`, the sharded epoch (one
`shard_map` of P/R supersteps over a mesh of the cell's chips), back to
back.

Set-up builds the plan on the cell's devices, the state with the
harness's weights (replicated) and runs the mix's `check_epochs` through
the same call; the window then runs further epochs back to back for
`--seconds`, and peak memory is read on the fullest chip. After the
window the program's state is freed and the configuration's op
(`bench/ops/<op>.py`) repeats the check epochs in its plain reference
on one device from the same weights; `harness.compare` holds the two to
the configuration's limits. With `--trace 1` the program's spans and
counters are recorded from before the plan is built until after the
window (`ctx["program"]`, a `repro.utils.spans.Recording`).

The configuration states `ranks` and `parts_per_step` (one part a chip,
so the two are equal), besides what a train configuration states.
"""
from __future__ import annotations

import gc
import math
import time
from pathlib import Path
from typing import List

import numpy as np

from bench import harness
from bench.drivers.train import window, work_of
from bench.harness import Inputs, log, span


def build(config: dict, inp: Inputs, devs=None):
    """(plan, state) of the program for these inputs on `devs` (default:
    the first `ranks` devices), with the harness's weights in the
    state."""
    from repro.core import runtime
    from repro.data.graphs import Graph
    from repro.gnn.model import GNNSpec

    m, o = config["model"], config["optimizer"]
    if config["batch_norm"]:
        raise ValueError("the program's GNN layers have no batch norm")
    if config["parts_per_step"] != config["ranks"]:
        raise ValueError("a superstep takes one part a chip: parts_per_step "
                         "must equal ranks")
    n = len(inp.part)
    graph = Graph(inp.indptr, inp.indices, inp.x, inp.y, inp.train,
                  ~inp.train, np.zeros(n, bool), m["classes"])
    spec = GNNSpec(harness.load_op(config).PROGRAM_OP, m["features"],
                   m["hidden"], m["classes"], m["layers"],
                   dropout=config["dropout"])
    clip = math.inf if o["grad_clip"] is None else o["grad_clip"]
    gcfg = runtime.GASConfig(
        num_parts=int(inp.part.max()) + 1, ranks=config["ranks"],
        backend=config["backend"], history_dtype=config["history_dtype"],
        fuse_halo=True, fused_epoch=True, lr=o["lr"],
        weight_decay=o["weight_decay"], grad_clip=clip,
        seed=inp.order_seed)
    with span(inp.spans, "plan_build"):
        plan = runtime.build_plan(graph, spec, gcfg, part=inp.part,
                                  devices=devs)
    return plan, runtime_state(plan, inp)


def runtime_state(plan, inp: Inputs):
    """A fresh program state with the harness's weights, replicated over
    the plan's mesh."""
    import jax
    from repro.core import dist_gas, runtime
    state = runtime.init_state(plan)
    params = jax.device_put(inp.params, dist_gas.replicated(plan.mesh))
    return state.replace(params=params)


def host_tables(plan, store) -> List[np.ndarray]:
    """The sharded store's history tables in node order, dequantized to
    float32."""
    out = []
    for i, t in enumerate(store.tables):
        t = np.asarray(t)[plan.node_rows].astype(np.float32)
        if store.scales is not None:
            t = t * np.asarray(store.scales[i])[plan.node_rows][:, None]
        out.append(t)
    return out


def check_epochs(plan, state, epochs: int):
    """Runs the check epochs through `runtime.train_epoch`; returns the
    state and what the comparison reads: per-epoch losses, AdamW's first
    moment after epoch 1, parameters and history tables after the last."""
    import jax
    from repro.core import runtime
    losses, first_m = [], None
    for e in range(epochs):
        t = time.perf_counter()
        state, metrics = runtime.train_epoch(plan, state, e)
        log(f"check epoch {e}: {time.perf_counter() - t:.3f} s")
        losses.append(float(metrics["loss"]))
        if first_m is None:
            first_m = jax.tree_util.tree_map(np.asarray, state.opt_state.m)
    got = {"loss": losses, "m": first_m,
           "params": jax.tree_util.tree_map(np.asarray, state.params),
           "tables": host_tables(plan, state.histories)}
    return state, got


def run(name: str, found: dict, devs, seed: int, seconds: float,
        trace: bool, t_start: float, *, chips_required: bool,
        trace_dir: Path) -> dict:
    import jax
    from repro.core.partition import inter_intra_ratio
    from repro.utils import spans

    config, traffic = found["config"], found["traffic"]
    log(f"{name}: {time.perf_counter() - t_start:.3f} s to start")
    inp = harness.make_inputs(config, seed)
    log(f"{name}: graph {inp.spans['graph']:.3f} s, weights "
        f"{inp.spans['weights']:.3f} s")
    log(f"{name}: graph {len(inp.part)} nodes, {len(inp.indices)} directed "
        f"edges, {int(inp.part.max()) + 1} parts on {len(devs)} chips, "
        f"inter/intra edge ratio "
        f"{inter_intra_ratio(inp.indptr, inp.indices, inp.part):.4f}")
    if trace:
        spans.start()
    plan, state = build(config, inp, devs)
    with span(inp.spans, "upload"):
        jax.block_until_ready(plan.batch_stack)
    dist = plan.batch_stack
    log(f"{name}: plan {inp.spans['plan_build']:.3f} s, upload "
        f"{inp.spans['upload']:.3f} s, blocks "
        f"{tuple(plan.batches.forward.vals.shape)} + transposed "
        f"{tuple(plan.batches.transposed.vals.shape)}, {dist.rows} rows a "
        f"shard, {dist.send_idx.shape[-1]} rows a peer, backend "
        f"{plan.backend}, histories {plan.history_dtype}")
    if chips_required and plan.backend != config["backend"]:
        raise RuntimeError(f"backend resolved to {plan.backend}")
    n_check = traffic["check_epochs"]
    state, got = check_epochs(plan, state, n_check)
    setup_s = time.perf_counter() - t_start
    log(f"{name}: set-up {setup_s:.3f} s, check losses {got['loss']}")

    if trace:
        harness.rmtree(trace_dir)
        trace_dir.mkdir(parents=True)
        jax.profiler.start_trace(str(trace_dir))
    try:
        state, epochs, failed, window_s, compiles = window(
            plan, state, n_check, seconds, trace)
    finally:
        if trace:
            jax.profiler.stop_trace()
    program = spans.stop() if trace else None
    peak = harness.peak_bytes(devs)
    log(f"{name}: {epochs} epochs in {window_s:.3f} s, {failed} failed, "
        f"{compiles} compiles in the window, peak {peak} bytes")
    del state, plan, dist
    gc.collect()

    want = harness.load_op(config).outputs(config, inp, n_check)
    chk = harness.checks(harness.compare(got, want, inp.params),
                         config["limits"])
    summary = None
    if trace:
        from bench import trace as tr
        summary = tr.load(trace_dir)
        harness.rmtree(trace_dir)
    ctx = {"setup_s": setup_s, "window_s": window_s, "epochs": epochs,
           "peak_bytes": peak, "spans": inp.spans, "program": program,
           "work": work_of(config, inp),
           "peaks": (harness.device_peaks(devs[0].device_kind)
                     if chips_required else None)}
    return harness.result(found, ctx, chk, epochs, failed, devs, summary)
