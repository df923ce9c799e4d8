"""Driver of the train mixes (`"kind": "train"`): `runtime.train_epoch`,
the fused `lax.scan` epoch, back to back.

Set-up builds the plan and the state and runs the mix's `check_epochs`
through that same call; the window then runs further epochs back to back
for `--seconds`. After the window the program's state is freed and the
configuration's op (`bench/ops/<op>.py`) repeats the check epochs in its
plain reference from the same weights; `harness.compare` holds the two
to the configuration's limits.

The configuration states the model, the optimizer, `parts_per_step`
(parts per batch), `dropout` and `batch_norm`; the program runs them as
stated and refuses what it has no option for.
"""
from __future__ import annotations

import contextlib
import gc
import math
import time
from pathlib import Path
from typing import List

import numpy as np

from bench import harness, work
from bench.harness import Inputs, log, span


def build(config: dict, inp: Inputs):
    """(plan, state) of the program for these inputs, with the harness's
    weights in the state."""
    from repro.core import runtime
    from repro.data.graphs import Graph
    from repro.gnn.model import GNNSpec

    m, o = config["model"], config["optimizer"]
    if config["batch_norm"]:
        raise ValueError("the program's GNN layers have no batch norm")
    n = len(inp.part)
    graph = Graph(inp.indptr, inp.indices, inp.x, inp.y, inp.train,
                  ~inp.train, np.zeros(n, bool), m["classes"])
    spec = GNNSpec(m["op"], m["features"], m["hidden"], m["classes"],
                   m["layers"], dropout=config["dropout"])
    clip = math.inf if o["grad_clip"] is None else o["grad_clip"]
    gcfg = runtime.GASConfig(
        num_parts=int(inp.part.max()) + 1,
        clusters_per_batch=config["parts_per_step"],
        backend=config["backend"], history_dtype=config["history_dtype"],
        fuse_halo=True, fused_epoch=True, lr=o["lr"],
        weight_decay=o["weight_decay"], grad_clip=clip,
        seed=inp.order_seed)
    with span(inp.spans, "plan_build"):
        plan = runtime.build_plan(graph, spec, gcfg, part=inp.part)
    return plan, runtime_state(plan, inp)


def runtime_state(plan, inp: Inputs):
    """A fresh program state with the harness's weights."""
    import jax
    import jax.numpy as jnp
    from repro.core import runtime
    state = runtime.init_state(plan)
    # copies: the state is donated to every epoch
    params = jax.tree_util.tree_map(lambda a: jnp.array(a, copy=True),
                                    inp.params)
    return state.replace(params=params)


def host_tables(store) -> List[np.ndarray]:
    """The store's history tables dequantized to float32, sentinel row
    dropped."""
    out = []
    for i, t in enumerate(store.tables):
        t = np.asarray(t)[:-1].astype(np.float32)
        if store.scales is not None:
            t = t * np.asarray(store.scales[i])[:-1, None]
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
           "tables": host_tables(state.histories)}
    return state, got


def window(plan, state, first_epoch: int, seconds: float, spans_on: bool):
    """Epochs back to back until `seconds` have passed. Returns (state,
    epochs, failed, window seconds, compiles seen in the window)."""
    import jax
    from repro.core import runtime
    compiles = []
    listener = (lambda name, secs, **kw: compiles.append(name)
                if "backend_compile" in name else None)
    jax.monitoring.register_event_duration_secs_listener(listener)
    n = failed = 0
    t0 = time.perf_counter()
    try:
        while True:
            with (jax.profiler.TraceAnnotation("bench/epoch") if spans_on
                  else contextlib.nullcontext()):
                state, metrics = runtime.train_epoch(plan, state,
                                                     first_epoch + n)
            n += 1
            failed += not math.isfinite(float(metrics["loss"]))
            elapsed = time.perf_counter() - t0
            if elapsed >= seconds:
                break
    finally:
        jax.monitoring.unregister_event_duration_listener(listener)
    return state, n, failed, elapsed, len(compiles)


def work_of(config: dict, inp: Inputs) -> dict:
    """Needed operations and bytes per epoch (bench/work.py)."""
    n = len(inp.part)
    row = work.history_row_bytes(config["history_dtype"])
    op = harness.load_op(config)
    return {"model_flops": op.model_flops(config, n, len(inp.indices)),
            "aggregation": work.aggregation_work(
                inp.indptr, inp.indices, inp.part, op.dims(config), row)}


def run(name: str, found: dict, devs, seed: int, seconds: float,
        trace: bool, t_start: float, *, chips_required: bool,
        trace_dir: Path) -> dict:
    import jax
    from repro.core.partition import inter_intra_ratio

    config, traffic = found["config"], found["traffic"]
    log(f"{name}: {time.perf_counter() - t_start:.3f} s to start")
    inp = harness.make_inputs(config, seed)
    log(f"{name}: graph {inp.spans['graph']:.3f} s, weights "
        f"{inp.spans['weights']:.3f} s")
    log(f"{name}: graph {len(inp.part)} nodes, {len(inp.indices)} directed "
        f"edges, {int(inp.part.max()) + 1} parts, inter/intra edge ratio "
        f"{inter_intra_ratio(inp.indptr, inp.indices, inp.part):.4f}")
    plan, state = build(config, inp)
    with span(inp.spans, "upload"):
        jax.block_until_ready(plan.batch_stack)
    log(f"{name}: plan {inp.spans['plan_build']:.3f} s, upload "
        f"{inp.spans['upload']:.3f} s, blocks "
        f"{tuple(plan.batches.forward.vals.shape)} + "
        f"transposed {tuple(plan.batches.transposed.vals.shape)}, backend "
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
    peak = harness.peak_bytes(devs)
    log(f"{name}: {epochs} epochs in {window_s:.3f} s, {failed} failed, "
        f"{compiles} compiles in the window, peak {peak} bytes")
    del state, plan
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
           "peak_bytes": peak, "spans": inp.spans,
           "work": work_of(config, inp),
           "peaks": (harness.device_peaks(devs[0].device_kind)
                     if chips_required else None)}
    return harness.result(found, ctx, chk, epochs, failed, devs, summary)
