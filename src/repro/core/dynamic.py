"""Evolving-graph GAS: snapshot-sequence training with incremental
`advance` (the training-side twin of serving's incremental refresh).

Production graphs churn — edges appear/disappear, nodes join, features
drift — and rebuilding the whole GAS substrate (partition, padded
batches, BCSR blocks, history tables) per snapshot throws away almost
everything a small delta leaves intact. `advance(plan, state, delta)`
carries the `GASPlan` + `GASState` across a `core.delta.GraphDelta` by
doing three incremental repairs instead:

  1. **Partition repair** (`core.partition.incremental_repair`): new
     nodes join by majority-neighbor vote, then the FM refinement passes
     re-run seeded from the OLD assignment over only the delta's 1-hop
     boundary region — O(region), not O(N) re-partitioning.
  2. **Batch patching** (`core.gas.patch_batches`): only the parts
     containing delta-touched nodes, their degree-coupled neighbors, or
     reassigned nodes get their padded rows AND BCSR block rows
     re-emitted; every other batch's arrays are copied verbatim, bitwise
     what a from-scratch `build_batches` on the new graph would produce
     (pads are sized with `pad_slack` up front so churn rarely overflows
     them).
  3. **Selective history invalidation**: only the rows inside the
     delta's L-1-hop out-closure (`core.delta.out_closure` of the
     structural + feature-updated seeds) are re-pushed — ONE
     layer-synchronous `subgraph_batch` through the standard
     `gas_batch_forward` push path, exactly serving's refresh machinery
     in the push direction. Every row outside the closure keeps its
     bits; repushed rows reset their staleness clock.

When the closure covers more than `cold_rebuild_frac` of the graph (or
a rebuilt part overflows its pads), `advance` falls back to a cold
rebuild — fresh METIS partition, fresh batches, full re-push — which is
always contract-correct, just slower. `BENCH_dynamic.json`
(benchmarks/dyn_bench.py) tracks the incremental/cold wall-clock ratio
per churn rate; tests/test_dynamic.py pins the bitwise contracts.

Optimizer state and parameters ride through `advance` untouched —
training resumes on the new snapshot exactly where it left off.
"""
from __future__ import annotations

import dataclasses
import functools
from dataclasses import dataclass
from typing import Any, Callable, Dict, Iterable, List, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.data.graphs import Graph
from repro.utils import spans
from . import delta as D
from . import gas as G
from .batch import BlockStructure, GASBatch
from .partition import (assign_new_nodes, incremental_repair,
                        metis_like_partition, random_partition)
from .runtime import (GASConfig, GASPlan, GASState, build_plan,
                      evaluate_exact, fit, init_state)


@dataclass(frozen=True)
class DynamicGASConfig:
    """Evolving-graph knobs on top of a base `GASConfig`.

    `cold_rebuild_frac`: closure fraction above which `advance` stops
    patching and cold-rebuilds (the incremental machinery only wins
    while the delta is local). `repair_passes`: FM passes of the
    partition repair. `pad_slack`: fractional headroom added to every
    padded dimension (max_b/max_h/max_e and block K) at build time, so
    moderate churn patches in place instead of overflowing pads.
    `closure_hops`: history-invalidation depth, default L-1 (the exact
    reach of a delta through L layers)."""
    base: GASConfig
    cold_rebuild_frac: float = 0.25
    repair_passes: int = 4
    pad_slack: float = 0.25
    closure_hops: Optional[int] = None


@dataclass
class AdvanceInfo:
    """What one `advance` did, and where its time went: the seconds of
    its spans `gas/advance/partition`, `/batches` and `/repush` (summed
    where a phase ran twice)."""
    cold: bool
    reason: str
    num_new_nodes: int
    closure_size: int
    closure_frac: float
    rebuilt_parts: int
    reassigned: int
    partition_s: float
    batches_s: float
    repush_s: float

    @property
    def total_s(self) -> float:
        return self.partition_s + self.batches_s + self.repush_s


def _slacked(n: int, frac: float) -> int:
    return int(np.ceil(max(int(n), 1) * (1.0 + frac)))


def _grow_block_k(batches: GASBatch, pad_k: int, pad_k_t: int) -> GASBatch:
    """Zero-extend the block K axes to (pad_k, pad_k_t) — identical to
    `build_batches(pad_k=...)` padding (padding slots are all-zero
    blocks at column 0), applied post hoc so the slack can be derived
    from the actual K."""
    bs = batches.unit or batches.forward
    if bs is None:
        return batches
    unit = batches.unit is not None
    bs_t = batches.unit_transposed if unit else batches.transposed

    def _grow(s: BlockStructure, k: int) -> BlockStructure:
        k0 = s.cols.shape[2]
        if k <= k0:
            return s
        bn = s.vals.shape[-1]
        vals = np.concatenate(
            [s.vals, np.zeros(s.vals.shape[:2] + (k - k0, bn, bn),
                              s.vals.dtype)], axis=2)
        cols = np.concatenate(
            [s.cols, np.zeros(s.cols.shape[:2] + (k - k0,),
                              s.cols.dtype)], axis=2)
        return BlockStructure(vals, cols)

    g, g_t = _grow(bs, pad_k), _grow(bs_t, pad_k_t)
    kw = ({"unit": g, "unit_transposed": g_t} if unit
          else {"forward": g, "transposed": g_t})
    return batches.replace(**kw)


def _build_slacked(graph: Graph, part: np.ndarray, build_blocks: bool,
                   unit_blocks: bool, pad_slack: float
                   ) -> Tuple[GASBatch, Tuple[int, int, int], int, int]:
    """Build stacked batches with `pad_slack` headroom on every padded
    dimension. The cheap block-less probe sizes the pads; K slack is
    grafted onto the real build. Returns (batches, pad_to, K, K_t)."""
    probe = G.build_batches(graph, part, build_blocks=False)
    pad_to = (_slacked(probe.max_b, pad_slack),
              _slacked(probe.max_h, pad_slack),
              _slacked(probe.max_e, pad_slack))
    batches = G.build_batches(graph, part, pad_to=pad_to,
                              build_blocks=build_blocks,
                              unit_weights=unit_blocks)
    pk = pk_t = 1
    bs = batches.unit or batches.forward
    if bs is not None:
        bs_t = (batches.unit_transposed if batches.unit is not None
                else batches.transposed)
        pk = _slacked(bs.cols.shape[2], pad_slack)
        pk_t = _slacked(bs_t.cols.shape[2], pad_slack)
        batches = _grow_block_k(batches, pk, pk_t)
    return batches, pad_to, pk, pk_t


def build_dynamic_plan(graph: Graph, spec,
                       dcfg: DynamicGASConfig) -> GASPlan:
    """`build_plan` for a graph that is going to evolve: identical plan
    surface, but every padded dimension carries `pad_slack` headroom so
    later `advance` calls can patch batches in place (and keep one jit
    trace) under moderate churn."""
    cfg = dcfg.base
    if cfg.clusters_per_batch != 1:
        raise ValueError(
            "dynamic plans require clusters_per_batch == 1 (regrouped "
            "epochs re-emit all batches every epoch — there is nothing "
            "incremental to preserve)")
    plan = build_plan(graph, spec, cfg)
    plan.batches, plan._pad_to, plan._pad_k, plan._pad_k_t = \
        _build_slacked(graph, plan.part, plan.build_blocks,
                       plan.unit_blocks, dcfg.pad_slack)
    plan.batch_stack = plan.batches.device()
    return plan


# ---------------------------------------------------------------------------
# Selective history re-push
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnums=(0, 1))
def _repush_step(spec, backend, params, store, batch, x):
    """Re-push the batch's rows through the standard Algorithm-1 forward
    (layer-synchronous: layer ℓ pulls layer ℓ-1 halo rows from the
    existing tables — outside-closure rows are valid by definition of
    the out-closure). Unfused so every store dtype takes the same
    materialized path; no decay — this is a recompute, not training."""
    from repro.gnn.model import gas_batch_forward
    _logits, store2, _reg, _diags = gas_batch_forward(
        params, spec, x, batch, store, use_history=True,
        backend=backend, fuse_halo=False)
    return store2


def _repush_closure(plan: GASPlan, state: GASState, store,
                    repush: np.ndarray) -> Any:
    """Re-push `repush` rows as ONE subgraph batch; every other row —
    and the whole staleness clock outside `repush` — keeps its bits."""
    if plan.spec.num_layers <= 1 or len(repush) == 0:
        return store
    old_age = store.age
    indptr, src, w = G.weighted_in_csr(plan.graph)
    batch = G.subgraph_batch(indptr, src, w, plan.graph.num_nodes,
                             repush).device()
    store = _repush_step(plan.spec, plan.backend, state.params, store,
                         batch, plan.x)
    # gas_batch_forward ticked the global clock; the dynamic contract is
    # narrower: only the re-pushed rows are fresh, everything else keeps
    # its exact pre-advance age (and bits)
    age = old_age.at[jnp.asarray(repush)].set(0)
    return dataclasses.replace(store, age=age)


# ---------------------------------------------------------------------------
# advance
# ---------------------------------------------------------------------------

def advance(plan: GASPlan, state: GASState, delta: D.GraphDelta,
            dcfg: DynamicGASConfig
            ) -> Tuple[GASPlan, GASState, AdvanceInfo]:
    """Carry (plan, state) across one `GraphDelta` — see the module
    docstring for the three incremental repairs and the cold fallback.
    Returns (new plan, new state, AdvanceInfo). The old plan/state are
    not mutated (the plan's cached jit closures are shared)."""
    cfg = dcfg.base
    partition_s = batches_s = 0.0
    with spans.span("gas/advance/partition") as sp:
        g_old = plan.graph
        n_old = g_old.num_nodes
        g_new = D.apply_delta(g_old, delta)
        N = g_new.num_nodes
        n_new_nodes = delta.num_new_nodes
        hops = (dcfg.closure_hops if dcfg.closure_hops is not None
                else plan.spec.num_layers - 1)
        seeds = delta.invalidation_seeds(n_old)
        closure = D.hop_closure(g_new.indptr, g_new.indices, seeds, hops)
        closure_frac = len(closure) / max(N, 1)

        cold = closure_frac > dcfg.cold_rebuild_frac
        reason = (f"closure {closure_frac:.3f} > cold_rebuild_frac "
                  f"{dcfg.cold_rebuild_frac}" if cold else "incremental")
        part_new = None
        patched = None
        rebuilt: np.ndarray = np.zeros(0, np.int64)
        reassigned = 0
        if not cold:
            part_ext = assign_new_nodes(g_new.indptr, g_new.indices,
                                        plan.part, cfg.num_parts)
            region = D.hop_closure(g_new.indptr, g_new.indices, seeds, 1)
            part_new = incremental_repair(
                g_new.indptr, g_new.indices, part_ext, cfg.num_parts,
                region, passes=dcfg.repair_passes, seed=cfg.seed)
            moved = np.flatnonzero(part_new[:n_old]
                                   != np.asarray(plan.part)[:n_old])
            reassigned = int(len(moved))
    partition_s += sp.seconds

    if not cold:
        with spans.span("gas/advance/batches") as sp:
            # a batch needs re-emission iff its membership or any of its
            # edge weights changed: parts holding a structural endpoint
            # or a new node (adjacency changed), a neighbor of one (its
            # incident GCN weights renormalize with the endpoint's
            # degree), or a repartitioned node (membership/halo changed
            # — old AND new part). Feature-only updates touch no batch
            # structure.
            touched = delta.touched_nodes(n_old)
            nbrs = D.csr_neighbors(g_new.indptr, g_new.indices, touched)
            aff = np.unique(np.concatenate(
                [touched, nbrs, moved,
                 np.arange(n_old, N, dtype=np.int64)]))
            rebuilt = np.unique(np.concatenate(
                [part_new[aff],
                 np.asarray(plan.part)[moved]])).astype(np.int64)
            patched = G.patch_batches(g_new, part_new, plan.batches,
                                      rebuilt, num_nodes_old=n_old)
        batches_s += sp.seconds
        if patched is None:
            cold = True
            reason = "pad overflow (or changed part count)"

    new_plan = dataclasses.replace(plan)   # shallow copy, caches shared
    if cold:
        with spans.span("gas/advance/partition") as sp:
            if cfg.partitioner == "metis":
                part_new = metis_like_partition(
                    g_new.indptr, g_new.indices, cfg.num_parts,
                    seed=cfg.seed)
            else:
                part_new = random_partition(N, cfg.num_parts,
                                            seed=cfg.seed)
        partition_s += sp.seconds
        with spans.span("gas/advance/batches") as sp:
            (patched, new_plan._pad_to, new_plan._pad_k,
             new_plan._pad_k_t) = _build_slacked(
                g_new, part_new, plan.build_blocks, plan.unit_blocks,
                dcfg.pad_slack)
        batches_s += sp.seconds
        rebuilt = np.arange(patched.num_batches, dtype=np.int64)

    with spans.span("gas/advance/repush") as sp:
        new_plan.graph = g_new
        new_plan.part = part_new
        new_plan.batches = patched
        new_plan.batch_stack = patched.device()
        new_plan.x = jnp.asarray(g_new.x)
        new_plan.y = jnp.concatenate([jnp.asarray(g_new.y),
                                      jnp.zeros((1,), jnp.int32)])
        new_plan.train_mask = jnp.asarray(
            np.concatenate([g_new.train_mask, [False]]))
        dst, src, w = G.gcn_edge_weights(g_new)
        new_plan.eval_edges = (jnp.asarray(dst), jnp.asarray(src))
        new_plan.eval_w = jnp.asarray(w)
        # predict() bakes N/num_classes into its trace as constants —
        # always drop it; the step/epoch closures only capture
        # spec/config/backend and re-trace themselves on any shape change
        new_plan._predict = None

        store = state.histories
        if n_new_nodes:
            store = store.grow(n_new_nodes)
        repush = np.arange(N, dtype=np.int64) if cold else closure
        new_state = state.replace(
            histories=_repush_closure(new_plan, state, store, repush))

    return new_plan, new_state, AdvanceInfo(
        cold=cold, reason=reason, num_new_nodes=n_new_nodes,
        closure_size=int(len(closure)), closure_frac=float(closure_frac),
        rebuilt_parts=int(len(rebuilt)), reassigned=reassigned,
        partition_s=partition_s, batches_s=batches_s,
        repush_s=sp.seconds)


# ---------------------------------------------------------------------------
# Snapshot-sequence trainer
# ---------------------------------------------------------------------------

DeltaLike = Union[D.GraphDelta, Callable[[Graph], D.GraphDelta]]


def fit_dynamic(graph: Graph, spec, dcfg: DynamicGASConfig,
                deltas: Iterable[DeltaLike],
                epochs_per_snapshot: Optional[int] = None,
                log: bool = False
                ) -> Tuple[GASPlan, GASState, List[Dict[str, float]]]:
    """Train across a snapshot sequence: fit on the initial graph, then
    per delta `advance` (carrying histories, partition, optimizer state
    and parameters) and keep fitting. A delta may be a `GraphDelta` or a
    callable `graph -> GraphDelta` (generators like
    `core.delta.random_delta` must see the CURRENT graph to reference
    valid edges). Returns (final plan, final state, one record per
    snapshot: exact-eval accuracies + advance diagnostics)."""
    plan = build_dynamic_plan(graph, spec, dcfg)
    state = init_state(plan)
    epochs = (dcfg.base.epochs if epochs_per_snapshot is None
              else epochs_per_snapshot)
    history: List[Dict[str, float]] = []

    def _record(snap: int, info: Optional[AdvanceInfo]) -> None:
        ev = evaluate_exact(plan, state)
        rec: Dict[str, float] = {"snapshot": float(snap), **ev,
                                 "num_nodes": float(plan.graph.num_nodes)}
        if info is not None:
            rec.update(cold=float(info.cold),
                       closure_frac=info.closure_frac,
                       rebuilt_parts=float(info.rebuilt_parts),
                       advance_s=info.total_s)
        history.append(rec)
        if log:
            extra = ("" if info is None else
                     f" advance={info.total_s * 1e3:.1f}ms "
                     f"({'cold' if info.cold else 'incremental'}, "
                     f"closure {info.closure_frac:.1%})")
            print(f"snapshot {snap}: val={ev['val_acc']:.4f} "
                  f"test={ev['test_acc']:.4f}{extra}")

    state, _ = fit(plan, state, epochs=epochs)
    _record(0, None)
    for i, d in enumerate(deltas):
        if callable(d):
            d = d(plan.graph)
        plan, state, info = advance(plan, state, d, dcfg)
        state, _ = fit(plan, state, epochs=epochs)
        _record(i + 1, info)
    return plan, state, history
