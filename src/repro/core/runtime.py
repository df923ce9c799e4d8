"""Pure-functional GAS runtime: `GASConfig` -> `GASPlan` -> `GASState`.

The runtime splits GAS training into three typed layers:

  * `GASConfig` — every knob in one frozen record: partitioning
    (`num_parts`/`partitioner`/`clusters_per_batch`), execution
    (`backend`/`fuse_halo`/`use_history`/`prefetch_depth`/`ranks`) and
    optimization (`lr`/`weight_decay`/`grad_clip`/`epochs`/`seed`).
  * `GASPlan` — everything *built once* from (graph, spec, config): the
    partition, the stacked `GASBatch` structures (host + device), the
    resolved kernel backend, padding bounds for regrouped epochs, the
    device-side label/feature/mask arrays and the exact-eval COO. A plan
    holds no trainable state and its jitted step/predict/epoch closures
    are cached on it.
  * `GASState` — everything that *changes* during training, as one
    pytree: params, optimizer state, the `HistoryStore` (tables + age,
    backend bound as aux data) and the RNG key. It serializes natively
    (`train.checkpoint.save_gas_state`) and restores bit-identically.

The step surface is pure and jit-donatable:

    state, metrics = train_step(plan, state, batch)    # one cluster batch
    state, metrics = train_epoch(plan, state, epoch)   # shuffled epoch
    state, history = fit(plan, state)                  # config.epochs
    logits         = predict(plan, state)              # constant-memory
    accs           = evaluate_exact(plan, state)       # full propagation

An epoch is one jitted program: a `lax.scan` over the batches on one
chip, a `shard_map` of supersteps on `ranks > 1` chips.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, replace
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.data.graphs import Graph
from repro.kernels import ops
from repro.utils import spans
from . import dist_gas
from . import gas as G
from . import history as H
from .batch import GASBatch
from .config import HistoryExecConfig
from .partition import metis_like_partition, random_partition


@dataclass(frozen=True)
class GASConfig(HistoryExecConfig):
    """One consolidated knob record. The shared execution knobs —
    `backend`, `history_dtype`, `staleness_slo` — are inherited from
    `core.config.HistoryExecConfig` (one declaration for training AND
    serving): `backend=None` auto-selects (see
    `kernels.ops.resolve_backend`) and `history_dtype=None` resolves via
    $REPRO_HISTORY_DTYPE -> "f32" (see `history.resolve_history_dtype`;
    "bf16"/"int8"/"vq" store the history tables compressed — the
    dominant memory term — with in-kernel dequant/decode on the pull
    side); training keeps the inherited `staleness_slo=None` (unbounded
    — Theorem 2 bounds the error, serving configs override). For "vq",
    `vq_refit_every=k > 0` refits the per-layer codebooks from this
    epoch's pushed-row statistics every k epochs
    (`HistoryStore.refit_codebooks`; 0 keeps the deterministic initial
    codebook). Hyperparameters mirror the paper's citation-graph
    defaults.

    `prefetch_depth > 0` software-pipelines the epoch (the paper's §5
    concurrent mini-batch execution): batch i+depth's halo pull is
    dispatched BEFORE batch i's forward/backward/push, so the history
    gather — and, with `history_storage="host"`, the host->device row
    transfer — overlaps compute instead of serializing with it. The
    pipelined schedule is bit-identical to the synchronous one (a
    write-after-read patch replays any pushes that land between a pull's
    dispatch and its use — see `history.HistoryStore.patch_pulled`).
    `history_storage="host"` pins the history tables in host RAM
    (`history.resolve_history_storage`), scaling table capacity with CPU
    RAM instead of HBM.

    `ranks=R > 1` shards training over R devices (`core.dist_gas`):
    the parts go to ranks P/R each, contiguous by part id, the history
    tables are row-sharded over a mesh of the plan's devices, and an
    epoch is one jitted `shard_map` of P/R supersteps in which every rank
    trains one of its parts on the same kernels as one chip. `ranks=1`
    is the one-chip path.

    `fused_epoch` selects nothing: every epoch is one jitted program (see
    `train_epoch`), and False is refused; `train_step` runs one step at
    a time."""
    num_parts: int
    partitioner: str = "metis"          # "metis" | "random"
    clusters_per_batch: int = 1
    use_history: bool = True
    fused_epoch: bool = True
    fuse_halo: bool = True
    vq_refit_every: int = 0              # epochs between vq codebook refits
    # drift-triggered vq refit: also refit whenever the previous epoch's
    # mean `hist_quant_err` exceeded this threshold (0 disables), so
    # k-means cost is spent only when the embedding distribution actually
    # moves (e.g. under graph churn). Complements the fixed cadence.
    vq_refit_drift: float = 0.0
    # haste-makes-waste staleness compensation: damp pulled halo rows by
    # 1 / (1 + decay * age) instead of trusting them uniformly (Xue et
    # al., 2024). 0.0 (default) is bit-identical to no compensation.
    halo_age_decay: float = 0.0
    prefetch_depth: int = 0              # 0 = synchronous epochs
    history_storage: Optional[str] = None  # "device" | "host"
    ranks: int = 1                       # chips the epoch is sharded over
    lr: float = 0.01
    weight_decay: float = 5e-4
    grad_clip: float = 2.0
    epochs: int = 100
    seed: int = 0

    def __post_init__(self):
        super().__post_init__()
        if not self.fused_epoch:
            raise ValueError(
                "fused_epoch=False: the per-step epoch loop was removed; "
                "an epoch is one jitted scan (train_epoch), and one step "
                "at a time is train_step")


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=["params", "opt_state", "histories", "rng"], meta_fields=[])
@dataclass(frozen=True)
class GASState:
    """The complete mutable training state as one donatable pytree."""
    params: Any
    opt_state: Any
    histories: H.HistoryStore
    rng: jax.Array

    def replace(self, **kw) -> "GASState":
        return replace(self, **kw)


@dataclass
class GASPlan:
    """Static execution plan; built once by `build_plan`. Mutable only in
    the narrow sense that `clusters_per_batch > 1` epochs re-randomize
    the cluster grouping (`_regroup`), which swaps `batches`/`batch_stack`
    in place while keeping the padded shapes (and thus the jit traces,
    until a regroup grows the lazy K pad) stable."""
    graph: Graph
    spec: Any                            # gnn.model.GNNSpec
    config: GASConfig
    backend: str                         # resolved once
    history_dtype: str                   # resolved once
    history_storage: str                 # resolved once
    part: np.ndarray
    batches: GASBatch                    # host (numpy) stacked
    batch_stack: GASBatch                # device stacked
    x: jnp.ndarray
    y: jnp.ndarray                       # [N+1] padded labels
    train_mask: jnp.ndarray              # [N+1]
    eval_edges: Tuple[jnp.ndarray, jnp.ndarray]
    eval_w: jnp.ndarray
    build_blocks: bool
    unit_blocks: bool
    mesh: Any = None                     # ranks > 1: the plan's devices
    node_rows: Optional[np.ndarray] = None  # ranks > 1: row of each node
    #                                      in the sharded history tables
    _pad_to: Optional[Tuple[int, int, int]] = None
    _pad_k: int = 1
    _pad_k_t: int = 1
    _last_qerr: Optional[float] = None   # prev epoch's mean hist_quant_err
    _np_rng: Any = None
    _step: Optional[Callable] = None
    _predict: Optional[Callable] = None
    _epoch: Optional[Callable] = None

    def batch(self, b) -> GASBatch:
        """One device batch off the stack."""
        return self.batch_stack[b]


def _accuracy(logits, labels, mask):
    pred = jnp.argmax(logits, axis=-1)
    ok = (pred == labels) & mask
    return jnp.sum(ok) / jnp.maximum(jnp.sum(mask), 1)


# ---------------------------------------------------------------------------
# Plan / state construction
# ---------------------------------------------------------------------------

def build_plan(graph: Graph, spec, config: GASConfig,
               part: Optional[np.ndarray] = None,
               devices=None) -> GASPlan:
    """Partition the graph, build (stack, upload) the typed batch
    structures, resolve the kernel backend — everything static. `part`
    (e.g. another plan's `part` over the same graph) skips partitioning,
    the slow host-side step of plan construction. With `config.ranks >
    1` the plan's mesh is the first `ranks` of `devices` (default
    `jax.devices()`), and the routing of the halo exchange is built too
    (`dist_gas.build_dist`). Timed as span `gas/plan`, with
    `gas/plan/partition` and `gas/plan/device_put` (sharded plans:
    `gas/dist/plan` and `gas/dist/plan/device_put`) inside
    (`utils.spans`); the bytes handed to the device count into
    `gas/plan/upload_bytes`."""
    with spans.span("gas/plan"):
        return _build_plan(graph, spec, config, part, devices)


def _build_plan(graph: Graph, spec, config: GASConfig,
                part: Optional[np.ndarray], devices=None) -> GASPlan:
    from repro.gnn.model import BLOCK_OPS, UNIT_BLOCK_OPS

    backend = ops.resolve_backend(config.backend)
    history_dtype = H.resolve_history_dtype(config.history_dtype)
    history_storage = H.resolve_history_storage(config.history_storage)
    if config.ranks > 1:
        dist_gas.check_config(config, history_dtype, history_storage)
    build_blocks = spec.op in BLOCK_OPS and backend != "jnp"
    unit_blocks = build_blocks and spec.op in UNIT_BLOCK_OPS
    N = graph.num_nodes

    if part is not None:
        part = np.asarray(part)
        if part.shape != (N,):
            raise ValueError(f"part must have shape ({N},), got {part.shape}")
    else:
        with spans.span("gas/plan/partition"):
            if config.partitioner == "metis":
                part = metis_like_partition(graph.indptr, graph.indices,
                                            config.num_parts,
                                            seed=config.seed)
            else:
                part = random_partition(N, config.num_parts,
                                        seed=config.seed)

    plan = GASPlan(
        graph=graph, spec=spec, config=config, backend=backend,
        history_dtype=history_dtype, history_storage=history_storage,
        part=part,
        batches=None, batch_stack=None, x=None, y=None, train_mask=None,
        eval_edges=None, eval_w=None,
        build_blocks=build_blocks, unit_blocks=unit_blocks,
        _np_rng=np.random.default_rng(config.seed + 17))

    if config.clusters_per_batch > 1:
        # PyGAS batch_size > 1: k random clusters per batch, reshuffled
        # each epoch; pad to the worst case so one jit serves all epochs.
        # K (blocks per row block) varies with the regrouping; padding to
        # the worst case would store the dense adjacency, so the pad grows
        # lazily (one-off re-jit when a regroup exceeds the largest seen).
        plan._pad_to = G.padding_bounds(graph, part,
                                        config.clusters_per_batch)
        _regroup(plan)
    else:
        plan.batches = G.build_batches(graph, part,
                                       build_blocks=build_blocks,
                                       unit_weights=unit_blocks)
    dst, src, w = G.gcn_edge_weights(graph)   # exact full-propagation eval
    if config.ranks > 1:
        _place_sharded(plan, devices)
        plan.eval_edges = (jnp.asarray(dst), jnp.asarray(src))
        plan.eval_w = jnp.asarray(w)
        return plan

    with spans.span("gas/plan/device_put"):
        plan.batch_stack = plan.batches.device()
        plan.x = jnp.asarray(graph.x)
        plan.y = jnp.concatenate([jnp.asarray(graph.y),
                                  jnp.zeros((1,), jnp.int32)])   # pad row
        plan.train_mask = jnp.asarray(
            np.concatenate([graph.train_mask, [False]]))
        plan.eval_edges = (jnp.asarray(dst), jnp.asarray(src))
        plan.eval_w = jnp.asarray(w)
    spans.count("gas/plan/upload_bytes", sum(
        a.nbytes for a in jax.tree_util.tree_leaves(
            (plan.batch_stack, plan.x, plan.y, plan.train_mask,
             plan.eval_edges, plan.eval_w))))
    return plan


def _place_sharded(plan: GASPlan, devices) -> None:
    """A sharded plan's routing, mesh and upload: the batches and routing
    sharded by rank, features, labels and masks replicated."""
    R = plan.config.ranks
    graph = plan.graph
    host, plan.node_rows, counts = dist_gas.build_dist(plan.part,
                                                       plan.batches, R)
    dist_gas.count_exchange(counts, plan.spec.hist_dims(),
                            plan.history_dtype, host.slots.shape[1])
    plan.mesh = dist_gas.make_mesh(R, devices)
    rep = dist_gas.replicated(plan.mesh)
    with spans.span("gas/dist/plan/device_put"):
        plan.batch_stack = jax.device_put(host,
                                          dist_gas.sharded(plan.mesh))
        plan.x = jax.device_put(graph.x, rep)
        plan.y = jax.device_put(
            np.concatenate([graph.y, [0]]).astype(np.int32), rep)
        plan.train_mask = jax.device_put(
            np.concatenate([graph.train_mask, [False]]), rep)
    spans.count("gas/plan/upload_bytes", sum(
        a.nbytes for a in jax.tree_util.tree_leaves(
            (plan.batch_stack, plan.x, plan.y, plan.train_mask))))


def _regroup(plan: GASPlan) -> None:
    """Draws a new grouping of clusters into batches and builds its host
    batches (`plan.batches`), growing the lazy block pads; the caller
    uploads them."""
    cfg = plan.config
    grouped = G.group_partition(plan.part, cfg.clusters_per_batch,
                                plan._np_rng)
    plan.batches = G.build_batches(plan.graph, grouped, pad_to=plan._pad_to,
                                   build_blocks=plan.build_blocks,
                                   pad_k=plan._pad_k,
                                   pad_k_t=plan._pad_k_t,
                                   unit_weights=plan.unit_blocks)
    fwd = plan.batches.forward or plan.batches.unit
    if fwd is not None:
        tr = plan.batches.transposed or plan.batches.unit_transposed
        plan._pad_k = max(plan._pad_k, fwd.cols.shape[2])
        plan._pad_k_t = max(plan._pad_k_t, tr.cols.shape[2])


def init_state(plan: GASPlan) -> GASState:
    """Fresh params/optimizer/histories/rng for a plan."""
    from repro.gnn.model import init_gnn
    from repro.train.optimizer import adamw_init

    cfg = plan.config
    params = init_gnn(jax.random.key(cfg.seed), plan.spec)
    if plan.mesh is not None:
        dist = plan.batch_stack
        state = GASState(
            params=params, opt_state=adamw_init(params),
            histories=None, rng=jax.random.key(cfg.seed + 1))
        state = jax.device_put(state, dist_gas.replicated(plan.mesh))
        return state.replace(histories=dist_gas.init_store(
            plan.spec.hist_dims(), dist.ranks, dist.rows, plan.mesh,
            plan.backend, plan.history_dtype))
    return GASState(
        params=params,
        opt_state=adamw_init(params),
        histories=H.HistoryStore.create(plan.graph.num_nodes + 1,
                                        plan.spec.hist_dims(),
                                        backend=plan.backend,
                                        history_dtype=plan.history_dtype,
                                        storage=plan.history_storage),
        rng=jax.random.key(cfg.seed + 1))


# ---------------------------------------------------------------------------
# Pure step functions
# ---------------------------------------------------------------------------

def _make_step_fn_ex(plan: GASPlan) -> Callable:
    """The extended pure step `(state, batch, x, y, train_mask,
    pulled=None) -> (state, metrics, pushed)`: `pulled` feeds the
    forward's history reads from prefetched mini-tables
    (`HistoryStore.prefetch`) and `pushed` hands the per-layer push
    payloads to the epoch pipeline's write-after-read patching."""
    from repro.gnn.model import gas_batch_forward
    from repro.train.optimizer import adamw_update, clip_by_global_norm

    spec, cfg, backend = plan.spec, plan.config, plan.backend

    def step(state: GASState, batch: GASBatch, x, y, train_mask,
             pulled=None):
        rng, sub = jax.random.split(state.rng)

        def loss_fn(p):
            logits, store, reg, diags, pushed = gas_batch_forward(
                p, spec, x, batch, state.histories,
                use_history=cfg.use_history, rng=sub, backend=backend,
                fuse_halo=cfg.fuse_halo, pulled=pulled,
                halo_age_decay=cfg.halo_age_decay,
                return_pushed=True)
            labels = jnp.take(y, batch.batch_nodes, mode="clip")
            m = jnp.take(train_mask, batch.batch_nodes, mode="clip")
            m = m & batch.batch_mask
            logz = jax.scipy.special.logsumexp(logits, axis=-1)
            gold = jnp.take_along_axis(logits, labels[:, None],
                                       axis=-1)[:, 0]
            ce = jnp.sum((logz - gold) * m) / jnp.maximum(jnp.sum(m), 1)
            loss = ce + spec.reg_weight * reg
            acc = _accuracy(logits, labels, m)
            return loss, (store, pushed,
                          {"loss": loss, "ce": ce, "acc": acc,
                           "reg": reg, **diags})

        (loss, (store, pushed, metrics)), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(state.params)
        grads, _gn = clip_by_global_norm(grads, cfg.grad_clip)
        params, opt_state = adamw_update(
            grads, state.opt_state, state.params, lr=cfg.lr, b1=0.9,
            b2=0.999, weight_decay=cfg.weight_decay)
        return GASState(params=params, opt_state=opt_state,
                        histories=store, rng=rng), metrics, pushed

    return step


def make_step_fn(plan: GASPlan) -> Callable:
    """The un-jitted pure step `(state, batch, x, y, train_mask) ->
    (state, metrics)` — exposed for introspection (jaxpr assertions) and
    for embedding into larger jitted programs (`lax.scan` epochs)."""
    step_ex = _make_step_fn_ex(plan)

    def step(state: GASState, batch: GASBatch, x, y, train_mask):
        state, metrics, _pushed = step_ex(state, batch, x, y, train_mask)
        return state, metrics

    return step


def _prefetch_entry(store: H.HistoryStore, batch: GASBatch):
    """Queue entry for one in-flight halo prefetch: the pulled rows plus
    the target batch's halo indexing (needed to patch later pushes in)."""
    return (store.prefetch(batch.halo_nodes), batch.halo_nodes,
            batch.halo_mask)


def make_prefetch_step_fn(plan: GASPlan, depth: int) -> Callable:
    """The software-pipelined step `(state, batch, future_batch, queue,
    x, y, train_mask) -> (state, metrics, queue)`.

    `queue` holds `depth` in-flight prefetch entries, head = the pull for
    THIS batch (dispatched `depth` steps ago). The body:

      1. dispatches `future_batch`'s halo pull FIRST — traced before the
         current batch's forward/backward, so its table gathers (and host
         stores' host->device row streams) are scheduled while the MXU
         chews on this batch;
      2. runs the train step with the head entry's prefetched rows
         feeding every history read (bit-identical mini-table view);
      3. patches this step's pushes into every still-queued entry
         (write-after-read hazard: those pulls predate these pushes).

    Exposed un-jitted so tests can jaxpr-assert the dispatch order (the
    future batch's [N+1, d] table gather precedes the current batch's
    [N+1, d] push scatter)."""
    step_ex = _make_step_fn_ex(plan)

    def pf_step(state: GASState, batch: GASBatch, future_batch: GASBatch,
                queue, x, y, train_mask):
        new_entry = _prefetch_entry(state.histories, future_batch)
        state, metrics, pushed = step_ex(state, batch, x, y, train_mask,
                                         pulled=queue[0][0])
        queue = tuple(
            (state.histories.patch_pulled(p, hn, hm, batch.batch_nodes,
                                          batch.batch_mask, pushed),
             hn, hm)
            for (p, hn, hm) in queue[1:] + (new_entry,))
        return state, metrics, queue

    return pf_step


def _resolved_depth(plan: GASPlan) -> int:
    """prefetch_depth clamped to [0, num_batches): each queue slot holds
    a distinct future batch (deeper would re-prefetch a batch already in
    flight — pure waste, the patches already keep every slot fresh)."""
    nb = plan.batches.num_batches
    return max(0, min(plan.config.prefetch_depth, nb - 1))


def _one_chip(plan: GASPlan, what: str) -> None:
    if plan.mesh is not None:
        raise NotImplementedError(
            f"{what} runs on one chip; a plan with ranks > 1 trains by "
            "train_epoch and evaluates by evaluate_exact")


def _jitted_step(plan: GASPlan) -> Callable:
    if plan._step is None:
        # donate the whole state: history tables and optimizer moments are
        # the largest buffers and every field is returned fresh
        plan._step = jax.jit(make_step_fn(plan), donate_argnums=(0,))
    return plan._step


def train_step(plan: GASPlan, state: GASState,
               batch: GASBatch) -> Tuple[GASState, Dict[str, jnp.ndarray]]:
    """One jitted optimization step on one cluster batch. `state` is
    donated — keep only the returned state."""
    _one_chip(plan, "train_step")
    return _jitted_step(plan)(state, batch, plan.x, plan.y, plan.train_mask)


def train_epoch(plan: GASPlan, state: GASState, epoch: int
                ) -> Tuple[GASState, Dict[str, float]]:
    """One shuffled epoch over every cluster batch, as one jitted
    `lax.scan` dispatch on one chip: the same steps, in the same order,
    as a loop of `train_step` over the batches.

    With `config.prefetch_depth > 0` the scan is software-pipelined
    (see `make_prefetch_step_fn`): a prologue dispatches the first
    `depth` batches' halo pulls, then every step prefetches batch
    i+depth's halo before running batch i — so history I/O rides behind
    compute, the paper's §5 concurrent execution at the epoch level.
    Bit-identical to the synchronous schedule (state, metrics, and
    checkpoint round-trips).

    With `config.ranks > 1` the epoch is the sharded one
    (`dist_gas.make_epoch_fn`): one jitted call of P/R supersteps, whose
    metrics hold one value per superstep.

    Timed as span `gas/epoch` (`utils.spans`), in which
    `gas/epoch/regroup` rebuilds the batches, `gas/epoch/dispatch` draws
    the order and dispatches the epoch, `gas/epoch/wait` waits for its
    metrics and `gas/epoch/readback` turns them into host floats."""
    with spans.span("gas/epoch"):
        cfg = plan.config
        cadence_due = (cfg.vq_refit_every > 0 and epoch > 0
                       and epoch % cfg.vq_refit_every == 0)
        drift_due = (cfg.vq_refit_drift > 0 and plan._last_qerr is not None
                     and plan._last_qerr > cfg.vq_refit_drift)
        if (cadence_due or drift_due) and plan.history_dtype == "vq":
            # k-means M-step on the vq codebooks from the stats last
            # epoch's pushes accumulated — on the fixed cadence and/or
            # whenever the measured quantization error drifted past
            # `vq_refit_drift`. Host-driven, OUTSIDE the jitted step: the
            # codebook is a constant within an epoch, which keeps the
            # prefetch pipeline's bit-identity guarantees
            state = replace(state,
                            histories=state.histories.refit_codebooks())
        if cfg.clusters_per_batch > 1 and epoch > 0:
            with spans.span("gas/epoch/regroup"):
                _regroup(plan)
                plan.batch_stack = plan.batches.device()
        with spans.span("gas/epoch/dispatch"):
            rng = np.random.default_rng(cfg.seed * 1000 + epoch)
            order = rng.permutation(plan.batches.num_batches)
            state, metrics = _dispatch_epoch(plan, state, order)
        with spans.span("gas/epoch/wait"):
            jax.block_until_ready(metrics)
        with spans.span("gas/epoch/readback"):
            out = {k: float(np.mean(v)) for k, v in metrics.items()}
        return state, _epoch_metrics(plan, out)


def _dispatch_epoch(plan: GASPlan, state: GASState, order: np.ndarray
                    ) -> Tuple[GASState, Dict[str, Any]]:
    """Dispatches one epoch's steps in `order`; returns the state and
    each metric's per-batch values (device arrays, not waited for)."""
    cfg = plan.config
    if plan.mesh is not None:
        if plan._epoch is None:
            plan._epoch = jax.jit(
                dist_gas.make_epoch_fn(plan.spec, cfg, plan.backend,
                                       plan.mesh), donate_argnums=(0,))
        return plan._epoch(state, plan.batch_stack, jnp.asarray(order),
                           plan.x, plan.y, plan.train_mask)
    if plan._epoch is None:
        depth = _resolved_depth(plan)
        if depth == 0:
            step = make_step_fn(plan)

            @functools.partial(jax.jit, donate_argnums=(0,))
            def epoch_fn(state, batch_stack, order, x, y, train_mask):
                def body(st, idx):
                    batch = jax.tree_util.tree_map(lambda a: a[idx],
                                                   batch_stack)
                    st, metrics = step(st, batch, x, y, train_mask)
                    return st, metrics

                return jax.lax.scan(body, state, order)
        else:
            pf_step = make_prefetch_step_fn(plan, depth)

            @functools.partial(jax.jit, donate_argnums=(0,))
            def epoch_fn(state, batch_stack, order, x, y, train_mask):
                def get(i):
                    return jax.tree_util.tree_map(lambda a: a[i],
                                                  batch_stack)

                # prologue: the first `depth` batches' pulls are in
                # flight before any step runs
                queue = tuple(
                    _prefetch_entry(state.histories, get(order[j]))
                    for j in range(depth))

                def body(carry, inp):
                    st, q = carry
                    idx, fidx = inp
                    st, metrics, q = pf_step(st, get(idx), get(fidx),
                                             q, x, y, train_mask)
                    return (st, q), metrics

                (state, _), metrics = jax.lax.scan(
                    body, (state, queue),
                    (order, jnp.roll(order, -depth)))
                return state, metrics

        plan._epoch = epoch_fn
    return plan._epoch(state, plan.batch_stack, jnp.asarray(order),
                       plan.x, plan.y, plan.train_mask)


def _epoch_metrics(plan: GASPlan, out: Dict[str, float]) -> Dict[str, float]:
    """Record the epoch's mean quantization error on the plan — the
    signal `vq_refit_drift` gates the next epoch's codebook refit on."""
    if "hist_quant_err" in out:
        plan._last_qerr = out["hist_quant_err"]
    return out


def fit(plan: GASPlan, state: GASState, epochs: Optional[int] = None,
        log_every: int = 0) -> Tuple[GASState, List[Dict[str, float]]]:
    out = []
    for e in range(epochs or plan.config.epochs):
        state, m = train_epoch(plan, state, e)
        out.append(m)
        if log_every and (e + 1) % log_every == 0:
            ev = evaluate_exact(plan, state)
            print(f"epoch {e+1}: loss={m['loss']:.4f} "
                  f"val={ev['val_acc']:.4f} test={ev['test_acc']:.4f}")
    return state, out


def predict(plan: GASPlan, state: GASState) -> jnp.ndarray:
    """Constant-memory history-based inference (paper advantage #2): one
    jitted dispatch, `lax.scan` over the stacked batches. The history
    store is NOT donated — `state` stays valid for further training."""
    from repro.gnn.model import gas_batch_forward
    _one_chip(plan, "predict")

    if plan._predict is None:
        spec, cfg, backend = plan.spec, plan.config, plan.backend
        N, C = plan.graph.num_nodes, spec.num_classes

        @jax.jit
        def predict_fn(params, store, batch_stack, x):
            def body(store, batch):
                logits, store, _reg, _diags = gas_batch_forward(
                    params, spec, x, batch, store,
                    use_history=cfg.use_history, backend=backend,
                    fuse_halo=cfg.fuse_halo,
                    halo_age_decay=cfg.halo_age_decay)
                return store, (logits, batch.batch_nodes, batch.batch_mask)

            _, (lg, nodes, masks) = jax.lax.scan(body, store, batch_stack)
            safe = jnp.where(masks, nodes, N).reshape(-1)
            out = jnp.zeros((N + 1, C), lg.dtype)
            # each node lives in exactly one cluster -> order-independent
            return out.at[safe].set(lg.reshape(-1, C), mode="drop")[:N]

        plan._predict = predict_fn
    return plan._predict(state.params, state.histories, plan.batch_stack,
                         plan.x)


def evaluate_exact(plan: GASPlan, state: GASState) -> Dict[str, float]:
    """Exact full-propagation evaluation (the paper evaluates exactly)."""
    from repro.gnn.model import full_forward

    logits = full_forward(state.params, plan.spec, plan.x, plan.eval_edges,
                          plan.eval_w, plan.graph.num_nodes)
    y = jnp.asarray(plan.graph.y)
    g = plan.graph
    return {f"{name}_acc": float(_accuracy(logits, y, jnp.asarray(mask)))
            for name, mask in (("train", g.train_mask), ("val", g.val_mask),
                               ("test", g.test_mask))}
