"""GAS serving: history tables as a low-latency node-embedding cache.

Training (Algorithm 1) fills one [N+1, d] table per hidden layer with
each node's most recent layer output. Serving flips that data structure
around: a batched inference request for an arbitrary query set Q is
answered by ONE padded mini-batch over Q whose halo rows come straight
out of the trained tables — per-request cost is O(|Q| + halo), not
O(neighborhood^L) recursive recomputation. Quantized stores (bf16/int8/
vq) are served as-is through the same fused dequant-gather pull path
training uses; no up-front dequantized copy of the cache is ever
materialized, and serving NEVER mutates the vq codebook or its k-means
refit statistics (`serve_step` restores them bit-for-bit — a refresh
must reuse the codebook the codes were written under).

Staleness SLO. Every table row carries an `age` (serve steps since the
row was last re-pushed). A request under `ServeConfig.staleness_slo = s`
is answered only from rows with age <= s: rows older than the bound are
re-pushed first by a single *refresh* batch over the stale closure of Q
(see `stale_closure`), then the query batch runs against the refreshed
tables. `s = None` disables refresh entirely (pure cache reads);
`s = 0` forces exact serving:

  * `init_serve_state` advances every age by one, so nothing a training
    run pushed (with pre-update parameters) is ever trusted as exact;
  * with s = 0 the refresh closure covers every stale node reachable
    from Q through stale-only in-paths within L-1 hops, which makes the
    query-batch halo pulls exact layer by layer (the paper's Theorem 2
    staleness term vanishes) — serving equals the full-graph forward
    bit-for-bit for f32 stores, and equals the quantize-roundtrip
    recursion for compressed stores (tests/test_serve.py pins both);
  * ages are reset only for rows the bound proves fresh: at s = 0 the
    query rows and the depth<=1 refresh rows (whole table stack provably
    exact — deeper rows get improved values but keep their old age, so
    they can never poison a later exact request); at s > 0 the clock
    simply means "steps since recompute" and every re-pushed row resets.

Request-size bucketing. Query sets are padded up to the next size in
`ServeConfig.buckets` and halo/edge pads are precomputed per bucket from
worst-case degree sums, so every request of a bucket reuses one jit
trace (`ServePlan.trace_log` records trace events for the no-retrace
tests). Refresh batches use a doubling ladder of the same buckets up to
N, so the whole closure always runs as ONE layer-synchronous batch —
chunking a refresh would break the exactness induction. On kernel
backends the request subgraph is additionally tiled into BCSR blocks
(`gas.subgraph_batch(build_blocks=True)`), so the serve step aggregates
through `ops.gas_aggregate`/`gather_spmm` — never the edge-indexed
segment fallback (jaxpr-asserted, like the train step). Block counts K
grow lazily per bucket (`ServePlan._pad_k`, mirroring `GASPlan._pad_k`):
a request whose closure is denser than anything the bucket has seen
re-traces once, then the grown pad is the bucket's floor.

Surface — the runtime's plan/state/step contract, serving edition:

    ServeConfig -> build_serve_plan -> init_serve_state -> serve_request

`ServePlan` is the static compiled artifact, `ServeState` the frozen
pytree threaded through requests (params + bound `HistoryStore` + the
monotonic table `version`, bumped by every writing step — the
process-split wire protocol in `core.serve_service` keys its
generation handshake on it). `serve_step` is the pure jitted per-bucket
step; `serve_request` the orchestrator (dedup, bucketing, refresh,
diagnostics). The PR-6 names (`bind_state`, `serve`) remain as
one-release deprecation shims that warn and delegate. Diagnostics per
request: `halo_age_mean`/`halo_age_max` of the served halo rows measured
AFTER refresh (the SLO assertion is `halo_age_max <= s`),
`hist_quant_err` of the serve-time re-pushes, and the refreshed-row
count.
"""
from __future__ import annotations

import dataclasses
import functools
import warnings
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.data.graphs import Graph
from repro.kernels import ops
from . import delta
from . import gas as G
from .batch import GASBatch
from .config import HistoryExecConfig
from .history import HistoryStore

# age stamped on rows invalidated by a feature update: large enough that
# every finite staleness SLO treats them as stale until re-pushed
INVALID_AGE = 1 << 20


@dataclass(frozen=True, kw_only=True)
class ServeConfig(HistoryExecConfig):
    """Serving knobs. The shared execution knobs come from
    `core.config.HistoryExecConfig`: `staleness_slo` (overridden default
    0 — max acceptable history age of any served halo row; 0 refreshes
    to exactness, None never refreshes), `backend` (None = bound store's
    backend wins, via `gnn.model.resolve_store`) and `history_dtype` (None =
    bound store's dtype wins; set it to make `init_serve_state` reject a
    store of any other precision). `buckets`: query-size pads (requests
    round up to the next bucket so assorted batch sizes share jit
    traces)."""
    staleness_slo: Optional[int] = 0
    buckets: Tuple[int, ...] = (8, 32, 128)


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=["params", "histories", "version"], meta_fields=[])
@dataclass(frozen=True)
class ServeState:
    """The complete serving state as one frozen pytree — the serving
    mirror of `runtime.GASState` (no optimizer, no rng): model `params`,
    the bound `HistoryStore`, and the monotonic table `version` — a
    scalar int32 leaf bumped by every writing `serve_step`/push, so two
    states of one serve plan are ordered and the process-split frontends
    (`core.serve_service`) can refuse to mix rows from two refresh
    generations. A leaf (not aux data) so version bumps never retrace."""
    params: Any
    histories: HistoryStore
    version: jnp.ndarray

    def replace(self, **kw) -> "ServeState":
        return dataclasses.replace(self, **kw)


@dataclass
class ServePlan:
    """Everything built once per served graph: the weighted in-edge CSR
    (global-COO per-destination order preserved — the bit-for-bit
    contract depends on it), per-bucket padding bounds, the BCSR
    emission switches, and the cached jitted step. Holds no mutable
    serving state; the history cache lives in the `ServeState` threaded
    through `serve_request`/`serve_step`."""
    graph: Graph
    spec: Any                              # gnn.model.GNNSpec
    config: ServeConfig
    backend: str
    x: jnp.ndarray
    indptr: np.ndarray                     # [N+1] in-edge CSR (w/ loops)
    src: np.ndarray                        # [E] sources, per-dst order
    w: np.ndarray                          # [E] GCN-normalized weights
    query_buckets: Tuple[int, ...]
    refresh_buckets: Tuple[int, ...]
    pads: Dict[int, Tuple[int, int]]       # bucket -> (max_h, max_e)
    build_blocks: bool = False
    unit_weights: bool = False
    bn: int = 128
    trace_log: List[Tuple[int, int, int]] = field(default_factory=list)
    # bucket -> (K, K_t) lazy monotone block-count floors (see module
    # docstring; the serve-side mirror of GASPlan._pad_k)
    _pad_k: Dict[int, Tuple[int, int]] = field(default_factory=dict)
    _step: Optional[Callable] = None


def build_serve_plan(graph: Graph, spec, config: ServeConfig) -> ServePlan:
    """CSR + padding bounds + bucket ladders; no trainable state."""
    from repro.gnn.model import BLOCK_OPS, UNIT_BLOCK_OPS
    backend = ops.resolve_backend(config.backend)
    N = graph.num_nodes
    dst, src, w = G.gcn_edge_weights(graph)
    order = np.argsort(dst, kind="stable")   # keeps per-dst edge order
    dst_s, src_s, w_s = dst[order], src[order], w[order]
    counts = np.bincount(dst_s, minlength=N)
    indptr = np.concatenate([[0], np.cumsum(counts)]).astype(np.int64)

    if not config.buckets:
        raise ValueError("ServeConfig.buckets must be non-empty")
    qb = tuple(sorted({min(int(b), N) for b in config.buckets if b > 0}))
    if not qb:
        raise ValueError(f"no usable bucket in {config.buckets}")
    ladder = list(qb)
    while ladder[-1] < N:
        ladder.append(min(ladder[-1] * 2, N))
    rb = tuple(dict.fromkeys(ladder))

    # worst-case pads per bucket size b: any b nodes pull at most the
    # top-b in-degree sum of edges, and at most one distinct halo node
    # per non-self edge (degrees here include the self-loop)
    degs = (indptr[1:] - indptr[:-1]).astype(np.int64)
    dsort = np.sort(degs)[::-1]
    cum_e = np.cumsum(dsort)
    cum_h = np.cumsum(np.maximum(dsort - 1, 0))
    pads = {}
    for b in set(qb) | set(rb):
        max_e = int(cum_e[min(b, N) - 1])
        max_h = int(max(1, min(cum_h[min(b, N) - 1], N)))
        pads[b] = (max_h, max(max_e, 1))

    # same emission rule as runtime.build_plan: only kernel backends
    # read blocks; GIN/GAT/PNA aggregate through the unit-weight
    # (multiplicity) families
    build_blocks = spec.op in BLOCK_OPS and backend != "jnp"
    unit_weights = spec.op in UNIT_BLOCK_OPS
    return ServePlan(graph=graph, spec=spec, config=config, backend=backend,
                     x=jnp.asarray(graph.x), indptr=indptr, src=src_s,
                     w=w_s, query_buckets=qb, refresh_buckets=rb, pads=pads,
                     build_blocks=build_blocks, unit_weights=unit_weights)


def init_serve_state(plan: ServePlan, state) -> ServeState:
    """Bind a trained state (`runtime.GASState`, or anything with
    `params`/`histories`) to the serving clock: every age is advanced
    once, because training's final step pushed its rows BEFORE the
    parameter update — under the served parameters no table row is exact
    until serving re-pushes it. After the bind, an SLO of 0 refreshes
    everything a first request touches. The table version starts at 0.

    When the plan's config pins a `history_dtype`, a store of any other
    precision is rejected here — the serve-side validation of the folded
    `HistoryExecConfig` knob."""
    store = state.histories
    if store.age.shape[0] != plan.graph.num_nodes + 1:
        raise ValueError(
            f"state serves {store.age.shape[0] - 1} nodes, plan has "
            f"{plan.graph.num_nodes}")
    want = plan.config.history_dtype
    if want is not None and want != store.history_dtype:
        raise ValueError(
            f"plan pins history_dtype={want!r} but the bound store is "
            f"{store.history_dtype!r}")
    return ServeState(
        params=state.params,
        histories=dataclasses.replace(store, age=store.age + 1),
        version=jnp.zeros((), jnp.int32))


def apply_feature_update(plan: ServePlan, state, nodes: np.ndarray,
                         values: np.ndarray):
    """Apply in-place node-feature updates to a live serving plan and
    invalidate every history row the change can reach.

    The plan's features are rewritten (`plan.x` and `plan.graph` — the
    graph structure is untouched), and every node within L-1 hops of an
    updated node — the updates' out-closure, computed by the shared
    `core.delta.hop_closure` walk over the plan's own CSR — gets its age
    stamped `INVALID_AGE`: the deepest table row (layer L-2) depends on
    features L-1 hops away, so everything inside that closure may now
    disagree with a fresh recompute, and nothing outside it can. Under
    any finite staleness SLO the next request touching the closure
    refreshes it through the normal `stale_closure` machinery; at SLO=0
    post-update serves are again bit-for-bit the full recompute on the
    NEW features (pinned by tests/test_serve.py). `slo=None` plans keep
    serving the old cached rows by design — pure cache reads.

    Accepts a `ServeState` (bumping its version — an invalidation is a
    write generation) or, for the deprecated flow, a `GASState`; returns
    the updated state of the same type. The plan is updated in place."""
    N = plan.graph.num_nodes
    nodes = np.asarray(nodes, np.int64).ravel()
    values = np.asarray(values, np.float32)
    # GraphDelta validates shape/uniqueness/range exactly once
    d = delta.GraphDelta(feat_nodes=nodes, feat_values=values)
    new_x = np.array(plan.graph.x, np.float32)
    if values.shape[1:] != new_x.shape[1:]:
        raise ValueError(
            f"feature width {values.shape[1:]} != {new_x.shape[1:]}")
    if len(nodes) and (nodes.min() < 0 or nodes.max() >= N):
        raise ValueError(f"update ids must be in [0, {N})")
    new_x[d.feat_nodes] = d.feat_values
    plan.graph = dataclasses.replace(plan.graph, x=new_x)
    plan.x = jnp.asarray(new_x)

    closure = delta.hop_closure(plan.indptr, plan.src, nodes,
                                plan.spec.num_layers - 1)
    store = state.histories
    age = store.age.at[closure].set(INVALID_AGE)
    out = state.replace(histories=dataclasses.replace(store, age=age))
    if isinstance(out, ServeState):
        out = out.replace(version=out.version + 1)
    return out


# ---------------------------------------------------------------------------
# Stale closure (host-side BFS over the in-edge CSR)
# ---------------------------------------------------------------------------

def _in_neighbors(plan: ServePlan, nodes: np.ndarray) -> np.ndarray:
    # one frontier expansion over the weighted in-CSR — the shared
    # closure primitive in core.delta (the out-closure walk on these
    # undirected graphs is the same expansion in the other direction)
    return delta.csr_neighbors(plan.indptr, plan.src, nodes)


def stale_closure(plan: ServePlan, age: np.ndarray, query: np.ndarray,
                  slo: Optional[int]) -> Tuple[np.ndarray, np.ndarray]:
    """Nodes to re-push before serving `query` under staleness bound
    `slo`: BFS from Q over in-edges, depth 1..L-1, expanding only
    through stale rows (age > slo). Depth 1 excludes Q (query rows are
    recomputed live anyway); deeper levels may re-enter Q — a stale
    query node feeding a depth-1 halo row must be refreshed too.
    Returns (refresh set, its depth<=1 subset), both sorted unique.

    Fresh rows prune the walk: their tables are already good enough for
    the bound, so nothing behind them needs recomputation. At slo = 0
    this closure is exactly what makes the single layer-synchronous
    refresh batch exact, layer by layer (see the module docstring)."""
    empty = np.zeros(0, np.int64)
    L = plan.spec.num_layers
    if slo is None or L <= 1:
        return empty, empty
    N = plan.graph.num_nodes
    stale = np.asarray(age)[:N] > slo
    in_q = np.zeros(N, bool)
    in_q[query] = True
    in_r = np.zeros(N, bool)
    frontier = np.asarray(query, np.int64)
    depth1 = empty
    for depth in range(1, L):
        nbrs = _in_neighbors(plan, frontier)
        if nbrs.size == 0:
            break
        cand = stale[nbrs] & ~in_r[nbrs]
        if depth == 1:
            cand &= ~in_q[nbrs]
        new = nbrs[cand]
        if depth == 1:
            depth1 = new
        if new.size == 0:
            break
        in_r[new] = True
        frontier = new
    return np.flatnonzero(in_r).astype(np.int64), depth1


# ---------------------------------------------------------------------------
# Request batches + the jitted per-bucket step
# ---------------------------------------------------------------------------

def _bucket_for(buckets: Tuple[int, ...], n: int) -> int:
    for b in buckets:
        if n <= b:
            return b
    raise ValueError(f"request of {n} rows exceeds largest bucket "
                     f"{buckets[-1]} (serve_request() chunks before this)")


def build_request_batch(plan: ServePlan, nodes: np.ndarray,
                        bucket: int) -> GASBatch:
    """One single-batch `GASBatch` over an arbitrary node set, padded to
    the bucket's static (max_b, max_h, max_e) — same index conventions
    as `core.gas.build_batches` (pad node N, trash row max_b, dummy zero
    row max_b + max_h), and the same per-destination edge order as the
    global COO, which the bit-for-bit equivalence rests on. The cut
    itself is `core.gas.subgraph_batch` (shared with the dynamic
    re-push); serving adds the bucket pads, the BCSR block emission on
    kernel backends (block counts padded to the bucket's lazy monotone
    K floor, which this call grows), and the device upload."""
    max_h, max_e = plan.pads[bucket]
    kw = {}
    if plan.build_blocks:
        k0, k0t = plan._pad_k.get(bucket, (1, 1))
        kw = dict(build_blocks=True, unit_weights=plan.unit_weights,
                  bn=plan.bn, pad_k=k0, pad_k_t=k0t)
    batch = G.subgraph_batch(plan.indptr, plan.src, plan.w,
                             plan.graph.num_nodes, nodes, max_b=bucket,
                             max_h=max_h, max_e=max_e, **kw)
    if plan.build_blocks:
        fam = batch.unit if plan.unit_weights else batch.forward
        fam_t = (batch.unit_transposed if plan.unit_weights
                 else batch.transposed)
        plan._pad_k[bucket] = (int(fam.cols.shape[1]),
                               int(fam_t.cols.shape[1]))
    return batch.device()


def _step_fn(plan: ServePlan) -> Callable:
    spec, backend = plan.spec, plan.backend
    trace_log = plan.trace_log

    def step(params, store, batch, reset_idx, reset_mask, x):
        # runs at trace time only: one entry per (bucket, treedef)
        trace_log.append((batch.max_b, batch.max_h, batch.max_e))
        from repro.gnn.model import gas_batch_forward
        logits, store2, _reg, diags = gas_batch_forward(
            params, spec, x, batch, store, use_history=True,
            backend=backend)
        # serving must not advance the global staleness clock: keep
        # the pre-step ages and clear only the rows the caller
        # proves fresh under the configured bound (see `serve_request`)
        safe = jnp.where(reset_mask, reset_idx, store.age.shape[0])
        age = store.age.at[safe].set(0, mode="drop")
        # serving must not mutate the vq codebook or its k-means refit
        # statistics either: the store's codes were written under the
        # bound codebook, and a refresh that shifted it (or accumulated
        # refit stats toward a future shift) would silently re-encode
        # rows under a different quantizer mid-serve. Restore the
        # pre-step codebook state bit-for-bit — only tables/scales/age
        # may change under serving.
        store2 = dataclasses.replace(
            store2, age=age, codebooks=store.codebooks,
            cb_counts=store.cb_counts, cb_sums=store.cb_sums)
        return logits, store2, diags

    return step


def make_serve_step_fn(plan: ServePlan) -> Callable:
    """The un-jitted serve step `(params, store, batch, reset_idx,
    reset_mask, x) -> (logits, store, diags)` — the serving mirror of
    `runtime.make_step_fn`, for jaxpr introspection (the no-edge-indexed
    -gather assertion) and custom jit wrappers."""
    return _step_fn(plan)


def _jitted_step(plan: ServePlan) -> Callable:
    if plan._step is None:
        plan._step = jax.jit(_step_fn(plan))
    return plan._step


def serve_step(plan: ServePlan, state: ServeState, batch: GASBatch,
               reset_idx: jnp.ndarray, reset_mask: jnp.ndarray
               ) -> Tuple[jnp.ndarray, ServeState, Dict[str, jnp.ndarray]]:
    """Pure jitted serving step on one padded request batch: the GAS
    forward (halo rows pulled — and dequantized in the same gather —
    from the bound history tables; BCSR-blocked aggregation on kernel
    backends), write-back pushes of the freshly computed rows, and the
    age resets in `reset_idx`/`reset_mask` ([max_b], padding masked).
    One trace per padding bucket. A step writes tables, so the state
    version is bumped. Returns (logits [max_b, C], the next
    `ServeState`, diagnostics)."""
    logits, store, diags = _jitted_step(plan)(
        state.params, state.histories, batch, reset_idx, reset_mask,
        plan.x)
    return logits, state.replace(histories=store,
                                 version=state.version + 1), diags


def _reset_arrays(rows: np.ndarray, bucket: int) -> Tuple[jnp.ndarray,
                                                          jnp.ndarray]:
    idx = np.zeros(bucket, np.int32)
    mask = np.zeros(bucket, bool)
    idx[:len(rows)] = rows
    mask[:len(rows)] = True
    return jnp.asarray(idx), jnp.asarray(mask)


# ---------------------------------------------------------------------------
# Request orchestration
# ---------------------------------------------------------------------------

def serve_request(plan: ServePlan, state: ServeState, query_nodes
                  ) -> Tuple[np.ndarray, ServeState, Dict[str, float]]:
    """Answer one batched inference request.

    Dedups the query ids, chunks them to the largest bucket, and per
    chunk: reads the staleness clock, re-pushes the stale closure as one
    layer-synchronous refresh batch (bound permitting), then serves the
    query batch against the refreshed tables. Returns (logits
    [len(query_nodes), num_classes] in input order, the updated state —
    thread it into the next request — and aggregated diagnostics;
    `halo_age_*` are measured at query-batch entry, i.e. AFTER refresh,
    so `halo_age_max <= staleness_slo` is the served-SLO assertion)."""
    cfg = plan.config
    slo = cfg.staleness_slo
    N = plan.graph.num_nodes
    q = np.asarray(query_nodes, np.int64).ravel()
    if q.size == 0:
        raise ValueError("empty query")
    if q.min() < 0 or q.max() >= N:
        raise ValueError(f"query ids must be in [0, {N})")
    uniq, inv = np.unique(q, return_inverse=True)
    max_q = plan.query_buckets[-1]
    n_chunks = -(-len(uniq) // max_q)
    chunks = np.array_split(uniq, n_chunks)

    out = np.zeros((len(uniq), plan.spec.num_classes), np.float32)
    halo_means: List[float] = []
    halo_max = 0.0
    qerrs: List[float] = []
    refreshed = 0
    steps = 0
    pos = 0
    for chunk in chunks:
        age = np.asarray(state.histories.age)
        refresh, depth1 = stale_closure(plan, age, chunk, slo)
        if refresh.size:
            bucket = _bucket_for(plan.refresh_buckets, len(refresh))
            batch = build_request_batch(plan, refresh, bucket)
            # slo = 0: only the depth<=1 rows end up exact at EVERY
            # layer — deeper rows keep their age so a later exact
            # request re-checks them. slo > 0: age means "steps since
            # re-push"; every refreshed row resets.
            reset_rows = depth1 if slo == 0 else refresh
            ridx, rmask = _reset_arrays(reset_rows, bucket)
            _, state, rdiags = serve_step(plan, state, batch, ridx, rmask)
            qerrs.append(float(rdiags["hist_quant_err"]))
            refreshed += int(refresh.size)
            steps += 1
        bucket = _bucket_for(plan.query_buckets, len(chunk))
        batch = build_request_batch(plan, chunk, bucket)
        # write-back: the query rows were just recomputed; under a
        # numeric bound their clock restarts (at slo = 0 they are
        # provably exact — all halo inputs were refreshed). slo = None
        # keeps the clock read-only: no refresh happened, so a
        # recompute from arbitrarily stale inputs must not look fresh.
        reset_rows = chunk if slo is not None else np.zeros(0, np.int64)
        ridx, rmask = _reset_arrays(reset_rows, bucket)
        logits, state, qdiags = serve_step(plan, state, batch, ridx, rmask)
        out[pos:pos + len(chunk)] = np.asarray(logits)[:len(chunk)]
        halo_means.append(float(qdiags["halo_age_mean"]))
        halo_max = max(halo_max, float(qdiags["halo_age_max"]))
        qerrs.append(float(qdiags["hist_quant_err"]))
        steps += 1
        pos += len(chunk)

    diags = {
        "halo_age_mean": float(np.mean(halo_means)),
        "halo_age_max": halo_max,
        "hist_quant_err": float(np.mean(qerrs)),
        "refreshed": float(refreshed),
        "num_steps": float(steps),
        "num_chunks": float(len(chunks)),
    }
    return out[inv], state, diags


# ---------------------------------------------------------------------------
# One-release deprecation shims (the PR-6 surface)
# ---------------------------------------------------------------------------

def bind_state(plan: ServePlan, state) -> ServeState:
    """Deprecated: use `init_serve_state(plan, state)`. Warns and
    delegates; note the return type is the new `ServeState` (it carries
    `params`/`histories` like the old bound `GASState` did, plus the
    table `version`)."""
    warnings.warn(
        "serve.bind_state is deprecated; use "
        "serve.init_serve_state(plan, state)",
        DeprecationWarning, stacklevel=2)
    return init_serve_state(plan, state)


def serve(plan: ServePlan, state, query_nodes
          ) -> Tuple[np.ndarray, ServeState, Dict[str, float]]:
    """Deprecated: use `serve_request(plan, state, query_nodes)`. Warns
    and delegates; a legacy bound `GASState` is wrapped into a
    `ServeState` (version 0, ages untouched — `bind_state` already
    advanced them) on the way through."""
    warnings.warn(
        "serve.serve is deprecated; use "
        "serve.serve_request(plan, state, query_nodes)",
        DeprecationWarning, stacklevel=2)
    if not isinstance(state, ServeState):
        state = ServeState(params=state.params, histories=state.histories,
                           version=jnp.zeros((), jnp.int32))
    return serve_request(plan, state, query_nodes)
