"""GNN model zoo assembled for both full-batch and GAS mini-batch execution.

A model = (pre, prop-layer stack, post):
  pre  : per-node input transform (exact for halo nodes too — no staleness),
  prop : K message-passing layers — the layers GAS interposes histories on,
  post : per-node readout.

`gas_batch_forward` implements Algorithm 1 on one padded cluster batch,
including the Eq. 3 local-Lipschitz regularizer for non-linear operators.
`full_forward` runs the identical layer code on the whole graph (halo-free)
— the full-batch baseline of Tables 1/5.
"""
from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import history as H
from repro.core.batch import GASBatch, ensure_batch
from repro.kernels import ops
from . import layers as L


@dataclass(frozen=True)
class GNNSpec:
    op: str                     # gcn | gat | gin | gcnii | appnp | pna
    d_in: int
    d_hidden: int
    num_classes: int
    num_layers: int             # number of propagation layers K
    heads: int = 8              # gat
    alpha: float = 0.1          # appnp / gcnii
    lam: float = 0.5            # gcnii identity-map strength
    dropout: float = 0.0
    reg_delta: float = 0.0      # Eq. 3 perturbation radius (0 = off)
    reg_weight: float = 0.0
    log_deg_mean: float = 1.0   # pna

    def hist_dims(self) -> List[int]:
        """Dims of H̄^(1..K-1) — outputs of prop layers 0..K-2."""
        if self.op == "appnp":
            return [self.num_classes] * (self.num_layers - 1)
        if self.op in ("gcn", "gat"):
            dims = [self.d_hidden] * (self.num_layers - 1)
            return dims
        return [self.d_hidden] * (self.num_layers - 1)


def init_gnn(key, spec: GNNSpec) -> Dict[str, Any]:
    keys = jax.random.split(key, spec.num_layers + 4)
    p: Dict[str, Any] = {"layers": []}
    op = spec.op
    if op == "gcn":
        dims = [spec.d_in] + [spec.d_hidden] * (spec.num_layers - 1) + \
            [spec.num_classes]
        p["layers"] = [L.init_gcn(keys[i], dims[i], dims[i + 1])
                       for i in range(spec.num_layers)]
    elif op == "gat":
        dims = [spec.d_in] + [spec.d_hidden] * (spec.num_layers - 1) + \
            [spec.num_classes]
        p["layers"] = [L.init_gat(keys[i], dims[i], dims[i + 1],
                                  spec.heads if i < spec.num_layers - 1 else 1)
                       for i in range(spec.num_layers)]
    elif op == "gin":
        dims = [spec.d_in] + [spec.d_hidden] * spec.num_layers
        p["layers"] = [L.init_gin(keys[i], dims[i], dims[i + 1])
                       for i in range(spec.num_layers)]
        p["head"] = {"w": L._glorot(keys[-1], (spec.d_hidden, spec.num_classes)),
                     "b": jnp.zeros((spec.num_classes,))}
    elif op == "gcnii":
        p["w_in"] = {"w": L._glorot(keys[-2], (spec.d_in, spec.d_hidden)),
                     "b": jnp.zeros((spec.d_hidden,))}
        p["layers"] = [L.init_gcnii(keys[i], spec.d_hidden)
                       for i in range(spec.num_layers)]
        p["head"] = {"w": L._glorot(keys[-1], (spec.d_hidden, spec.num_classes)),
                     "b": jnp.zeros((spec.num_classes,))}
    elif op == "appnp":
        k1, k2 = jax.random.split(keys[-1])
        p["mlp"] = {"w1": L._glorot(k1, (spec.d_in, spec.d_hidden)),
                    "b1": jnp.zeros((spec.d_hidden,)),
                    "w2": L._glorot(k2, (spec.d_hidden, spec.num_classes)),
                    "b2": jnp.zeros((spec.num_classes,))}
    elif op == "pna":
        dims = [spec.d_in] + [spec.d_hidden] * spec.num_layers
        p["layers"] = [L.init_pna(keys[i], dims[i], dims[i + 1])
                       for i in range(spec.num_layers)]
        p["head"] = {"w": L._glorot(keys[-1], (spec.d_hidden, spec.num_classes)),
                     "b": jnp.zeros((spec.num_classes,))}
    else:
        raise ValueError(op)
    return p


def _pre(params, spec: GNNSpec, x):
    if spec.op == "gcnii":
        return jax.nn.relu(x @ params["w_in"]["w"] + params["w_in"]["b"])
    if spec.op == "appnp":
        h = jax.nn.relu(x @ params["mlp"]["w1"] + params["mlp"]["b1"])
        return h @ params["mlp"]["w2"] + params["mlp"]["b2"]
    return x


def _post(params, spec: GNNSpec, h):
    if spec.op in ("gin", "gcnii", "pna"):
        return h @ params["head"]["w"] + params["head"]["b"]
    return h


def _prop(params, spec: GNNSpec, ell: int, x_all, edges, edge_w, n_out, ctx):
    op = spec.op
    last = ell == spec.num_layers - 1
    if op == "gcn":
        h = L.gcn(params["layers"][ell], x_all, edges, edge_w, n_out,
                  blocks=ctx.get("blocks"), backend=ctx.get("backend"))
        return h if last else jax.nn.relu(h)
    if op == "gat":
        h = L.gat(params["layers"][ell], x_all, edges, edge_w, n_out,
                  ublocks=ctx.get("ublocks"), backend=ctx.get("backend"))
        return h if last else jax.nn.elu(h)
    if op == "gin":
        h = L.gin(params["layers"][ell], x_all, edges, edge_w, n_out,
                  blocks=ctx.get("ublocks"), backend=ctx.get("backend"))
        return jax.nn.relu(h)
    if op == "gcnii":
        beta = math.log(spec.lam / (ell + 1) + 1.0)
        h = L.gcnii(params["layers"][ell], x_all, edges, edge_w, n_out,
                    ctx["h0"], spec.alpha, beta,
                    blocks=ctx.get("blocks"), backend=ctx.get("backend"))
        return jax.nn.relu(h)
    if op == "appnp":
        return L.appnp_prop(x_all, edges, edge_w, n_out, ctx["h0"],
                            spec.alpha, blocks=ctx.get("blocks"),
                            backend=ctx.get("backend"))
    if op == "pna":
        h = L.pna(params["layers"][ell], x_all, edges, edge_w, n_out,
                  spec.log_deg_mean, ublocks=ctx.get("ublocks"),
                  backend=ctx.get("backend"))
        return jax.nn.relu(h)
    raise ValueError(op)


# fixed-weight SpMM ops: eligible for the fused history-gather route
# (layers >= 1 aggregate straight out of the history table)
FUSED_OPS = ("gcn", "gin", "gcnii", "appnp")
# data-dependent-aggregation ops: no fused gather_spmm, but layers >= 1
# still avoid materializing the dequantized halo via the halo-split route
# (`_halo_prop`: lane-padded pulls + zero-padded per-node transforms)
HALO_SPLIT_OPS = ("gat", "pna")
# ops that consume the *unit-weight* (multiplicity) blocks instead of the
# GCN-normalized ones: GIN's unweighted sum, GAT's edge softmax, PNA's
# multi-aggregator reduction
UNIT_BLOCK_OPS = ("gin", "gat", "pna")
# every operator with a block-dense kernel route (forward AND backward):
# the whole zoo — no segment_* island remains
BLOCK_OPS = ("gcn", "gin", "gcnii", "appnp", "gat", "pna")


def _fused_prop(params, spec: GNNSpec, ell: int, x_cur,
                store: H.HistoryStore, batch: GASBatch, ctx):
    """One propagation layer on the fused kernel path: the aggregation
    reads halo columns straight out of the layer's history table
    (`ops.gas_aggregate`, no materialized x_all — int8 tables are
    dequantized and vq code tables codebook-decoded in-kernel against
    the store's per-row scales), then applies the op's `*_combine`
    transform — identical math to `_prop` over concat([x_cur, pull,
    0])."""
    op = spec.op
    n_out = batch.batch_mask.shape[0]
    blocks = ctx["ublocks"] if op == "gin" else ctx["blocks"]
    agg = ops.gas_aggregate(x_cur, store.tables[ell - 1],
                            batch.halo_nodes, batch.halo_mask, n_out,
                            blocks, scales=store.layer_scales(ell - 1),
                            codebook=store.layer_codebook(ell - 1),
                            backend=ctx.get("backend"))
    last = ell == spec.num_layers - 1
    if op == "gcn":
        h = L.gcn_combine(params["layers"][ell], agg)
        return h if last else jax.nn.relu(h)
    if op == "gin":
        h = L.gin_combine(params["layers"][ell], x_cur, agg)
        return jax.nn.relu(h)
    if op == "gcnii":
        beta = math.log(spec.lam / (ell + 1) + 1.0)
        h = L.gcnii_combine(params["layers"][ell], agg, ctx["h0"],
                            spec.alpha, beta)
        return jax.nn.relu(h)
    if op == "appnp":
        return L.appnp_combine(agg, ctx["h0"], spec.alpha)
    raise ValueError(op)


def _halo_prop(params, spec: GNNSpec, ell: int, x_cur,
               store: H.HistoryStore, batch: GASBatch,
               edges, edge_w, ctx):
    """One GAT/PNA propagation layer without materializing the
    dequantized halo. These ops have no fused `gas_aggregate` route
    (data-dependent edge softmax / multi-aggregator), but the PR-5 debt
    — a [max_h, d] f32 halo tensor materialized in HBM per layer — is
    retired the same way: the halo rows are pulled LANE-PADDED
    (`pull_rows(..., pad_out=True)`: int8/vq stores dequantize/decode
    inside the gather kernel, and the result keeps the kernel's padded
    width), the per-node transforms run with zero-padded weights
    (`gat_transform_split` / `pna_transform_split`), and only the padded
    intermediates ever exist. Identical math to `_prop` over
    concat([x_cur, pull, 0]) — the padded columns are exact zeros."""
    op = spec.op
    p = params["layers"][ell]
    n_out = batch.batch_mask.shape[0]
    backend = ctx.get("backend")
    last = ell == spec.num_layers - 1
    xh_pad = store.pull(ell - 1, batch.halo_nodes, pad_out=True)
    xh_pad = xh_pad.astype(x_cur.dtype) * batch.halo_mask[:, None]
    if op == "gat":
        wx, a_d, a_s = L.gat_transform_split(p, x_cur, xh_pad)
        att = ops.edge_softmax_aggregate(wx, a_d, a_s, edges, edge_w,
                                         n_out, ctx.get("ublocks"),
                                         backend=backend)
        h = L.gat_combine(att)
        return h if last else jax.nn.elu(h)
    if op == "pna":
        f = p["b1"].shape[0]
        fp = -(-f // 128) * 128
        xd, xs = L.pna_transform_split(p, x_cur, xh_pad, fp)
        s, mn, mx, cnt = ops.pna_reduce(xd, xs, edges, edge_w, n_out,
                                        ctx.get("ublocks"),
                                        backend=backend)
        h = L.pna_combine(p, x_cur, s[:, :f], mn[:, :f], mx[:, :f], cnt,
                          spec.log_deg_mean)
        return jax.nn.relu(h)
    raise ValueError(op)


# ---------------------------------------------------------------------------
# GAS batch execution (Algorithm 1)
# ---------------------------------------------------------------------------

def staleness_diags(age: jnp.ndarray, halo_nodes: jnp.ndarray,
                    halo_mask: jnp.ndarray) -> Dict[str, jnp.ndarray]:
    """Mean/max history age (iterations since last push) of the halo rows
    this batch pulls — the staleness that Lemma 1 / Theorem 2 bound."""
    hage = jnp.take(age, halo_nodes, mode="clip").astype(jnp.float32)
    valid = halo_mask.astype(jnp.float32)
    n = jnp.maximum(jnp.sum(valid), 1.0)
    return {"halo_age_mean": jnp.sum(hage * valid) / n,
            "halo_age_max": jnp.max(hage * valid)}


def resolve_store(store: H.HistoryStore, backend: Optional[str]
                  ) -> Tuple[H.HistoryStore, str]:
    """The store and the backend to run on: the store's own bound
    backend when `backend` is None, else `backend` resolved and bound to
    a copy of the store."""
    if backend is None:
        return store, store.backend
    backend = ops.resolve_backend(backend)
    if backend != store.backend:
        store = dataclasses.replace(store, backend=backend)
    return store, backend


def materialize_x_all(ell: int, x_cur: jnp.ndarray, xh: jnp.ndarray,
                      store: H.HistoryStore, batch: GASBatch,
                      use_history: bool,
                      halo_scale: Optional[jnp.ndarray] = None
                      ) -> jnp.ndarray:
    """Unfused layer input `x_all = [x_cur ; halo_rows ; dummy-zero row]`:
    layer 0 uses the exact precomputed halo rows `xh`; layers >= 1 pull
    stale rows from the previous layer's history table (dequantized for
    compressed stores; zeros when history is off). `halo_scale` [max_h],
    when given, damps the pulled rows (haste-makes-waste staleness
    compensation — see `GASConfig.halo_age_decay`); layer-0 halo rows are
    exact raw features and are never scaled."""
    if ell == 0:
        halo_rows = xh
    elif use_history:
        halo_rows = store.pull(ell - 1, batch.halo_nodes)
        halo_rows = halo_rows.astype(x_cur.dtype) * \
            batch.halo_mask[:, None]
        if halo_scale is not None:
            halo_rows = halo_rows * halo_scale[:, None]
    else:
        halo_rows = jnp.zeros((batch.halo_nodes.shape[0],
                               x_cur.shape[-1]), x_cur.dtype)
    dummy = jnp.zeros((1, x_cur.shape[-1]), x_cur.dtype)
    return jnp.concatenate([x_cur, halo_rows, dummy], axis=0)


def gas_batch_forward(params, spec: GNNSpec, x_global: jnp.ndarray,
                      batch: GASBatch,
                      hist: H.HistoryStore,
                      use_history: bool = True,
                      rng: Optional[jax.Array] = None,
                      backend: Optional[str] = None,
                      fuse_halo: bool = True,
                      pulled: Optional[Tuple] = None,
                      halo_age_decay: float = 0.0,
                      return_pushed: bool = False,
                      apply_pushes: bool = True,
                      ) -> Tuple[jnp.ndarray, H.HistoryStore,
                                 jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Returns (logits [max_b, C], new histories, Eq.3 reg loss,
    diagnostics — mean/max history age of the pulled halo rows plus the
    mean relative quantization error of this step's pushes,
    `hist_quant_err`, exactly 0 for f32 stores); with
    `return_pushed=True`, a 5th element: the per-hidden-layer pushed
    payload tuple (what `HistoryStore.patch_pulled` consumes).

    `batch` is a single-batch `GASBatch`; `hist` is a `HistoryStore`,
    whose bound backend is used when `backend` is None.

    The resolved backend selects the kernel path for history I/O and the
    aggregation — BCSR SpMM for the weighted-sum ops, the edge-softmax /
    multi-aggregator block kernels for GAT/PNA (see `kernels/ops.py`).
    The batch's block families (when present) are forwarded to the
    propagation layers; with `fuse_halo` (default) layers ℓ >= 1 of
    GCN/GIN/GCNII/APPNP skip the per-layer halo pull + concatenate
    entirely and aggregate through the fused `gather_spmm` kernel, which
    reads halo columns directly out of the history tables (int8 stores
    dequantize and vq stores codebook-decode in-kernel — no f32 halo
    tensor in HBM). GAT/PNA layers ℓ >= 1 take the halo-split route
    instead (`_halo_prop`): lane-padded history pulls plus zero-padded
    per-node transforms, so they too never materialize a dequantized
    [max_h, d] float halo. Layer 0 keeps the materialized path: its halo
    rows are exact (raw features / `_pre` outputs, which may carry
    parameter gradients). The Eq. 3 regularizer perturbs the
    materialized x_all, so an active regularizer falls back to the
    unfused materialized path for every op.

    `pulled` (from `HistoryStore.prefetch`, dispatched a step ahead by
    the `prefetch_depth` epoch pipeline) swaps every history READ onto
    the prefetched mini-tables: halo reads become `view[arange(max_h)]`
    against `store.with_pulled(pulled)`, which is bit-identical to
    pulling `halo_nodes` from the full tables — same storage bits, same
    dequant multiplies, same block contraction order — for both the
    fused and materialized paths. Pushes (and the age clock) still hit
    the real store.

    `apply_pushes=False` computes the forward (and `hist_quant_err`,
    and the `return_pushed` payloads) WITHOUT writing anything back: no
    table scatter, no age tick — the returned histories are the input
    histories. This is the stateless-frontend mode of the serving
    process split (`core.serve_service`): a frontend runs the batch
    against prefetched mini-tables (`pulled`) and ships the pushed
    payloads to the history-owning backend instead of scattering into
    tables it does not own.

    `halo_age_decay > 0` (haste-makes-waste staleness compensation,
    `GASConfig.halo_age_decay`) damps every pulled halo row by
    `1 / (1 + decay * age)` — a stale row is trusted less the longer ago
    it was pushed; a just-pushed row (age 0) passes unscaled. The scale
    is computed once per batch from the REAL pre-step ages and applied
    on the materialized path for every layer >= 1 (fuse/halo-split are
    bypassed when the decay is on — the fused kernels read raw table
    rows), so 0.0 is bit-identical to no compensation.
    """
    batch = ensure_batch(batch)
    store, backend = resolve_store(hist, backend)
    bmask = batch.batch_mask
    hmask = batch.halo_mask
    edges = (batch.edge_dst, batch.edge_src)
    edge_w = batch.edge_w
    max_b = bmask.shape[0]

    xb = ops.pull_rows(x_global, batch.batch_nodes, backend=backend)
    xb = xb * bmask[:, None]
    xh = ops.pull_rows(x_global, batch.halo_nodes, backend=backend)
    xh = xh * hmask[:, None]

    hb = _pre(params, spec, xb)
    hh = _pre(params, spec, xh)       # exact for halo: per-node transform
    ctx = {"h0": hb, "backend": backend}
    if batch.forward is not None:
        ctx["blocks"] = batch.blocks
    if batch.unit is not None:
        # unit-weight (multiplicity) families replace the weighted ones
        # for GIN/GAT/PNA and are only ever built alongside their
        # transpose (core.gas.build_batches)
        ctx["ublocks"] = batch.ublocks

    reg_on = spec.reg_weight > 0.0 and rng is not None
    vals_t = (batch.unit_transposed if spec.op in UNIT_BLOCK_OPS
              else batch.transposed)
    fuse = (fuse_halo and use_history and backend != "jnp" and not reg_on
            and not halo_age_decay
            and spec.op in FUSED_OPS and vals_t is not None)
    # GAT/PNA: no fused aggregate, but layers >= 1 still skip the
    # materialized dequantized halo via the halo-split route (the Eq. 3
    # regularizer perturbs x_all, so it forces the materialized path)
    halo_split = (fuse_halo and use_history and backend != "jnp"
                  and not reg_on and not halo_age_decay
                  and spec.op in HALO_SPLIT_OPS)

    diags = staleness_diags(store.age, batch.halo_nodes, hmask)
    halo_scale = None
    if halo_age_decay and use_history:
        # one scale per halo slot from the pre-step clock (`store.age`
        # only advances at the final tick, so every layer sees the same
        # trust weights); the REAL halo ids — prefetch views swap the
        # batch's ids for arange, but the clock is indexed globally
        hage = jnp.take(store.age, batch.halo_nodes,
                        mode="clip").astype(jnp.float32)
        halo_scale = 1.0 / (1.0 + halo_age_decay * hage)
    if pulled is not None and use_history:
        # history READS ride the prefetched mini-tables: halo row i of
        # the view holds the exact bits of tables[halo_nodes[i]] at
        # prefetch time (+ pipeline patches), so arange-indexing the
        # view is bit-identical to halo_nodes-indexing the full tables
        hview = store.with_pulled(pulled)
        hbatch = dataclasses.replace(
            batch,
            halo_nodes=jnp.arange(hmask.shape[0], dtype=jnp.int32))
    else:
        hview, hbatch = store, batch
    reg = jnp.zeros((), jnp.float32)
    qerr = jnp.zeros((), jnp.float32)
    pushed_rows = []
    x_cur = hb
    for ell in range(spec.num_layers):
        if ell > 0 and fuse:
            x_next = _fused_prop(params, spec, ell, x_cur, hview, hbatch,
                                 ctx)
        elif ell > 0 and halo_split:
            x_next = _halo_prop(params, spec, ell, x_cur, hview, hbatch,
                                edges, edge_w, ctx)
        else:
            x_all = materialize_x_all(ell, x_cur, hh, hview, hbatch,
                                      use_history, halo_scale=halo_scale)
            x_next = _prop(params, spec, ell, x_all, edges, edge_w, max_b,
                           ctx)

            if reg_on:
                # Eq. 3: || f(h) - f(h + eps) ||, eps ~ B_delta(0);
                # normalized per node, per dim and per layer so the weight
                # is scale-free.
                rng, sub = jax.random.split(rng)
                noise = spec.reg_delta * jax.random.normal(sub, x_all.shape)
                x_pert = _prop(params, spec, ell, x_all + noise, edges,
                               edge_w, max_b, ctx)
                sq = jnp.sum(jnp.square((x_next - x_pert) * bmask[:, None]),
                             axis=-1)
                # eps-guarded norm: ||0|| has a NaN gradient otherwise
                # (padding rows have exactly-zero diff)
                diff = jnp.sqrt(sq + 1e-12) / np.sqrt(x_next.shape[-1])
                reg = reg + (jnp.sum(diff) / jnp.maximum(jnp.sum(bmask), 1)
                             ) / spec.num_layers

        if ell < spec.num_layers - 1:
            # history tables are [N+1, d] with a masked sentinel row ->
            # the kernel path scatters into the donated buffer in place
            # (quantizing on the way in for compressed stores)
            pushed = jax.lax.stop_gradient(x_next)
            if apply_pushes:
                store = store.push(ell, batch.batch_nodes, pushed, bmask)
            qerr = qerr + store.quant_error(pushed, bmask, ell)
            pushed_rows.append(pushed)
        x_cur = x_next

    diags["hist_quant_err"] = qerr / max(spec.num_layers - 1, 1)
    if apply_pushes:
        store = store.tick(batch.batch_nodes, bmask)
    logits = _post(params, spec, x_cur)
    if return_pushed:
        return logits, store, reg, diags, tuple(pushed_rows)
    return logits, store, reg, diags


# ---------------------------------------------------------------------------
# Full-batch execution (baseline)
# ---------------------------------------------------------------------------

def full_forward(params, spec: GNNSpec, x: jnp.ndarray,
                 edges: Tuple[jnp.ndarray, jnp.ndarray], edge_w: jnp.ndarray,
                 num_nodes: int) -> jnp.ndarray:
    h = _pre(params, spec, x)
    ctx = {"h0": h}
    for ell in range(spec.num_layers):
        dummy = jnp.zeros((1, h.shape[-1]), h.dtype)
        x_all = jnp.concatenate([h, dummy], axis=0)
        h = _prop(params, spec, ell, x_all, edges, edge_w, num_nodes, ctx)
    return _post(params, spec, h)
