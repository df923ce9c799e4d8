"""Distributed GAS: partition-parallel training under `shard_map`.

The paper names "the fusion of GAS into a distributed training algorithm"
as future work (§7); this module implements it JAX-natively:

 - P ranks on the mesh's `data` axis; METIS-like cluster r lives on rank r.
   Nodes are re-indexed into a padded id space (new_id = rank*rows + slot)
   so every rank owns a contiguous, equally-sized row block — the paper's
   "contiguous memory transfers" taken to its distributed conclusion.
 - Histories are row-sharded: rank r stores H̄[rank block]. Pushes are
   always LOCAL (a rank only updates embeddings of its own cluster).
 - Pulls need remote rows: a static halo exchange — (P-1) rounds of
   `lax.ppermute`, each round sending exactly the rows the peer statically
   needs. XLA schedules these collectives alongside layer compute (the
   distributed analogue of PyGAS's concurrent CUDA-stream transfers).
   Quantized stores exchange RAW int8 rows + per-row scales and
   dequantize at the receiver — no f32 halo on the wire, and pushes
   re-quantize locally (`history.quantize_rows`).
 - One superstep = every rank processes its cluster concurrently; the loss
   is `psum`-averaged and grads flow through `shard_map` AD. Halo rows are
   one superstep stale — the "one-shot" regime of Cong et al. (2020),
   error-bounded by Theorem 2.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Dict, List, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.data.graphs import Graph
from . import gas as G
from . import history as H
from .batch import GASBatch


@dataclass
class DistStructs:
    """Static distributed plan. The per-rank local graph is the SAME typed
    structure the single-host executor uses — a `GASBatch` stacked over
    the rank axis (batch r == rank r's cluster: `batch_mask` is the
    node-slot validity mask, `edge_*` the local padded COO, `halo_*` the
    remote rows this rank pulls) — so model code consumes one batch type
    on both paths. Only the halo-exchange routing tables (`send_idx` /
    `send_mask` / `recv_pos`) are dist-specific."""
    num_ranks: int
    rows: int                      # row slots per rank
    sizes: np.ndarray              # [P] real nodes per rank
    old_of_new: np.ndarray         # [P*rows] padded new id -> old id (or -1)
    new_of_old: np.ndarray         # [N] old id -> padded new id
    max_halo: int
    max_edges: int
    batch: GASBatch                # stacked over ranks (numpy leaves)
    send_idx: np.ndarray           # [P, P, C] my local slots to send to peer q
    send_mask: np.ndarray          # [P, P, C]
    recv_pos: np.ndarray           # [P, P, C] halo slots for rows from peer q

    def device_batch(self) -> GASBatch:
        return self.batch.device()

    def exchange_arrays(self) -> Dict[str, jnp.ndarray]:
        return {k: jnp.asarray(getattr(self, k)) for k in
                ("send_idx", "send_mask", "recv_pos")}

    def init_store(self, dims: List[int], dtype=jnp.float32,
                   history_dtype: str = None) -> H.HistoryStore:
        """Row-sharded histories: [P*rows, d] per hidden layer. The dist
        path pulls via collective halo exchange (not the kernel gather),
        so the store is bound to the jnp backend; `history_dtype`
        resolves arg > $REPRO_HISTORY_DTYPE > "f32" like the single-host
        store, and int8 stores carry per-row scale shards that
        `halo_exchange` ppermutes alongside the raw rows (the exchange
        never materializes an f32 halo on the wire). vq stores are not
        supported on the dist path (the wire protocol exchanges raw
        rows + scales only; broadcasting per-layer codebooks across
        ranks is future work) and raise here. Tables stay
        device-resident — the host-spill path (`storage="host"`) is a
        single-host feature."""
        resolved = H.resolve_history_dtype(history_dtype)
        if H.get_codec(resolved).vq:
            raise NotImplementedError(
                "dist_gas does not support history_dtype='vq': the halo "
                "exchange wire protocol carries raw rows + per-row "
                "scales, not codebooks — use f32/bf16/int8 for sharded "
                "runs")
        n = self.num_ranks * self.rows
        return H.HistoryStore.create(
            n, dims, dtype=dtype, backend="jnp",
            history_dtype=resolved, storage="device")


def build_dist_structs(graph: Graph, part: np.ndarray) -> DistStructs:
    N = graph.num_nodes
    P_ = int(part.max()) + 1
    sizes = np.bincount(part, minlength=P_)
    rows = int(sizes.max())

    new_of_old = np.empty(N, np.int64)
    old_of_new = np.full(P_ * rows, -1, np.int64)
    for r in range(P_):
        mine = np.flatnonzero(part == r)
        new_of_old[mine] = r * rows + np.arange(len(mine))
        old_of_new[r * rows: r * rows + len(mine)] = mine

    dst, src, w = G.gcn_edge_weights(graph)
    dst_n, src_n = new_of_old[dst], new_of_old[src]
    owner_d = dst_n // rows

    halos: List[np.ndarray] = []
    edges = []
    for r in range(P_):
        sel = owner_d == r
        d_r, s_r, w_r = dst_n[sel], src_n[sel], w[sel]
        remote = s_r[(s_r // rows) != r]
        halo = np.unique(remote)
        halos.append(halo)
        edges.append((d_r, s_r, w_r))
    max_h = max(max((len(h) for h in halos), default=1), 1)
    max_e = max(len(e[0]) for e in edges)

    node_mask = np.arange(rows)[None, :] < sizes[:, None]
    ed = np.full((P_, max_e), rows, np.int32)              # trash row
    es = np.full((P_, max_e), rows + max_h, np.int32)      # dummy zero row
    ew = np.zeros((P_, max_e), np.float32)
    hmask = np.zeros((P_, max_h), bool)

    C = 1
    plans = []
    for r in range(P_):
        halo = halos[r]
        hmask[r, :len(halo)] = True
        lookup = np.full(P_ * rows + 1, rows + max_h, np.int64)
        lookup[r * rows: (r + 1) * rows] = np.arange(rows)
        lookup[halo] = rows + np.arange(len(halo))
        d_r, s_r, w_r = edges[r]
        ed[r, :len(d_r)] = (d_r - r * rows)
        es[r, :len(s_r)] = lookup[s_r]
        ew[r, :len(w_r)] = w_r
        plan = []
        for q in range(P_):
            sel = np.flatnonzero((halo // rows) == q)
            plan.append((sel, halo[sel] - q * rows))
            if q != r:
                C = max(C, len(sel))
        plans.append(plan)

    send_idx = np.zeros((P_, P_, C), np.int32)
    send_mask = np.zeros((P_, P_, C), bool)
    recv_pos = np.zeros((P_, P_, C), np.int32)
    for r in range(P_):
        for q in range(P_):
            if q == r:
                continue
            slots, qrows = plans[r][q]
            send_idx[q, r, :len(qrows)] = qrows
            send_mask[q, r, :len(qrows)] = True
            recv_pos[r, q, :len(slots)] = slots

    bnode = np.where(node_mask,
                     np.arange(rows, dtype=np.int64)[None, :]
                     + rows * np.arange(P_, dtype=np.int64)[:, None],
                     P_ * rows).astype(np.int32)
    hnode = np.full((P_, max_h), P_ * rows, np.int32)
    for r in range(P_):
        hnode[r, :len(halos[r])] = halos[r]
    batch = GASBatch(bnode, node_mask, hnode, hmask, ed, es, ew,
                     num_batches=P_, max_b=rows, max_h=max_h, max_e=max_e)
    return DistStructs(num_ranks=P_, rows=rows, sizes=sizes,
                       old_of_new=old_of_new, new_of_old=new_of_old,
                       max_halo=max_h, max_edges=max_e, batch=batch,
                       send_idx=send_idx, send_mask=send_mask,
                       recv_pos=recv_pos)


def permute_node_array(structs: DistStructs, arr: np.ndarray,
                       fill=0) -> np.ndarray:
    """old-id array [N, ...] -> padded new-id layout [P*rows, ...]."""
    out = np.full((structs.num_ranks * structs.rows,) + arr.shape[1:], fill,
                  arr.dtype)
    valid = structs.old_of_new >= 0
    out[valid] = arr[structs.old_of_new[valid]]
    return out


def halo_exchange(table_loc: jnp.ndarray, plan: Dict[str, jnp.ndarray],
                  max_halo: int, axis: str = "data",
                  scales_loc: jnp.ndarray = None):
    """Inside shard_map: [rows, d] local history shard -> [max_halo, d]
    halo rows pulled from their owners via (P-1) static ppermute rounds.

    Rows travel in RAW storage precision: an int8 shard ppermutes int8
    rows, and its per-row scale shard (`scales_loc`, [rows] f32) rides
    along as a second ppermute per round, so only int8 bytes + one f32
    scalar per row cross the interconnect — never a dequantized f32
    halo. With `scales_loc` the return is the `(halo_rows, halo_scales)`
    pair; the caller dequantizes at the receiver
    (`rows.astype(f32) * scales[:, None]`), which is bitwise the
    single-host `dequantize_rows` of the same table rows."""
    # static rank count (jax.lax.axis_size is jax >= 0.5; the per-peer
    # send table is [P, C], so its leading dim is the portable source)
    P_ = plan["send_idx"].shape[0]
    me = jax.lax.axis_index(axis)
    halo = jnp.zeros((max_halo, table_loc.shape[-1]), table_loc.dtype)
    hscl = (None if scales_loc is None
            else jnp.zeros((max_halo,), scales_loc.dtype))
    for shift in range(1, P_):
        to = (me + shift) % P_
        frm = (me - shift) % P_
        perm = [(r, (r + shift) % P_) for r in range(P_)]
        payload = jnp.take(plan["send_idx"], to, axis=0)        # [C]
        mask = jnp.take(plan["send_mask"], to, axis=0)
        # mask via where, not multiply: keeps int8 rows int8 on the wire
        rows = jnp.where(mask[:, None],
                         jnp.take(table_loc, payload, axis=0), 0)
        got = jax.lax.ppermute(rows, axis, perm=perm)
        pos = jnp.take(plan["recv_pos"], frm, axis=0)
        halo = halo.at[pos].add(got)
        if scales_loc is not None:
            srows = jnp.where(mask, jnp.take(scales_loc, payload), 0)
            hscl = hscl.at[pos].add(
                jax.lax.ppermute(srows, axis, perm=perm))
    return halo if scales_loc is None else (halo, hscl)


def make_dist_loss_fn(spec, structs: DistStructs, mesh,
                      axis: str = "data") -> Callable:
    """Builds loss(params, store, x_pad, y_pad, mask_pad, batch, exchange)
    where `store` is a `core.history.HistoryStore` (row-sharded tables),
    `batch` the rank-stacked `GASBatch` (`structs.device_batch()`) and
    `exchange` the ppermute routing dict (`structs.exchange_arrays()`);
    everything node-indexed is sharded over `axis` and params are
    replicated. Returns (loss, (new_store, acc, logits)) — the same
    typed history/batch surface as the single-host runtime."""
    from repro.gnn.model import _post, _pre, _prop

    rows, max_h = structs.rows, structs.max_halo
    num_layers = spec.num_layers

    def make_shard_body(quantized: bool):
        def shard_body(params, tables, scales, x_loc, y_loc, m_loc, batch,
                       plan):
            # batch/plan leaves arrive with a leading local rank axis of
            # size 1
            batch = jax.tree_util.tree_map(lambda a: a[0], batch)
            plan = jax.tree_util.tree_map(lambda a: a[0], plan)
            node_mask = batch.batch_mask
            edges = (batch.edge_dst.astype(jnp.int32),
                     batch.edge_src.astype(jnp.int32))
            edge_w = batch.edge_w

            hb = _pre(params, spec, x_loc) * node_mask[:, None]
            # exact layer-0 halo: exchange *input features* transformed by
            # pre (per-node, exact — no staleness at layer 0, per Thm. 2)
            hh0 = halo_exchange(hb, plan, max_h, axis)
            hh0 = hh0 * batch.halo_mask[:, None]
            ctx = {"h0": hb}

            new_tables, new_scales = [], []
            x_cur = hb
            for ell in range(num_layers):
                if ell == 0:
                    halo_rows = hh0
                else:
                    if quantized:
                        # raw int8 rows + scales on the wire; dequantize
                        # at the receiver (bitwise `dequantize_rows`)
                        hraw, hscl = halo_exchange(
                            tables[ell - 1], plan, max_h, axis,
                            scales_loc=scales[ell - 1])
                        halo_rows = hraw.astype(jnp.float32) * hscl[:, None]
                    else:
                        halo_rows = halo_exchange(tables[ell - 1], plan,
                                                  max_h, axis)
                        halo_rows = halo_rows.astype(jnp.float32)
                    halo_rows = halo_rows * batch.halo_mask[:, None]
                dummy = jnp.zeros((1, x_cur.shape[-1]), x_cur.dtype)
                x_all = jnp.concatenate([x_cur, halo_rows, dummy], axis=0)
                x_next = _prop(params, spec, ell, x_all, edges, edge_w,
                               rows, ctx)
                if ell < num_layers - 1:
                    fresh = (jax.lax.stop_gradient(x_next)
                             * node_mask[:, None])
                    if quantized:
                        q, s = H.quantize_rows(fresh)
                        new_tables.append(q)
                        new_scales.append(s)
                    else:
                        new_tables.append(
                            fresh.astype(tables[ell].dtype))
                x_cur = x_next

            logits = _post(params, spec, x_cur)
            m = m_loc & node_mask
            logz = jax.scipy.special.logsumexp(logits, axis=-1)
            gold = jnp.take_along_axis(logits, y_loc[:, None], axis=-1)[:, 0]
            ce_sum = jnp.sum((logz - gold) * m)
            cnt = jnp.sum(m)
            correct = jnp.sum((jnp.argmax(logits, -1) == y_loc) & m)
            ce_sum, cnt, correct = (jax.lax.psum(v, axis)
                                    for v in (ce_sum, cnt, correct))
            loss = ce_sum / jnp.maximum(cnt, 1)
            acc = correct / jnp.maximum(cnt, 1)
            return loss, acc, new_tables, new_scales, logits

        return shard_body

    batch_specs = jax.tree_util.tree_map(lambda _: P(axis), structs.batch)
    plan_specs = {k: P(axis) for k in ("send_idx", "send_mask", "recv_pos")}
    smapped_cache = {}

    def get_smapped(quantized: bool):
        # two traced variants (the scales operand list is [] for
        # non-int8 stores, so the pytree structure is static per flag)
        if quantized not in smapped_cache:
            nscl = (num_layers - 1) if quantized else 0
            smapped_cache[quantized] = jax.shard_map(
                make_shard_body(quantized), mesh=mesh,
                in_specs=(P(), [P(axis)] * (num_layers - 1),
                          [P(axis)] * nscl, P(axis), P(axis),
                          P(axis), batch_specs, plan_specs),
                out_specs=(P(), P(), [P(axis)] * (num_layers - 1),
                           [P(axis)] * nscl, P(axis)),
                check_vma=False)
        return smapped_cache[quantized]

    def loss_fn(params, store: Union[H.HistoryStore, List], x_pad, y_pad,
                m_pad, batch: GASBatch, exchange: Dict):
        legacy = not isinstance(store, H.HistoryStore)
        if not legacy and H.get_codec(store.history_dtype).vq:
            raise NotImplementedError(
                "dist_gas does not support history_dtype='vq' (no "
                "codebook exchange on the wire) — use f32/bf16/int8")
        tables = list(store) if legacy else list(store.tables)
        quantized = (not legacy) and store.scales is not None
        scales = list(store.scales) if quantized else []
        loss, acc, new_tables, new_scales, logits = get_smapped(quantized)(
            params, tables, scales, x_pad, y_pad, m_pad, batch, exchange)
        if legacy:
            return loss, (new_tables, acc, logits)
        # every rank pushes all of its rows each superstep, so the whole
        # clock resets: histories are exactly one superstep stale
        new_store = H.HistoryStore(
            tables=tuple(new_tables),
            age=jnp.zeros_like(store.age),
            scales=tuple(new_scales) if quantized else None,
            backend=store.backend, history_dtype=store.history_dtype,
            storage=store.storage)
        return loss, (new_store, acc, logits)

    return loss_fn
