"""Sharded GAS: the runtime's epoch on R chips under `shard_map`.

`GASConfig(ranks=R)` puts this module on the normal path
(`runtime.build_plan` -> `init_state` -> `train_epoch`):

 - The P parts go to ranks P/R each, contiguous by part id: rank r holds
   parts r*S .. r*S+S-1, S = P/R, and an epoch is S supersteps.
 - Each rank owns a contiguous block of history rows: node n of rank r is
   row `r * (rows + 1) + slot` of every table, `rows` the largest rank's
   node count, and each shard ends in its own sentinel row (the kernel
   push scatters padding into it). Tables, their int8 scales and the age
   clock are row-sharded over the mesh; parameters, Adam's state and the
   features are replicated.
 - The block stacks are the one-chip `gas.build_batches` stacks of all P
   parts, regrouped [R, S, ...]: every rank runs the same
   `gnn.model.gas_batch_forward` as one chip, so the fused
   `gather_spmm`, the transposed `bcsr_spmm` backward and the
   `scatter_rows` push run unchanged inside `shard_map`.
 - In superstep s every rank takes its own next part, in the order the
   epoch's permutation of all P parts lists them (`rank_orders`). Before
   the step each rank builds each hidden layer's halo view: its own rows
   through the `gather_rows` kernel, every other rank's rows through one
   `ppermute` per peer, in storage precision (int8 rows travel with
   their f32 scales). The view feeds `gas_batch_forward(pulled=...)`, so
   every read in a superstep sees the tables as they stood at its start.
 - The loss is psum(cross-entropy) / psum(count) over the superstep's R
   parts; gradients are summed over ranks and every rank takes the same
   AdamW step. The pushes then land, each rank into its own shard.

Routing (`DistBatches`, built once on the host): for part j of rank r,
`send_idx[q, r, j]` lists the rows of q's shard that r's halo needs, in
halo order, and `src_pos[r, j]` says where each halo slot's row lies in
the exchange buffer [own rows; the rows from rank r-1; from r-2; ...;
one zero row].
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.kernels import ops
from repro.utils import spans
from . import history as H
from .batch import GASBatch

AXIS = "ranks"


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=["batch", "slots", "loc_idx", "send_idx", "src_pos"],
    meta_fields=["ranks", "rows"])
@dataclass(frozen=True)
class DistBatches:
    """Every part's batch and exchange routing, with a leading rank axis
    on every leaf (the axis the mesh shards)."""
    batch: GASBatch      # [R, S, ...]: part r*S + j at [r, j], global ids
    slots: Any           # [R, S, max_b] shard row of each batch node
    #                      (padding: `rows`, the shard's sentinel)
    loc_idx: Any         # [R, S, L] shard rows of the halo rows the rank
    #                      owns itself (padding 0)
    send_idx: Any        # [R, R, S, C] [q, r, j]: rows of q's shard that
    #                      r's part j needs from q (padding 0)
    src_pos: Any         # [R, S, max_h] row of each halo slot in the
    #                      exchange buffer (padding: its zero row)
    ranks: int = 1
    rows: int = 0        # node rows per shard, sentinel excluded


def check_config(config, history_dtype: str, history_storage: str) -> None:
    """Refuses what the sharded path has no form of."""
    R = config.ranks
    if config.num_parts % R:
        raise ValueError(f"num_parts ({config.num_parts}) must be a "
                         f"multiple of ranks ({R})")
    if H.get_codec(history_dtype).vq:
        raise NotImplementedError(
            "ranks > 1 does not support history_dtype='vq': the halo "
            "exchange carries rows and per-row scales, not codebooks")
    unsupported = {
        "clusters_per_batch > 1": config.clusters_per_batch > 1,
        "prefetch_depth > 0": config.prefetch_depth > 0,
        "history_storage='host'": history_storage != "device",
        "halo_age_decay": bool(config.halo_age_decay),
    }
    bad = [k for k, v in unsupported.items() if v]
    if bad:
        raise NotImplementedError(f"ranks > 1 does not support {bad}")


def make_mesh(ranks: int, devices=None) -> Mesh:
    """A one-axis mesh of the first `ranks` devices (or of `devices`)."""
    devs = list(jax.devices() if devices is None else devices)
    if len(devs) < ranks:
        raise ValueError(f"ranks={ranks} needs {ranks} devices, "
                         f"found {len(devs)}")
    return Mesh(np.array(devs[:ranks]), (AXIS,))


def build_dist(part: np.ndarray, batches: GASBatch, ranks: int
               ) -> Tuple[DistBatches, np.ndarray, Dict[str, int]]:
    """Host routing for `batches` (`gas.build_batches` of all parts):
    (DistBatches with numpy leaves, each node's row in the sharded tables
    [N], per-epoch row counts of one hidden layer: `remote` halo rows
    that cross chips and `local` ones read from the rank's own shard).
    Timed as span `gas/dist/plan`, the routing as `gas/dist/plan/routing`.
    """
    with spans.span("gas/dist/plan"):
        Pn, N = batches.num_batches, len(part)
        S = Pn // ranks
        # a rank's nodes in part order, then node id
        owner = (np.asarray(part) // S).astype(np.int64)
        order = np.lexsort((np.arange(N), part))
        counts = np.bincount(owner, minlength=ranks)
        starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
        slot = np.empty(N, np.int64)
        slot[order] = np.arange(N) - starts[owner[order]]
        rows = int(counts.max())
        node_rows = owner * (rows + 1) + slot
        bn, bm = batches.batch_nodes, batches.batch_mask
        slots = np.where(bm, slot[np.minimum(bn, N - 1)], rows)
        with spans.span("gas/dist/plan/routing"):
            hn, hm = batches.halo_nodes, batches.halo_mask
            own = [[None] * ranks for _ in range(Pn)]  # [p][q]: halo slots
            for p in range(Pn):
                valid = np.flatnonzero(hm[p])
                q_of = owner[hn[p, valid]]
                for q in range(ranks):
                    own[p][q] = valid[q_of == q]
            L = max(1, max(len(own[p][p // S]) for p in range(Pn)))
            C = max([1] + [len(own[p][q]) for p in range(Pn)
                           for q in range(ranks) if q != p // S])
            max_h = hn.shape[1]
            zero = L + (ranks - 1) * C
            loc_idx = np.zeros((Pn, L), np.int32)
            send_idx = np.zeros((ranks, ranks, S, C), np.int32)
            src_pos = np.full((Pn, max_h), zero, np.int32)
            n_remote = n_local = 0
            for p in range(Pn):
                r, j = divmod(p, S)
                for q in range(ranks):
                    hs = own[p][q]
                    rows_q = slot[hn[p, hs]]
                    if q == r:
                        loc_idx[p, :len(hs)] = rows_q
                        src_pos[p, hs] = np.arange(len(hs))
                        n_local += len(hs)
                    else:
                        k = (r - q) % ranks
                        send_idx[q, r, j, :len(hs)] = rows_q
                        src_pos[p, hs] = L + (k - 1) * C + np.arange(len(hs))
                        n_remote += len(hs)

    def ranked(a):
        return a.reshape((ranks, S) + a.shape[1:])

    stacked = jax.tree_util.tree_map(ranked, batches)
    dist = DistBatches(batch=stacked, slots=ranked(slots.astype(np.int32)),
                       loc_idx=ranked(loc_idx), send_idx=send_idx,
                       src_pos=ranked(src_pos), ranks=ranks, rows=rows)
    return dist, node_rows, {"remote": n_remote, "local": n_local}


def count_exchange(counts: Dict[str, int], dims, history_dtype: str,
                   supersteps: int) -> None:
    """The plan's counters: halo rows an epoch over every hidden layer,
    the bytes of the remote ones as stored (int8 scales included)."""
    codec = H.get_codec(history_dtype)
    item = np.dtype(codec.storage).itemsize
    row_bytes = [d * item + (4 if codec.scaled else 0) for d in dims]
    spans.count("gas/dist/exchange_rows", counts["remote"] * len(dims))
    spans.count("gas/dist/exchange_bytes", counts["remote"] * sum(row_bytes))
    spans.count("gas/dist/local_halo_rows", counts["local"] * len(dims))
    spans.count("gas/dist/supersteps", supersteps)


def rank_orders(order, ranks: int):
    """[R, S] part of each rank (index within the rank) in each superstep:
    a rank's parts in the order the epoch's permutation of all parts
    lists them. Works on numpy and jax arrays alike."""
    xp = jnp if isinstance(order, jax.Array) else np
    S = order.shape[0] // ranks
    idx = xp.argsort(order // S, stable=True)
    return (order[idx] % S).reshape(ranks, S)


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def sharded(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P(AXIS))


def init_store(dims, ranks: int, rows: int, mesh: Mesh, backend: str,
               history_dtype: str) -> H.HistoryStore:
    """Zeroed history tables [R * (rows + 1), d], row-sharded over
    `mesh`: each shard holds one rank's rows and its own sentinel."""
    store = H.HistoryStore.create(ranks * (rows + 1), dims, backend=backend,
                                  history_dtype=history_dtype,
                                  storage="device")
    return jax.device_put(store, sharded(mesh))


def _rows(table, idx, backend: str):
    """Raw rows table[idx] in storage precision: the `gather_rows` kernel
    for float tables on the kernel backends."""
    if backend == "jnp" or table.dtype != jnp.float32:
        return jnp.take(table, idx, axis=0)
    return ops.pull_rows(table, idx, backend=backend)


def halo_view(table, scales, dist: DistBatches, me, per_rank, s, j,
              backend: str):
    """Inside `shard_map`: the halo rows of this rank's part `j` from the
    layer's table as it stands, in storage precision: (rows [max_h, d],
    scales [max_h] or None), what `HistoryStore.with_pulled` takes. Own
    rows are gathered from the local shard; each other rank sends the
    rows it owns, found by the receiver's part in this superstep."""
    R = dist.ranks
    bufs = [_rows(table, dist.loc_idx[j], backend)]
    sbufs = [] if scales is None else [jnp.take(scales, dist.loc_idx[j])]
    for k in range(1, R):
        to = (me + k) % R
        idx = dist.send_idx[to, per_rank[to, s]]
        perm = [(q, (q + k) % R) for q in range(R)]
        bufs.append(jax.lax.ppermute(_rows(table, idx, backend), AXIS, perm))
        if scales is not None:
            sbufs.append(jax.lax.ppermute(jnp.take(scales, idx), AXIS, perm))
    bufs.append(jnp.zeros((1, table.shape[1]), table.dtype))
    pos = dist.src_pos[j]
    view = jnp.take(jnp.concatenate(bufs), pos, axis=0)
    if scales is None:
        return view, None
    sbufs.append(jnp.ones((1,), scales.dtype))
    return view, jnp.take(jnp.concatenate(sbufs), pos)


def make_epoch_fn(spec, config, backend: str, mesh: Mesh):
    """The un-jitted sharded epoch `(state, dist, order, x, y, train_mask)
    -> (state, per-superstep metrics)`: one `shard_map` around one
    `lax.scan` of S supersteps. `order` is the epoch's permutation of
    all parts (as the one-chip epoch takes it); `dist` the device
    `DistBatches`; x, y and train_mask are in node order, replicated."""
    from repro.gnn.model import gas_batch_forward
    from repro.train.optimizer import adamw_update, clip_by_global_norm
    from .runtime import GASState

    R = mesh.shape[AXIS]

    def body(state, dist, per_rank, x, y, train_mask):
        dist = jax.tree_util.tree_map(lambda a: a[0], dist)
        me = jax.lax.axis_index(AXIS)
        S = per_rank.shape[1]

        def superstep(st, s):
            j = per_rank[me, s]
            batch = jax.tree_util.tree_map(lambda a: a[j], dist.batch)
            slots = dist.slots[j]
            bmask = batch.batch_mask
            max_b, max_h = bmask.shape[0], batch.halo_mask.shape[0]
            store = st.histories
            pulled = tuple(
                halo_view(store.tables[ell], store.layer_scales(ell), dist,
                          me, per_rank, s, j, backend)
                for ell in range(store.num_layers))
            # layer 0 reads the replicated features of the batch and halo
            ids = jnp.concatenate([batch.batch_nodes, batch.halo_nodes])
            x_view = jnp.take(x, ids, axis=0, mode="clip")
            local = batch.replace(
                batch_nodes=jnp.arange(max_b, dtype=jnp.int32),
                halo_nodes=max_b + jnp.arange(max_h, dtype=jnp.int32))
            labels = jnp.take(y, batch.batch_nodes, mode="clip")
            m = jnp.take(train_mask, batch.batch_nodes, mode="clip") & bmask
            count = jax.lax.psum(jnp.sum(m), AXIS)
            denom = jnp.maximum(count, 1)
            rng, sub = jax.random.split(st.rng)

            def loss_fn(p):
                logits, _, reg, diags, pushed = gas_batch_forward(
                    p, spec, x_view, local, store,
                    use_history=config.use_history, rng=sub,
                    backend=backend, fuse_halo=config.fuse_halo,
                    pulled=pulled, return_pushed=True, apply_pushes=False)
                logz = jax.scipy.special.logsumexp(logits, axis=-1)
                gold = jnp.take_along_axis(logits, labels[:, None],
                                           axis=-1)[:, 0]
                ce = jnp.sum((logz - gold) * m)
                ok = jnp.sum((jnp.argmax(logits, -1) == labels) & m)
                loss = ce / denom + spec.reg_weight * reg / R
                return loss, (pushed, ce, ok, reg, diags["hist_quant_err"])

            (loss, (pushed, ce, ok, reg, qerr)), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(st.params)
            grads = jax.lax.psum(grads, AXIS)
            grads, _gn = clip_by_global_norm(grads, config.grad_clip)
            params, opt_state = adamw_update(
                grads, st.opt_state, st.params, lr=config.lr, b1=0.9,
                b2=0.999, weight_decay=config.weight_decay)
            for ell, rows in enumerate(pushed):
                store = store.push(ell, slots, rows, bmask)
            store = store.tick(slots, bmask)
            metrics = {"loss": jax.lax.psum(loss, AXIS),
                       "ce": jax.lax.psum(ce, AXIS) / denom,
                       "acc": jax.lax.psum(ok, AXIS) / denom,
                       "reg": jax.lax.pmean(reg, AXIS),
                       "hist_quant_err": jax.lax.pmean(qerr, AXIS)}
            return GASState(params=params, opt_state=opt_state,
                            histories=store, rng=rng), metrics

        return jax.lax.scan(superstep, state, jnp.arange(S))

    state_spec = GASState(params=P(), opt_state=P(), histories=P(AXIS),
                          rng=P())
    mapped = jax.shard_map(
        body, mesh=mesh,
        in_specs=(state_spec, P(AXIS), P(), P(), P(), P()),
        out_specs=(state_spec, P()), check_vma=False)

    def epoch_fn(state, dist, order, x, y, train_mask):
        return mapped(state, dist, rank_orders(order, R), x, y, train_mask)

    return epoch_fn
