"""GAS mini-batch construction (paper Algorithm 1's static padded shapes).

Setup (numpy, once): partition nodes into B clusters; for each cluster build
the pruned computation graph — in-batch nodes + 1-hop halo + the COO edges
into in-batch destinations — padded to the max over clusters so one jitted
step serves every batch. The same pass tiles each cluster's local adjacency
into block-CSR form (`blk_vals` [B,R,K,bn,bn] / `blk_cols` [B,R,K], K
padded to the max over batches) so the kernel backends can aggregate with
dense MXU block matmuls instead of gather/segment ops.

This module only builds batches: `build_batches` (whole partitions),
`patch_batches` (after a graph delta), `subgraph_batch` (an arbitrary
node set — serving and the dynamic re-push), the GCN edge weights and the
padding bounds of regrouped epochs. The forward that consumes them — per
layer ℓ, `x_all = [in-batch rows ; halo rows pulled from H̄^{ℓ-1} ; 0]`,
then a push of the new in-batch rows to H̄^{ℓ} — is
`gnn.model.gas_batch_forward`.
"""
from __future__ import annotations

from typing import Optional, Tuple

import numpy as np

from repro.data.graphs import Graph
from repro.kernels import ops
from repro.utils import spans
from .batch import BlockStructure, GASBatch


def gcn_edge_weights(graph: Graph, add_self_loops: bool = True
                     ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Global COO with symmetric GCN normalization (self-loops included)."""
    dst, src = graph.coo()
    if add_self_loops:
        loops = np.arange(graph.num_nodes, dtype=np.int32)
        dst = np.concatenate([dst, loops])
        src = np.concatenate([src, loops])
    deg = np.bincount(dst, minlength=graph.num_nodes).astype(np.float64)
    w = 1.0 / np.sqrt(deg[dst] * deg[src])
    return dst.astype(np.int32), src.astype(np.int32), w.astype(np.float32)


def group_partition(part: np.ndarray, clusters_per_batch: int,
                    rng: np.ndarray | None = None) -> np.ndarray:
    """Relabel clusters into batches of `clusters_per_batch` random clusters
    (PyGAS dataloader semantics: mixing clusters per batch de-correlates
    label-pure clusters, e.g. SBM communities)."""
    num_clusters = int(part.max()) + 1
    order = (np.random.default_rng(0) if rng is None else rng
             ).permutation(num_clusters)
    group_of = np.empty(num_clusters, np.int32)
    for i, c in enumerate(order):
        group_of[c] = i // clusters_per_batch
    return group_of[part]


def padding_bounds(graph: Graph, part: np.ndarray, clusters_per_batch: int,
                   add_self_loops: bool = True):
    """Worst-case (max_b, max_h, max_e) over any grouping of k clusters:
    sums of the k largest per-cluster sizes (halo/edges are subadditive)."""
    singles = build_batches(graph, part, add_self_loops, build_blocks=False)
    k = clusters_per_batch
    b_sizes = np.sort(singles.batch_mask.sum(1))[::-1]
    h_sizes = np.sort(singles.halo_mask.sum(1))[::-1]
    e_sizes = np.sort((singles.edge_w > 0).sum(1))[::-1]
    return (int(b_sizes[:k].sum()), int(max(h_sizes[:k].sum(), 1)),
            int(e_sizes[:k].sum()))


def build_batches(graph: Graph, part: np.ndarray,
                  add_self_loops: bool = True,
                  pad_to: tuple | None = None,
                  build_blocks: bool | None = None,
                  bn: int = 128,
                  pad_k: int | None = None,
                  pad_k_t: int | None = None,
                  unit_weights: bool = False) -> GASBatch:
    """Builds the stacked `GASBatch` for one partition (numpy leaves;
    `.device()` / `.device_batch(b)` move it). The BCSR families describe
    each batch's local [max_b, max_b+max_h+1] adjacency (GCN-normalized
    weights baked in) tiled into bn x bn blocks; `transposed` keeps the
    SpMM *backward* on the MXU. With `unit_weights=True` (GIN/GAT/PNA)
    the unit-weight (edge-multiplicity) families are built *instead of*
    the weighted ones — those ops never read the normalized values, and
    the value buffers are the dominant allocation — sharing the same
    column structure.

    Blocks default to backend-auto (`build_blocks=None`): they are
    built iff the resolved kernel backend (`ops.resolve_backend`) is a
    block-consuming one, since only kernel backends read them and the
    dense [B, R, K, bn, bn] buffers (x2 with the transposed structure)
    are the dominant host allocation — jnp-path callers should not pay
    for them. Pass True/False to force."""
    if build_blocks is None:
        build_blocks = ops.resolve_backend(None) != "jnp"
    with spans.span("gas/plan/coo"):
        N = graph.num_nodes
        B = int(part.max()) + 1
        dst, src, w = gcn_edge_weights(graph, add_self_loops)

        order = np.argsort(part[dst], kind="stable")
        dst_s, src_s, w_s = dst[order], src[order], w[order]
        edge_part = part[dst_s]
        bounds = np.searchsorted(edge_part, np.arange(B + 1))

        batches, halos, edges = [], [], []
        for b in range(B):
            nodes_b = np.flatnonzero(part == b).astype(np.int32)
            e0, e1 = bounds[b], bounds[b + 1]
            d_b, s_b, w_b = dst_s[e0:e1], src_s[e0:e1], w_s[e0:e1]
            halo = np.setdiff1d(s_b, nodes_b)
            # local index map: batch nodes -> [0, nb), halo -> [nb, nb+nh)
            batches.append(nodes_b)
            halos.append(halo.astype(np.int32))
            edges.append((d_b, s_b, w_b))

        max_b = max(len(x) for x in batches)
        max_h = max(max(len(x) for x in halos), 1)
        max_e = max(len(e[0]) for e in edges)
        if pad_to is not None:
            max_b = max(max_b, pad_to[0])
            max_h = max(max_h, pad_to[1])
            max_e = max(max_e, pad_to[2])

        bnode = np.full((B, max_b), N, np.int32)
        bmask = np.zeros((B, max_b), bool)
        hn = np.full((B, max_h), N, np.int32)
        hm = np.zeros((B, max_h), bool)
        ed = np.full((B, max_e), max_b, np.int32)          # trash row
        es = np.full((B, max_e), max_b + max_h, np.int32)  # dummy zero row
        ew = np.zeros((B, max_e), np.float32)

        for b in range(B):
            nodes_b, halo = batches[b], halos[b]
            d_b, s_b, w_b = edges[b]
            nb, nh, ne = len(nodes_b), len(halo), len(d_b)
            bnode[b, :nb] = nodes_b
            bmask[b, :nb] = True
            hn[b, :nh] = halo
            hm[b, :nh] = True
            # global -> local
            lookup = np.full(N + 1, max_b + max_h, np.int64)
            lookup[nodes_b] = np.arange(nb)
            lookup[halo] = max_b + np.arange(nh)
            ed[b, :ne] = lookup[d_b]      # always < nb (dst in batch)
            es[b, :ne] = lookup[s_b]
            ew[b, :ne] = w_b

    blk_vals = blk_cols = blk_vals_t = blk_cols_t = None
    ublk_vals = ublk_vals_t = None
    if build_blocks:
        # tile each batch's local [max_b, max_b+max_h+1] adjacency into
        # BCSR — forward AND transposed (backward-on-MXU) structures, plus
        # optional unit-weight value blocks (GIN). K/K_t padded to the max
        # over batches (pad_k/pad_k_t let regrouped epochs share one jit
        # trace — see runtime._regroup)
        with spans.span("gas/plan/emit"):
            per = [_emit_part_blocks(ed[b], es[b], ew[b], max_b, max_h, bn,
                                     unit_weights) for b in range(B)]
        with spans.span("gas/plan/stack"):
            R = per[0]["v"].shape[0]
            R_t = per[0]["vt"].shape[0]
            K = max(max(e["c"].shape[1] for e in per), pad_k or 1)
            K_t = max(max(e["ct"].shape[1] for e in per), pad_k_t or 1)
            vals = np.zeros((B, R, K, bn, bn), np.float32)
            blk_cols = np.zeros((B, R, K), np.int32)
            vals_t = np.zeros((B, R_t, K_t, bn, bn), np.float32)
            blk_cols_t = np.zeros((B, R_t, K_t), np.int32)
            for b, e in enumerate(per):
                vals[b, :, :e["v"].shape[1]] = e["v"]
                blk_cols[b, :, :e["c"].shape[1]] = e["c"]
                vals_t[b, :, :e["vt"].shape[1]] = e["vt"]
                blk_cols_t[b, :, :e["ct"].shape[1]] = e["ct"]
        # block fill, from the COO and the shapes: every valid edge slot
        # (ew > 0) is one nonzero entry of each of the two stacks
        spans.count("gas/plan/edge_entries", 2 * int((ew > 0).sum()))
        spans.count("gas/plan/block_entries", vals.size + vals_t.size)
        if unit_weights:
            ublk_vals, ublk_vals_t = vals, vals_t
        else:
            blk_vals, blk_vals_t = vals, vals_t
    fwd = tr = un = un_t = None
    if blk_vals is not None:
        fwd = BlockStructure(blk_vals, blk_cols)
        tr = BlockStructure(blk_vals_t, blk_cols_t)
    if ublk_vals is not None:
        un = BlockStructure(ublk_vals, blk_cols)
        un_t = BlockStructure(ublk_vals_t, blk_cols_t)
    return GASBatch(bnode, bmask, hn, hm, ed, es, ew,
                    forward=fwd, transposed=tr, unit=un, unit_transposed=un_t,
                    num_batches=B, max_b=max_b, max_h=max_h, max_e=max_e,
                    bn=bn)


# ---------------------------------------------------------------------------
# Incremental batch patching (evolving graphs — core/dynamic.py)
# ---------------------------------------------------------------------------

def _emit_part_blocks(ed_row: np.ndarray, es_row: np.ndarray,
                      ew_row: np.ndarray, max_b: int, max_h: int,
                      bn: int, unit_weights: bool) -> dict:
    """BCSR forward + transposed blocks for ONE batch's padded local COO
    row (shared by `build_batches` and `patch_batches` so a patched row
    cannot drift from a from-scratch one). Valid slots are `ew > 0` —
    GCN-normalized weights are strictly positive, padding is 0.
    With `unit_weights` (GIN/GAT/PNA) the values are the edge
    multiplicities instead: those ops never read the normalized weights,
    and the value buffers are the dominant host+device allocation."""
    valid = ew_row > 0
    d_b, s_b, w_b = ed_row[valid], es_row[valid], ew_row[valid]
    wv = np.ones_like(w_b) if unit_weights else w_b
    n_cols = max_b + max_h + 1
    v, c, _, _ = ops.build_bcsr_rect(d_b, s_b, wv, max_b, n_cols, bn=bn)
    vt, ct, _, _ = ops.build_bcsr_rect(s_b, d_b, wv, n_cols, max_b, bn=bn)
    return {"v": v, "c": c, "vt": vt, "ct": ct}


def _part_edges(graph: Graph, part: np.ndarray, b: int, deg: np.ndarray,
                add_self_loops: bool = True):
    """Reconstruct part `b`'s slice of the part-sorted global COO without
    materializing the global COO: the global order is [real edges
    (dst-major, CSR src order) ; self-loops (node order)] and the part
    sort is STABLE, so within a part it is exactly (real in-edges of the
    members, members ascending, CSR order per member) followed by (the
    members' self-loops, ascending). `deg` is the global float64 degree
    vector (self-loop included when `add_self_loops`), so the normalized
    weights are bitwise what `gcn_edge_weights` computes. Returns
    (nodes_b, halo, d_b, s_b, w_b) in global ids."""
    nodes_b = np.flatnonzero(part == b).astype(np.int32)
    indptr = graph.indptr.astype(np.int64)
    starts = indptr[nodes_b]
    lens = indptr[nodes_b + 1] - starts
    total = int(lens.sum())
    offs = np.concatenate([[0], np.cumsum(lens)[:-1]]).astype(np.int64)
    flat = np.repeat(starts - offs, lens) + np.arange(total)
    dst_r = np.repeat(nodes_b, lens)
    src_r = graph.indices[flat].astype(np.int32)
    if add_self_loops:
        d_b = np.concatenate([dst_r, nodes_b]).astype(np.int32)
        s_b = np.concatenate([src_r, nodes_b]).astype(np.int32)
    else:
        d_b, s_b = dst_r.astype(np.int32), src_r
    w_b = (1.0 / np.sqrt(deg[d_b] * deg[s_b])).astype(np.float32)
    halo = np.setdiff1d(s_b, nodes_b).astype(np.int32)
    return nodes_b, halo, d_b, s_b, w_b


def _fill_batch_row(bnode, bmask, hn, hm, ed, es, ew, b: int,
                    nodes_b, halo, d_b, s_b, w_b, N: int) -> None:
    """Overwrite batch row `b` of the padded arrays in place: reset the
    whole row to pad values (node N, trash row max_b, dummy zero row
    max_b + max_h, weight 0) then fill — the same layout
    `build_batches`'s fill loop produces."""
    max_b, max_h = bnode.shape[1], hn.shape[1]
    nb, nh, ne = len(nodes_b), len(halo), len(d_b)
    bnode[b] = N
    bnode[b, :nb] = nodes_b
    bmask[b] = False
    bmask[b, :nb] = True
    hn[b] = N
    hn[b, :nh] = halo
    hm[b] = False
    hm[b, :nh] = True
    lookup = np.full(N + 1, max_b + max_h, np.int64)
    lookup[nodes_b] = np.arange(nb)
    lookup[halo] = max_b + np.arange(nh)
    ed[b] = max_b
    ed[b, :ne] = lookup[d_b]
    es[b] = max_b + max_h
    es[b, :ne] = lookup[s_b]
    ew[b] = 0.0
    ew[b, :ne] = w_b


def patch_batches(graph: Graph, part: np.ndarray, old: GASBatch,
                  rebuild_parts, num_nodes_old: Optional[int] = None,
                  add_self_loops: bool = True) -> Optional[GASBatch]:
    """Patch a stacked host `GASBatch` after a graph delta: re-emit only
    the batches in `rebuild_parts` (index rows AND their BCSR block rows,
    whichever families `old` carries), copying every other batch's arrays
    verbatim. The result is bitwise what `build_batches(graph, part,
    pad_to=old pads, pad_k=K, pad_k_t=K_t, ...)` would build — pinned by
    tests/test_dynamic.py.

    Pads are a contract, not a preference: growing max_b/max_h would
    shift every *untouched* batch's local index space (edge_src offsets,
    trash/dummy rows), so any rebuilt part overflowing the old pads —
    or a changed part count — returns None and the caller cold-rebuilds
    (`core.dynamic` sizes pads with slack up front to make that rare).
    A grown node count only moves the pad *values* (node id N), which is
    fixed up here for the untouched rows. Block K/K_t may grow: padding
    slots are all-zero blocks at column 0, so zero-extending along K is
    exactly `build_batches`'s own padding."""
    N = graph.num_nodes
    if int(part.max()) + 1 != old.num_batches:
        return None
    B = old.num_batches
    max_b, max_h, max_e = old.max_b, old.max_h, old.max_e
    n_old = N if num_nodes_old is None else int(num_nodes_old)

    deg = np.diff(graph.indptr).astype(np.float64)
    if add_self_loops:
        deg = deg + 1.0

    rebuilt = {}
    for b in sorted({int(b) for b in np.asarray(rebuild_parts).ravel()}):
        nodes_b, halo, d_b, s_b, w_b = _part_edges(
            graph, part, b, deg, add_self_loops)
        if (len(nodes_b) > max_b or len(halo) > max_h
                or len(d_b) > max_e):
            return None
        rebuilt[b] = (nodes_b, halo, d_b, s_b, w_b)

    bnode = np.array(old.batch_nodes, np.int32)
    bmask = np.array(old.batch_mask, bool)
    hn = np.array(old.halo_nodes, np.int32)
    hm = np.array(old.halo_mask, bool)
    ed = np.array(old.edge_dst, np.int32)
    es = np.array(old.edge_src, np.int32)
    ew = np.array(old.edge_w, np.float32)
    if N != n_old:
        # pad slots are exactly the masked-off slots; repoint them at the
        # new sentinel row so untouched batches keep gathering zeros
        bnode[~bmask] = N
        hn[~hm] = N
    for b, (nodes_b, halo, d_b, s_b, w_b) in rebuilt.items():
        _fill_batch_row(bnode, bmask, hn, hm, ed, es, ew, b,
                        nodes_b, halo, d_b, s_b, w_b, N)

    fwd = tr = un = un_t = None
    unit_weights = old.unit is not None
    bs = old.unit if unit_weights else old.forward
    bs_t = old.unit_transposed if unit_weights else old.transposed
    if bs is not None:
        bn = old.bn
        per = {b: _emit_part_blocks(ed[b], es[b], ew[b], max_b, max_h,
                                    bn, unit_weights) for b in rebuilt}
        vals = np.array(bs.vals, np.float32)
        cols = np.array(bs.cols, np.int32)
        vals_t = np.array(bs_t.vals, np.float32)
        cols_t = np.array(bs_t.cols, np.int32)
        K = max([cols.shape[2]] + [e["c"].shape[1] for e in per.values()])
        K_t = max([cols_t.shape[2]]
                  + [e["ct"].shape[1] for e in per.values()])
        if K > cols.shape[2]:
            grow = K - cols.shape[2]
            vals = np.concatenate(
                [vals, np.zeros(vals.shape[:2] + (grow, bn, bn),
                                vals.dtype)], axis=2)
            cols = np.concatenate(
                [cols, np.zeros(cols.shape[:2] + (grow,), cols.dtype)],
                axis=2)
        if K_t > cols_t.shape[2]:
            grow = K_t - cols_t.shape[2]
            vals_t = np.concatenate(
                [vals_t, np.zeros(vals_t.shape[:2] + (grow, bn, bn),
                                  vals_t.dtype)], axis=2)
            cols_t = np.concatenate(
                [cols_t, np.zeros(cols_t.shape[:2] + (grow,),
                                  cols_t.dtype)], axis=2)
        for b, e in per.items():
            vals[b] = 0.0
            cols[b] = 0
            vals[b, :, :e["v"].shape[1]] = e["v"]
            cols[b, :, :e["c"].shape[1]] = e["c"]
            vals_t[b] = 0.0
            cols_t[b] = 0
            vals_t[b, :, :e["vt"].shape[1]] = e["vt"]
            cols_t[b, :, :e["ct"].shape[1]] = e["ct"]
        if unit_weights:
            un = BlockStructure(vals, cols)
            un_t = BlockStructure(vals_t, cols_t)
        else:
            fwd = BlockStructure(vals, cols)
            tr = BlockStructure(vals_t, cols_t)
    return GASBatch(bnode, bmask, hn, hm, ed, es, ew,
                    forward=fwd, transposed=tr, unit=un,
                    unit_transposed=un_t, num_batches=B, max_b=max_b,
                    max_h=max_h, max_e=max_e, bn=old.bn)


def weighted_in_csr(graph: Graph) -> Tuple[np.ndarray, np.ndarray,
                                           np.ndarray]:
    """The weighted in-edge CSR (self-loops included, per-destination
    global-COO order preserved): (indptr [N+1] int64, src [E], w [E]).
    The per-dst order is the bit-for-bit contract `subgraph_batch`
    callers (serving, the dynamic re-push) rest on."""
    N = graph.num_nodes
    dst, src, w = gcn_edge_weights(graph)
    order = np.argsort(dst, kind="stable")   # keeps per-dst edge order
    counts = np.bincount(dst[order], minlength=N)
    indptr = np.concatenate([[0], np.cumsum(counts)]).astype(np.int64)
    return indptr, src[order], w[order]


def _next_pow2(n: int) -> int:
    n = int(n)
    return 1 if n <= 1 else 1 << (n - 1).bit_length()


def subgraph_batch(indptr: np.ndarray, src: np.ndarray, w: np.ndarray,
                   num_nodes: int, nodes: np.ndarray,
                   max_b: Optional[int] = None,
                   max_h: Optional[int] = None,
                   max_e: Optional[int] = None,
                   build_blocks: bool = False,
                   unit_weights: bool = False,
                   bn: int = 128,
                   pad_k: Optional[int] = None,
                   pad_k_t: Optional[int] = None) -> GASBatch:
    """One single-batch host `GASBatch` over an arbitrary node set, cut
    from a weighted in-edge CSR (`weighted_in_csr`) — same index
    conventions as `build_batches` (pad node N, trash row max_b, dummy
    zero row max_b + max_h) and the same per-destination edge order as
    the global COO, which the bit-for-bit equivalence rests on. Shared
    by serving (`serve.build_request_batch` adds bucket pads) and the
    dynamic re-push (`core.dynamic.advance`). Pads default to the next
    power of two of the needed size (bounded retraces under varying
    closure sizes); explicit pads raise on overflow.

    `build_blocks=True` additionally tiles the local
    [max_b, max_b+max_h+1] adjacency into BCSR block families through
    the SAME `_emit_part_blocks` emitter `build_batches` uses — forward
    AND transposed, as `kernels.ops.gas_aggregate` requires the 4-tuple
    — so a request-closure subgraph aggregates on the kernel/MXU path
    instead of the segment fallback. `unit_weights=True` builds the
    unit-weight (edge-multiplicity) families instead, for the ops that
    never read the normalized weights (GIN/GAT/PNA). `pad_k`/`pad_k_t`
    are monotone K floors: zero-block padding up to the caller's
    previously seen K keeps same-bucket requests on one jit trace (the
    serve-side mirror of `GASPlan._pad_k`)."""
    N = int(num_nodes)
    nodes = np.asarray(nodes, np.int64)
    nb = len(nodes)
    indptr = np.asarray(indptr, np.int64)
    starts = indptr[nodes]
    lens = indptr[nodes + 1] - starts
    total = int(lens.sum())
    offs = np.concatenate([[0], np.cumsum(lens)[:-1]]).astype(np.int64)
    flat = np.repeat(starts - offs, lens) + np.arange(total)
    e_src = np.asarray(src)[flat].astype(np.int64)
    e_w = np.asarray(w)[flat]
    e_dst = np.repeat(np.arange(nb, dtype=np.int64), lens)
    halo = np.setdiff1d(e_src, nodes)
    nh = len(halo)

    max_b = _next_pow2(nb) if max_b is None else int(max_b)
    max_h = _next_pow2(nh) if max_h is None else int(max_h)
    max_e = _next_pow2(total) if max_e is None else int(max_e)
    if nb > max_b or nh > max_h or total > max_e:
        raise ValueError(
            f"subgraph ({nb}, {nh}, {total}) exceeds pads "
            f"({max_b}, {max_h}, {max_e})")

    lookup = np.full(N + 1, max_b + max_h, np.int64)
    lookup[nodes] = np.arange(nb)
    lookup[halo] = max_b + np.arange(nh)
    bnode = np.full(max_b, N, np.int32)
    bnode[:nb] = nodes
    bmask = np.zeros(max_b, bool)
    bmask[:nb] = True
    hn = np.full(max_h, N, np.int32)
    hn[:nh] = halo
    hm = np.zeros(max_h, bool)
    hm[:nh] = True
    ed = np.full(max_e, max_b, np.int32)
    ed[:total] = e_dst
    es = np.full(max_e, max_b + max_h, np.int32)
    es[:total] = lookup[e_src]
    ew = np.zeros(max_e, np.float32)
    ew[:total] = e_w

    fwd = tr = un = un_t = None
    if build_blocks:
        e = _emit_part_blocks(ed, es, ew, max_b, max_h, bn, unit_weights)
        K = max(e["c"].shape[1], pad_k or 1)
        K_t = max(e["ct"].shape[1], pad_k_t or 1)
        vals = np.zeros((e["v"].shape[0], K, bn, bn), np.float32)
        cols = np.zeros((e["c"].shape[0], K), np.int32)
        vals_t = np.zeros((e["vt"].shape[0], K_t, bn, bn), np.float32)
        cols_t = np.zeros((e["ct"].shape[0], K_t), np.int32)
        vals[:, :e["v"].shape[1]] = e["v"]
        cols[:, :e["c"].shape[1]] = e["c"]
        vals_t[:, :e["vt"].shape[1]] = e["vt"]
        cols_t[:, :e["ct"].shape[1]] = e["ct"]
        if unit_weights:
            un = BlockStructure(vals, cols)
            un_t = BlockStructure(vals_t, cols_t)
        else:
            fwd = BlockStructure(vals, cols)
            tr = BlockStructure(vals_t, cols_t)
    return GASBatch(bnode, bmask, hn, hm, ed, es, ew,
                    forward=fwd, transposed=tr, unit=un,
                    unit_transposed=un_t, num_batches=1,
                    max_b=max_b, max_h=max_h, max_e=max_e, bn=bn)
