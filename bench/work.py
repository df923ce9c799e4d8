"""Operations and bytes that GAS training of a GCN needs, from the graph.

Everything here is counted from the graph's edges, its parts and the
layer widths: never from the 128 x 128 adjacency blocks the kernels
multiply, so a kernel that skips empty blocks or gathers rows in place of
blocks is judged against the same work. Node order does not change any
count.

Edges include one self-loop per node (GCN's A + I). An aggregation of
width d over E edges needs 2 E d operations. Its bytes are the source
rows it reads once each, 8 bytes per edge (source index and weight) and
its output rows.
"""
from __future__ import annotations

import numpy as np

EDGE_BYTES = 8


def edge_parts(indptr: np.ndarray, indices: np.ndarray, part: np.ndarray):
    """(dst part, src part) of every edge, self-loops included."""
    n = len(indptr) - 1
    dst = np.concatenate([np.repeat(np.arange(n), np.diff(indptr)),
                          np.arange(n)])
    src = np.concatenate([indices, np.arange(n)])
    return part[dst], part[src], src


def gcn_model_flops(num_nodes: int, num_edges: int, dims) -> float:
    """Operations of one full-graph GCN forward and backward pass.

    `dims` = [d_in, hidden..., classes]; `num_edges` counts self-loops.
    Forward: per layer an aggregation (2 E d_in) and a dense product
    (2 N d_in d_out). Backward: every weight gradient (2 N d_in d_out),
    and for every layer but the first the input gradient (2 N d_in d_out)
    and the transposed aggregation (2 E d_in): the first layer's input is
    the features, which need no gradient."""
    total = 0.0
    for ell, (di, do) in enumerate(zip(dims[:-1], dims[1:])):
        dense = 2.0 * num_nodes * di * do
        agg = 2.0 * num_edges * di
        total += agg + dense + dense
        if ell > 0:
            total += dense + agg
    return total


def aggregation_work(indptr: np.ndarray, indices: np.ndarray,
                     part: np.ndarray, dims, halo_row_bytes) -> dict:
    """Needed work of one GAS epoch's aggregations, summed over parts.

    `halo_row_bytes(d)` gives the bytes of one history row of width d
    (`history_row_bytes`). Layer 0 reads feature rows (4 d).

    Forward, layer ell, part b: the distinct source rows of the edges into
    b (its own nodes and its halo), the edges, and b's output rows.
    Backward, layers > 0: only edges whose source is in b carry a
    gradient; they read b's output gradient rows and write its input
    gradient rows. Returns one dict per layer, {"forward": (flops,
    bytes), "backward": (flops, bytes)}, per epoch; the first layer's
    backward is (0, 0)."""
    dpart, spart, src = edge_parts(indptr, indices, part)
    p = int(part.max()) + 1
    nodes = np.bincount(part, minlength=p).astype(np.float64)
    edges = np.bincount(dpart, minlength=p).astype(np.float64)
    inner = np.bincount(dpart[dpart == spart], minlength=p).astype(
        np.float64)
    # distinct halo sources of each part
    outer = dpart != spart
    n1 = len(part) + 1
    key = np.unique(dpart[outer].astype(np.int64) * n1 + src[outer])
    halo = np.bincount(key // n1, minlength=p).astype(np.float64)
    layers = []
    for ell, d in enumerate(dims[:-1]):
        hb = 4.0 * d if ell == 0 else float(halo_row_bytes(d))
        fwd = (float(np.sum(2.0 * edges * d)),
               float(np.sum(nodes * 4.0 * d + halo * hb
                            + edges * EDGE_BYTES + nodes * 4.0 * d)))
        bwd = (0.0, 0.0)
        if ell > 0:
            bwd = (float(np.sum(2.0 * inner * d)),
                   float(np.sum(nodes * 8.0 * d + inner * EDGE_BYTES)))
        layers.append({"forward": fwd, "backward": bwd})
    return layers


def history_row_bytes(history_dtype: str):
    """Bytes of one stored history row of width d."""
    if history_dtype == "f32":
        return lambda d: 4.0 * d
    if history_dtype == "int8":
        return lambda d: d + 4.0
    raise ValueError(f"no byte count for history_dtype {history_dtype!r}")
