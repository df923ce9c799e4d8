"""Operations and bytes are counted from the graph, never from blocks."""
import numpy as np
import pytest

from bench import graphgen, work

CFG = {"nodes": 900, "parts": 4, "mean_degree": 13.77, "intra_share": 0.7,
       "graph_seed": 3}
DIMS = [128, 256, 256, 40]


def relabel(indptr, indices, part, perm):
    """The same graph with node i renamed perm[i]."""
    n = len(part)
    dst = np.repeat(np.arange(n), np.diff(indptr))
    d, s = perm[dst], perm[indices]
    order = np.lexsort((s, d))
    ip = np.zeros(n + 1, np.int64)
    np.cumsum(np.bincount(d, minlength=n), out=ip[1:])
    new_part = np.empty_like(part)
    new_part[perm] = part
    return ip, s[order], new_part


def nonzero_blocks(indptr, indices, part):
    """Nonzero 128 x 128 blocks of the program's per-part adjacency."""
    from repro.core.gas import build_batches
    from repro.data.graphs import Graph
    n = len(part)
    g = Graph(indptr, indices, np.zeros((n, 1), np.float32),
              np.zeros(n, np.int32), *(np.zeros(n, bool),) * 3, 2)
    vals = build_batches(g, part, build_blocks=True).forward.vals
    return int(np.count_nonzero(vals.reshape(*vals.shape[:3], -1).any(-1)))


def ring(n, parts):
    """Each node linked to its two nearest neighbours on each side; parts
    are contiguous arcs."""
    i = np.arange(n)
    nbr = np.sort(np.stack([(i + k) % n for k in (-2, -1, 1, 2)], 1), 1)
    indptr = np.arange(0, 4 * n + 1, 4)
    return indptr, nbr.ravel(), (i * parts // n).astype(np.int32)


def test_work_ignores_node_order_and_block_layout():
    ip, ix, part = ring(2048, 2)
    perm = np.random.default_rng(0).permutation(len(part))
    ip2, ix2, part2 = relabel(ip, ix, part, perm)
    # the block layout differs: a banded adjacency against a scattered one
    assert nonzero_blocks(ip, ix, part) < nonzero_blocks(ip2, ix2, part2)
    for ip_, ix_, part_ in ((ip, ix, part),
                            graphgen.structure(CFG)):
        perm = np.random.default_rng(1).permutation(len(part_))
        ip2, ix2, part2 = relabel(ip_, ix_, part_, perm)
        for dtype in ("f32", "int8"):
            row = work.history_row_bytes(dtype)
            a = work.aggregation_work(ip_, ix_, part_, DIMS, row)
            b = work.aggregation_work(ip2, ix2, part2, DIMS, row)
            # ... the needed work does not
            assert a == pytest.approx(b, rel=1e-12)
        n, e = len(part_), len(ix_) + len(part_)
        assert work.gcn_model_flops(n, e, DIMS) == \
            work.gcn_model_flops(len(part2), len(ix2) + len(part2), DIMS)


def test_counts_on_a_hand_made_graph():
    # path 0-1-2, parts {0, 1} and {2}
    indptr = np.array([0, 1, 3, 4])
    indices = np.array([1, 0, 2, 1])
    part = np.array([0, 0, 1])
    d = 8
    (layer0, layer1) = work.aggregation_work(
        indptr, indices, part, [d, d, 2], work.history_row_bytes("int8"))
    # 7 edges with the self-loops: 2 * 7 * d operations per layer
    assert layer0["forward"][0] == layer1["forward"][0] == 2 * 7 * d
    # part 0: 2 nodes, halo {2}; part 1: 1 node, halo {1}. Layer 0 reads
    # feature rows (4 d bytes), layer 1 int8 history rows (d + 4).
    rows_out_in = 3 * 4 * d * 2
    assert layer0["forward"][1] == rows_out_in + 2 * 4 * d + 7 * 8
    assert layer1["forward"][1] == rows_out_in + 2 * (d + 4) + 7 * 8
    # backward: only edges inside a part carry gradient (5 of 7)
    assert layer0["backward"] == (0.0, 0.0)
    assert layer1["backward"] == (2 * 5 * d, 3 * 8 * d + 5 * 8)
    # full graph: 3 nodes, 7 edges
    dense = [2 * 3 * d * d, 2 * 3 * d * 2]
    fwd = 2 * 7 * d * 2 + sum(dense)
    bwd = sum(dense) + dense[1] + 2 * 7 * d
    assert work.gcn_model_flops(3, 7, [d, d, 2]) == fwd + bwd
