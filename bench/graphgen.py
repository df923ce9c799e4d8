"""Seeded, vectorized generator of the benchmark's graphs.

A configuration's `nodes`, `mean_degree`, `parts` and `intra_share` fix
the structure, drawn from the configuration's own `graph_seed`, so every run
of a cell aggregates over the same edges and compiles the same shapes; the
run's `--seed` draws what a run feeds the model: features, labels and the
train split (`node_data`).

Structure (`structure`): Chung-Lu style. Each node gets a lognormal weight;
edge endpoints are drawn in proportion to it, so degrees are heavy-tailed
like a citation graph's. An edge's second endpoint is drawn from the
first one's planted part with probability `intra_share`, else from the
whole graph (so with P parts about intra_share + (1 - intra_share) / P of
the edges lie inside a part). Pairs are made
undirected, self-loops and duplicates dropped, and exactly
`round(nodes * mean_degree / 2)` undirected edges kept.
"""
from __future__ import annotations

import numpy as np

# lognormal sigma of the node weights: a few hubs with hundreds of
# neighbours, most nodes with a handful
WEIGHT_SIGMA = 1.0


def planted_parts(nodes: int, parts: int, rng) -> np.ndarray:
    """Part of each node: sizes differ by at most one, ids shuffled."""
    return rng.permutation(np.arange(nodes) % parts).astype(np.int32)


def structure(graph_cfg: dict):
    """(indptr [N+1] int32, indices [E] int32, part [N] int32) of the
    undirected graph, CSR by destination, from `graph_cfg` alone."""
    n = int(graph_cfg["nodes"])
    p = int(graph_cfg["parts"])
    target = int(round(n * float(graph_cfg["mean_degree"]) / 2))
    intra = float(graph_cfg["intra_share"])
    rng = np.random.default_rng(int(graph_cfg["graph_seed"]))
    part = planted_parts(n, p, rng)
    w = rng.lognormal(0.0, WEIGHT_SIGMA, n)

    # members of each part contiguous, with the cumulative weight over them
    members = np.argsort(part, kind="stable")
    starts = np.searchsorted(part[members], np.arange(p + 1))
    cum = np.concatenate([[0.0], np.cumsum(w[members])])

    def pick(lo, hi, size):
        """Members index in [lo, hi) drawn in proportion to weight."""
        u = cum[lo] + rng.random(size) * (cum[hi] - cum[lo])
        k = np.searchsorted(cum, u, side="right") - 1
        return np.clip(k, lo, hi - 1)

    # oversample: duplicates and self-loops are dropped below
    m = int(target * 1.3) + 64
    u = members[pick(np.zeros(m, np.int64), np.full(m, n), m)]
    same = rng.random(m) < intra
    pu = part[u]
    v_intra = members[pick(starts[pu], starts[pu + 1], m)]
    v_any = members[pick(np.zeros(m, np.int64), np.full(m, n), m)]
    v = np.where(same, v_intra, v_any)
    lo, hi = np.minimum(u, v), np.maximum(u, v)
    keys = np.unique(lo.astype(np.int64) * n + hi)
    keys = keys[(keys // n) != (keys % n)]
    if len(keys) < target:
        raise ValueError(f"generator made {len(keys)} edges, needs {target}")
    keys = rng.permutation(keys)[:target]
    a, b = keys // n, keys % n
    dst = np.concatenate([a, b])
    src = np.concatenate([b, a])
    order = np.lexsort((src, dst))
    dst, src = dst[order], src[order]
    indptr = np.zeros(n + 1, np.int64)
    np.cumsum(np.bincount(dst, minlength=n), out=indptr[1:])
    return indptr.astype(np.int32), src.astype(np.int32), part


def node_data(part: np.ndarray, graph_cfg: dict, features: int,
              classes: int, seed: int):
    """(x [N, F] f32, y [N] int32, train_mask [N] bool) from the run seed.

    Labels follow the planted parts (each part has a dominant class, as
    topics cluster in a citation graph) with `label_noise` of them drawn
    uniformly; features are a class mean plus unit noise. The train split
    takes `train_share` of the nodes."""
    rng = np.random.default_rng(seed)
    n = len(part)
    p = int(part.max()) + 1
    dominant = rng.integers(0, classes, p)
    noisy = rng.random(n) < float(graph_cfg["label_noise"])
    y = np.where(noisy, rng.integers(0, classes, n), dominant[part])
    means = rng.normal(0.0, 1.0, (classes, features))
    x = (0.5 * means[y] + rng.normal(0.0, 1.0, (n, features)))
    train = rng.random(n) < float(graph_cfg["train_share"])
    return x.astype(np.float32), y.astype(np.int32), train


def intra_share(indptr: np.ndarray, indices: np.ndarray,
                part: np.ndarray) -> float:
    """Share of directed edges whose endpoints share a part."""
    dst = np.repeat(np.arange(len(indptr) - 1), np.diff(indptr))
    return float(np.mean(part[dst] == part[indices]))
