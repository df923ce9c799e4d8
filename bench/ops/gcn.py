"""GCN for the benchmark: the weights a run starts from, the operations
of a full-graph pass, and a plain float32 `jax.numpy` reference of GAS
training. Found by `harness.load_op` from a configuration's
`model.op`; another op is another file beside this one with the same
entry points: `init_params`, `model_flops` and `outputs`.

The reference follows GNNAutoScale's Algorithm 1 on the benchmark's own
graph, independently of the program under test: for each planted part in
the epoch's order, the part's nodes are the batch, every neighbour
outside the part is read from the historical embeddings of the layer
below, each hidden layer's batch rows are pushed to its history, and one
AdamW step (with global-norm clipping where the configuration states a
clip) follows the masked cross-entropy. GCN layer: `h = A_hat x W + b`
with `A_hat = D^-1/2 (A + I) D^-1/2`, ReLU between layers. Aggregation is
a gather and segment sum over the edges; dense layers run at
`Precision.HIGHEST`.

Two knobs make the control of the check:
  * `passes=3` computes every dense product as three bf16 products
    (hi*hi + hi*lo + lo*hi, f32 accumulation, forward and backward): the
    `high` matmul precision, one step below the `highest` the
    configuration states, made explicit so that it means the same on
    every platform;
  * `qmax` sets the symmetric per-row quantization of the history rows:
    0 keeps float32, 127 is int8, 7 is int4.

Nothing here imports the program or takes anything it made.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench import work

HIGHEST = jax.lax.Precision.HIGHEST


def dims(config: dict):
    """[features, hidden..., classes] of the configuration's model."""
    m = config["model"]
    return ([m["features"]] + [m["hidden"]] * (m["layers"] - 1)
            + [m["classes"]])


def init_params(seed: int, config: dict):
    """Glorot-uniform weights and zero biases in the program's pytree
    layout ({"layers": [{"w", "b"}, ...]}), made in one jitted call from
    `seed` (any whole number: its high bits are folded in), as numpy."""
    ds = dims(config)

    @jax.jit
    def make(key):
        layers = []
        for k, (di, do) in zip(jax.random.split(key, len(ds) - 1),
                               zip(ds[:-1], ds[1:])):
            lim = math.sqrt(6.0 / (di + do))
            layers.append({"w": jax.random.uniform(k, (di, do), jnp.float32,
                                                   -lim, lim),
                           "b": jnp.zeros((do,), jnp.float32)})
        return {"layers": layers}

    key = jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)
    return jax.tree_util.tree_map(np.asarray, make(key))


def model_flops(config: dict, num_nodes: int, num_edges: int) -> float:
    """Operations of one full-graph forward and backward pass
    (`num_edges` without self-loops)."""
    return work.gcn_model_flops(num_nodes, num_edges + num_nodes,
                                dims(config))


def outputs(config: dict, inp, epochs: int, control: bool = False) -> dict:
    """The reference's readings over `epochs` check epochs from the run's
    inputs (`harness.Inputs`); with `control`, at the configuration's
    control precision (`config["control"]`)."""
    prec = config["control"] if control else config["reference"]
    parts = part_edges(inp.indptr, inp.indices, inp.part)
    hist_dims = dims(config)[1:-1]
    return train(inp.params, inp.x, inp.y, inp.train, parts,
                 inp.orders(epochs), hist_dims, config["optimizer"],
                 passes=prec["passes"], qmax=prec["qmax"])


def _split(a):
    hi = a.astype(jnp.bfloat16)
    return hi, (a - hi.astype(jnp.float32)).astype(jnp.bfloat16)


def _dot3_raw(a, b):
    (ah, al), (bh, bl) = _split(a), _split(b)
    f = functools.partial(jnp.dot, preferred_element_type=jnp.float32)
    return f(ah, bh) + f(ah, bl) + f(al, bh)


@jax.custom_vjp
def dot3(a, b):
    """a @ b as three bf16 products with f32 accumulation."""
    return _dot3_raw(a, b)


def _dot3_fwd(a, b):
    return _dot3_raw(a, b), (a, b)


def _dot3_bwd(res, g):
    a, b = res
    return _dot3_raw(g, b.T), _dot3_raw(a.T, g)


dot3.defvjp(_dot3_fwd, _dot3_bwd)


def matmul(a, b, passes: int):
    if passes == 6:
        return jnp.dot(a, b, precision=HIGHEST)
    if passes == 3:
        return dot3(a, b)
    raise ValueError(f"passes must be 6 or 3, got {passes}")


def quantize(v, qmax: int):
    """Symmetric per-row quantization to [-qmax, qmax], returned
    dequantized: scale = max|row| / qmax (1 for an all-zero row)."""
    if not qmax:
        return v
    amax = jnp.max(jnp.abs(v), axis=-1)
    s = jnp.where(amax > 0, amax / qmax, 1.0)
    q = jnp.clip(jnp.round(v / s[:, None]), -qmax, qmax)
    return q * s[:, None]


def part_edges(indptr: np.ndarray, indices: np.ndarray, part: np.ndarray):
    """Per-part padded arrays of the GCN-normalized graph with self-loops:
    nodes [P, B] (pad N), dst [P, E] local row (pad B: a trash row), src
    [P, E] global id (pad N: the zero sentinel row), w [P, E] (pad 0)."""
    n = len(indptr) - 1
    deg_in = np.diff(indptr).astype(np.int64)
    dst = np.concatenate([np.repeat(np.arange(n), deg_in), np.arange(n)])
    src = np.concatenate([indices.astype(np.int64), np.arange(n)])
    deg = np.bincount(dst, minlength=n).astype(np.float64)
    w = (1.0 / np.sqrt(deg[dst] * deg[src])).astype(np.float32)
    p = int(part.max()) + 1
    members = [np.flatnonzero(part == b) for b in range(p)]
    max_b = max(len(m) for m in members)
    local = np.empty(n, np.int64)
    for m in members:
        local[m] = np.arange(len(m))
    eparts = part[dst]
    order = np.argsort(eparts, kind="stable")
    bounds = np.searchsorted(eparts[order], np.arange(p + 1))
    max_e = int(np.diff(bounds).max())
    nodes = np.full((p, max_b), n, np.int32)
    e_dst = np.full((p, max_e), max_b, np.int32)
    e_src = np.full((p, max_e), n, np.int32)
    e_w = np.zeros((p, max_e), np.float32)
    for b in range(p):
        nodes[b, :len(members[b])] = members[b]
        sl = order[bounds[b]:bounds[b + 1]]
        e_dst[b, :len(sl)] = local[dst[sl]]
        e_src[b, :len(sl)] = src[sl]
        e_w[b, :len(sl)] = w[sl]
    return nodes, e_dst, e_src, e_w


def init_adam(params):
    zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
    return {"step": jnp.zeros((), jnp.int32), "m": zeros,
            "v": jax.tree_util.tree_map(jnp.zeros_like, params)}


def make_epoch(hp: dict, passes: int, qmax: int):
    """jitted (params, adam, tables, x, y, train, parts, order) ->
    (params, adam, tables, per-batch losses) for one epoch in `order`."""
    lr, wd = hp["lr"], hp["weight_decay"]
    clip = math.inf if hp["grad_clip"] is None else hp["grad_clip"]
    b1, b2, eps = 0.9, 0.999, 1e-8

    def batch_loss(params, tables, x, y, train, nodes, e_dst, e_src, e_w):
        n_sent = x.shape[0] - 1
        max_b = nodes.shape[0]
        mask = nodes < n_sent
        drop = jnp.where(mask, nodes, n_sent + 1)
        pushes, cur = [], None
        nl = len(params["layers"])
        for ell, lp in enumerate(params["layers"]):
            if ell == 0:
                src_rows = x[e_src]
            else:
                table = tables[ell - 1].at[drop].set(cur, mode="drop")
                src_rows = table[e_src]
            agg = jax.ops.segment_sum(src_rows * e_w[:, None], e_dst,
                                      num_segments=max_b + 1)[:max_b]
            h = matmul(agg, lp["w"], passes) + lp["b"]
            if ell < nl - 1:
                h = jax.nn.relu(h)
                pushes.append(jax.lax.stop_gradient(h))
            cur = h
        m = train[jnp.minimum(nodes, n_sent)] & mask
        logz = jax.scipy.special.logsumexp(cur, axis=-1)
        gold = jnp.take_along_axis(cur, y[jnp.minimum(nodes, n_sent)][:, None],
                                   axis=-1)[:, 0]
        loss = jnp.sum((logz - gold) * m) / jnp.maximum(jnp.sum(m), 1)
        return loss, (pushes, drop)

    def step(carry, b, x, y, train, parts):
        params, adam, tables = carry
        nodes, e_dst, e_src, e_w = (a[b] for a in parts)
        (loss, (pushes, drop)), g = jax.value_and_grad(
            batch_loss, has_aux=True)(params, tables, x, y, train, nodes,
                                      e_dst, e_src, e_w)
        tables = tuple(t.at[drop].set(quantize(h, qmax), mode="drop")
                       for t, h in zip(tables, pushes))
        leaves = jax.tree_util.tree_leaves(g)
        gn = jnp.sqrt(sum(jnp.sum(jnp.square(a)) for a in leaves))
        g = jax.tree_util.tree_map(
            lambda a: a * jnp.minimum(1.0, clip / jnp.maximum(gn, 1e-9)), g)
        t = adam["step"] + 1
        tf = t.astype(jnp.float32)
        m = jax.tree_util.tree_map(lambda m, a: b1 * m + (1 - b1) * a,
                                   adam["m"], g)
        v = jax.tree_util.tree_map(lambda v, a: b2 * v + (1 - b2) * a * a,
                                   adam["v"], g)
        params = jax.tree_util.tree_map(
            lambda p, m, v: p - lr * ((m / (1 - b1 ** tf))
                                      / (jnp.sqrt(v / (1 - b2 ** tf)) + eps)
                                      + wd * p), params, m, v)
        return (params, {"step": t, "m": m, "v": v}, tables), loss

    @jax.jit
    def epoch(params, adam, tables, x, y, train, parts, order):
        (params, adam, tables), losses = jax.lax.scan(
            lambda c, b: step(c, b, x, y, train, parts),
            (params, adam, tables), order)
        return params, adam, tables, losses

    return epoch


def train(params, x, y, train_mask, parts, orders, hist_dims, hp: dict,
          passes: int = 6, qmax: int = 0):
    """Run `len(orders)` epochs from `params` with zeroed histories.
    Returns per-epoch mean losses (and each epoch's per-batch losses),
    the AdamW first moment after epoch 1,
    the parameters after the last epoch and the history tables (one
    [N, d] float32 array per hidden layer), all as numpy."""
    n = x.shape[0]
    xs = jnp.concatenate([jnp.asarray(x), jnp.zeros((1, x.shape[1]))])
    ys = jnp.concatenate([jnp.asarray(y), jnp.zeros((1,), jnp.int32)])
    tr = jnp.concatenate([jnp.asarray(train_mask), jnp.zeros((1,), bool)])
    parts = tuple(jnp.asarray(a) for a in parts)
    tables = tuple(jnp.zeros((n + 1, d), jnp.float32) for d in hist_dims)
    params = jax.tree_util.tree_map(jnp.asarray, params)
    adam = init_adam(params)
    epoch = make_epoch(hp, passes, qmax)
    losses, batch_losses, first_m = [], [], None
    for order in orders:
        params, adam, tables, lb = epoch(params, adam, tables, xs, ys, tr,
                                         parts, jnp.asarray(order))
        batch_losses.append(np.asarray(lb))
        losses.append(float(np.mean(batch_losses[-1])))
        if first_m is None:
            first_m = jax.tree_util.tree_map(np.asarray, adam["m"])
    return {"loss": losses, "batch_loss": batch_losses, "m": first_m,
            "params": jax.tree_util.tree_map(np.asarray, params),
            "tables": [np.asarray(t[:n]) for t in tables]}
