"""GCN trained in sharded supersteps, for the benchmark: the weights a run
starts from, the operations of a full-graph pass, and a plain float32
`jax.numpy` reference of the supersteps on one device. Found by
`harness.load_op` from a configuration's `model.op` ("gcn_dist"); the
program's layer is `PROGRAM_OP`. What it shares with one-chip training
(weights, operation counts, the per-part edge arrays, the dense products
and the history quantization of the control) comes from `gcn.py`.

The reference follows the configuration's deployment, independently of
the program under test: the P planted parts go to `ranks` ranks, P/R
each, contiguous by part id. Each epoch's permutation of all parts
(`Inputs.orders`, the draw one chip makes) hands every rank its own
parts in the order the permutation lists them, and superstep s takes the
s-th part of every rank. All R parts of a superstep read the history
tables as they stood at its start: in-part neighbours are exact, every
other neighbour comes from the layer below's history. The loss is the
summed masked cross-entropy of the R parts over their summed count of
training nodes; one AdamW step follows (with global-norm clipping where
the configuration states a clip), and then every part's hidden rows are
pushed. Nothing here imports the program or takes anything it made.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.ops import gcn

PROGRAM_OP = "gcn"
dims = gcn.dims
init_params = gcn.init_params
model_flops = gcn.model_flops


def rank_orders(order: np.ndarray, ranks: int) -> np.ndarray:
    """[R, S]: each rank's parts (index within the rank) in the order
    `order`, a permutation of all parts, lists them."""
    s = len(order) // ranks
    order = np.asarray(order)
    return np.stack([order[order // s == r] % s for r in range(ranks)])


def outputs(config: dict, inp, epochs: int, control: bool = False,
            drop_exchange: bool = False) -> dict:
    """The reference's readings over `epochs` check epochs from the run's
    inputs (`harness.Inputs`); with `control`, at the configuration's
    control precision (`config["control"]`); with `drop_exchange`, the
    planted fault of `make_epoch`."""
    prec = config["control"] if control else config["reference"]
    parts = gcn.part_edges(inp.indptr, inp.indices, inp.part)
    return train(inp.params, inp.x, inp.y, inp.train, parts,
                 inp.orders(epochs), dims(config)[1:-1],
                 config["optimizer"], config["ranks"],
                 passes=prec["passes"], qmax=prec["qmax"],
                 drop_exchange=drop_exchange)


def make_epoch(hp: dict, ranks: int, passes: int, qmax: int,
               fresh_reads: bool = False, drop_exchange: bool = False):
    """jitted (params, adam, tables, x, y, train, parts, owner, steps) ->
    (params, adam, tables, per-superstep losses); `steps` [S, R] holds
    the global part of each rank in each superstep, `owner` [N+1] the
    rank of each node (-1 for the sentinel). Two faults, planted by the
    tests and `bench/calibrate_dist.py`: `fresh_reads` lets each part
    read the pushes of the parts before it in the same superstep;
    `drop_exchange` reads every history row another rank owns as zeros,
    as if the exchange between chips were left out."""
    lr, wd = hp["lr"], hp["weight_decay"]
    clip = math.inf if hp["grad_clip"] is None else hp["grad_clip"]
    b1, b2, eps = 0.9, 0.999, 1e-8

    def part_forward(params, tables, x, y, train, owner, rank, nodes,
                     e_dst, e_src, e_w):
        """(summed cross-entropy, training nodes, pushes, scatter ids)."""
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
                if drop_exchange:
                    src_rows = jnp.where((owner[e_src] == rank)[:, None],
                                         src_rows, 0.0)
            agg = jax.ops.segment_sum(src_rows * e_w[:, None], e_dst,
                                      num_segments=max_b + 1)[:max_b]
            h = gcn.matmul(agg, lp["w"], passes) + lp["b"]
            if ell < nl - 1:
                h = jax.nn.relu(h)
                pushes.append(jax.lax.stop_gradient(h))
            cur = h
        safe = jnp.minimum(nodes, n_sent)
        m = train[safe] & mask
        logz = jax.scipy.special.logsumexp(cur, axis=-1)
        gold = jnp.take_along_axis(cur, y[safe][:, None], axis=-1)[:, 0]
        return jnp.sum((logz - gold) * m), jnp.sum(m), pushes, drop

    def push(tables, pushes, drop):
        return tuple(t.at[drop].set(gcn.quantize(h, qmax), mode="drop")
                     for t, h in zip(tables, pushes))

    def step(carry, sel, x, y, train, parts, owner):
        params, adam, tables = carry

        def loss_fn(p):
            ce, count, out, tabs = 0.0, 0, [], tables
            for r in range(ranks):
                c, n, pushes, drop = part_forward(
                    p, tabs, x, y, train, owner, r,
                    *(a[sel[r]] for a in parts))
                ce, count = ce + c, count + n
                out.append((pushes, drop))
                if fresh_reads:
                    tabs = push(tabs, pushes, drop)
            return ce / jnp.maximum(count, 1), out

        (loss, out), g = jax.value_and_grad(loss_fn, has_aux=True)(params)
        for pushes, drop in out:
            tables = push(tables, pushes, drop)
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
    def epoch(params, adam, tables, x, y, train, parts, owner, steps):
        (params, adam, tables), losses = jax.lax.scan(
            lambda c, sel: step(c, sel, x, y, train, parts, owner),
            (params, adam, tables), steps)
        return params, adam, tables, losses

    return epoch


def train(params, x, y, train_mask, parts, orders, hist_dims, hp: dict,
          ranks: int, passes: int = 6, qmax: int = 0,
          fresh_reads: bool = False, drop_exchange: bool = False):
    """Run `len(orders)` epochs from `params` with zeroed histories;
    `orders` are the epochs' permutations of all parts. Returns per-epoch
    mean losses (and each epoch's per-superstep losses), the AdamW first
    moment after epoch 1, the parameters after the last epoch and the
    history tables (one [N, d] float32 array per hidden layer), all as
    numpy."""
    n = x.shape[0]
    xs = jnp.concatenate([jnp.asarray(x), jnp.zeros((1, x.shape[1]))])
    ys = jnp.concatenate([jnp.asarray(y), jnp.zeros((1,), jnp.int32)])
    tr = jnp.concatenate([jnp.asarray(train_mask), jnp.zeros((1,), bool)])
    s = parts[0].shape[0] // ranks
    owner = np.full(n + 1, -1, np.int32)
    owner[np.asarray(parts[0]).ravel()] = np.repeat(
        np.arange(parts[0].shape[0]) // s, parts[0].shape[1])
    owner[n] = -1
    parts = tuple(jnp.asarray(a) for a in parts)
    tables = tuple(jnp.zeros((n + 1, d), jnp.float32) for d in hist_dims)
    params = jax.tree_util.tree_map(jnp.asarray, params)
    adam = gcn.init_adam(params)
    epoch = make_epoch(hp, ranks, passes, qmax, fresh_reads, drop_exchange)
    losses, step_losses, first_m = [], [], None
    for order in orders:
        steps = (rank_orders(order, ranks)
                 + s * np.arange(ranks)[:, None]).T
        params, adam, tables, ls = epoch(params, adam, tables, xs, ys, tr,
                                         parts, jnp.asarray(owner),
                                         jnp.asarray(steps))
        step_losses.append(np.asarray(ls))
        losses.append(float(np.mean(step_losses[-1])))
        if first_m is None:
            first_m = jax.tree_util.tree_map(np.asarray, adam["m"])
    return {"loss": losses, "batch_loss": step_losses, "m": first_m,
            "params": jax.tree_util.tree_map(np.asarray, params),
            "tables": [np.asarray(t[:n]) for t in tables]}
