"""The baselines the paper compares GAS against (Tables 1/3/5), and their
`TrainConfig`; GAS itself trains through `core.runtime`:

  - FullBatchTrainer — full-graph training with exact propagation: the
    accuracy reference of Table 1.
  - GraphSAGETrainer — node-wise neighbor sampling (Hamilton et al., 2017):
    recursive fixed-fanout L-hop mini-batches; drops edges, working set
    grows ~fanout^L (the neighbor-explosion regime GAS eliminates).
  - SGCTrainer — Simplifying Graph Convolution (Wu et al., 2019):
    non-trainable propagation Â^K X precomputed once, then logistic
    regression; fast but provably less expressive (no trainable MESSAGE).
  - CLUSTER-GCN is `GASConfig(use_history=False)` — intra-cluster edges
    only.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.gas import gcn_edge_weights
from repro.core.runtime import _accuracy
from repro.data.graphs import Graph
from repro.gnn import layers as L
from repro.gnn.model import GNNSpec, full_forward, init_gnn
from .optimizer import adamw_init, adamw_update, clip_by_global_norm


@dataclass
class TrainConfig:
    lr: float = 0.01
    weight_decay: float = 5e-4
    grad_clip: float = 2.0
    epochs: int = 100
    seed: int = 0


# ---------------------------------------------------------------------------
# Full-batch training
# ---------------------------------------------------------------------------

class FullBatchTrainer:
    def __init__(self, graph: Graph, spec: GNNSpec,
                 tcfg: Optional[TrainConfig] = None):
        tcfg = TrainConfig() if tcfg is None else tcfg
        self.graph, self.spec, self.tcfg = graph, spec, tcfg
        dst, src, w = gcn_edge_weights(graph)
        self.edges = (jnp.asarray(dst), jnp.asarray(src))
        self.edge_w = jnp.asarray(w)
        self.x = jnp.asarray(graph.x)
        self.y = jnp.asarray(graph.y)
        self.masks = {n: jnp.asarray(m) for n, m in
                      (("train", graph.train_mask), ("val", graph.val_mask),
                       ("test", graph.test_mask))}
        key = jax.random.key(tcfg.seed)
        self.params = init_gnn(key, spec)
        self.opt_state = adamw_init(self.params)
        self._step = jax.jit(self._make_step())

    def _make_step(self):
        spec, tcfg, N = self.spec, self.tcfg, self.graph.num_nodes

        def step(params, opt_state, x, y, train_mask, edges, edge_w):
            def loss_fn(p):
                logits = full_forward(p, spec, x, edges, edge_w, N)
                logz = jax.scipy.special.logsumexp(logits, axis=-1)
                gold = jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0]
                ce = jnp.sum((logz - gold) * train_mask) / \
                    jnp.maximum(jnp.sum(train_mask), 1)
                return ce, _accuracy(logits, y, train_mask)

            (loss, acc), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params)
            grads, _ = clip_by_global_norm(grads, tcfg.grad_clip)
            params, opt_state = adamw_update(
                grads, opt_state, params, lr=tcfg.lr, b1=0.9, b2=0.999,
                weight_decay=tcfg.weight_decay)
            return params, opt_state, {"loss": loss, "acc": acc}

        return step

    def fit(self, epochs: Optional[int] = None) -> List[Dict[str, float]]:
        out = []
        for _ in range(epochs or self.tcfg.epochs):
            self.params, self.opt_state, m = self._step(
                self.params, self.opt_state, self.x, self.y,
                self.masks["train"], self.edges, self.edge_w)
            out.append({k: float(v) for k, v in m.items()})
        return out

    def evaluate(self) -> Dict[str, float]:
        logits = full_forward(self.params, self.spec, self.x, self.edges,
                              self.edge_w, self.graph.num_nodes)
        return {f"{n}_acc": float(_accuracy(logits, self.y, m))
                for n, m in self.masks.items()}


# ---------------------------------------------------------------------------
# GraphSAGE: recursive neighbor sampling
# ---------------------------------------------------------------------------

class GraphSAGETrainer:
    """GCN-mean aggregation over sampled fixed-fanout neighborhoods.

    Batches are padded to static shapes: layer ℓ has at most
    batch_size * prod(fanouts[:ℓ]) rows — the exponential working set the
    paper's Table 4/Figure 1b describes."""

    def __init__(self, graph: Graph, d_hidden: int, num_layers: int = 2,
                 fanout: int = 10, batch_size: int = 256,
                 tcfg: Optional[TrainConfig] = None):
        tcfg = TrainConfig() if tcfg is None else tcfg
        self.g, self.tcfg = graph, tcfg
        self.L, self.fanout, self.bs = num_layers, fanout, batch_size
        self.rng = np.random.default_rng(tcfg.seed)

        key = jax.random.key(tcfg.seed)
        keys = jax.random.split(key, num_layers + 1)
        dims = [graph.x.shape[1]] + [d_hidden] * (num_layers - 1) + \
            [graph.num_classes]
        self.params = {"layers": [L.init_gcn(keys[i], dims[i], dims[i + 1])
                                  for i in range(num_layers)]}
        self.opt_state = adamw_init(self.params)
        self.train_nodes = np.flatnonzero(graph.train_mask)
        # static per-layer frontier caps: bs * (fanout+1)^ell
        self.caps = [batch_size * (fanout + 1) ** ell
                     for ell in range(num_layers + 1)]
        self._x = jnp.asarray(np.concatenate(
            [graph.x, np.zeros((1, graph.x.shape[1]), np.float32)]))
        self._y = jnp.asarray(graph.y)
        self._step = jax.jit(self._make_step())

    # -- host-side sampling --------------------------------------------------
    def _sample_batch(self, seeds: np.ndarray):
        """Returns per-layer padded (dst_local, src_local, w) with STATIC
        shapes (frontier padded to bs*(fanout+1)^ell) plus the padded global
        ids feeding the innermost layer (-1 = padding row)."""
        g = self.g
        layers = []
        frontier = np.full(self.caps[0], -1, np.int64)
        frontier[:len(seeds)] = seeds
        for ell in range(self.L):
            n_out = self.caps[ell]
            max_e = n_out * (self.fanout + 1)
            dst = np.full(max_e, n_out, np.int32)          # trash row
            src_g = np.full(max_e, -1, np.int64)
            w = np.zeros(max_e, np.float32)
            nxt: List[int] = [int(v) for v in frontier if v >= 0]
            index = {int(v): i for i, v in enumerate(frontier) if v >= 0}
            e = 0
            for i, v in enumerate(frontier):
                if v < 0:
                    continue
                nbrs = g.indices[g.indptr[v]:g.indptr[v + 1]]
                if len(nbrs) > self.fanout:
                    nbrs = self.rng.choice(nbrs, self.fanout, replace=False)
                deg = max(len(nbrs), 1)
                # self loop + sampled neighbors (mean aggregation)
                for u in np.concatenate([[v], nbrs]):
                    dst[e] = i
                    src_g[e] = u
                    w[e] = 1.0 / (deg + 1)
                    e += 1
                    if int(u) not in index:
                        index[int(u)] = len(nxt)
                        nxt.append(int(u))
            src = np.array([index[int(u)] if u >= 0 else -1
                            for u in src_g], np.int32)
            layers.append((dst, src, w))
            frontier = np.full(self.caps[ell + 1], -1, np.int64)
            frontier[:len(nxt)] = nxt
        return layers, frontier

    def _make_step(self):
        tcfg = self.tcfg

        def step(params, opt_state, x_rows, layer_data, labels, lmask):
            def loss_fn(p):
                h = x_rows
                for ell in reversed(range(self.L)):
                    dst, src, w = layer_data[ell]
                    n_out = self.caps[ell]
                    dummy = jnp.zeros((1, h.shape[-1]), h.dtype)
                    h_all = jnp.concatenate([h, dummy], axis=0)
                    src_safe = jnp.where(src >= 0, src, h.shape[0])
                    h = L.gcn(p["layers"][self.L - 1 - ell], h_all,
                              (dst, src_safe), w, n_out)
                    if ell != 0:
                        h = jax.nn.relu(h)
                logits = h
                logz = jax.scipy.special.logsumexp(logits, axis=-1)
                gold = jnp.take_along_axis(logits, labels[:, None],
                                           axis=-1)[:, 0]
                ce = jnp.sum((logz - gold) * lmask) / \
                    jnp.maximum(jnp.sum(lmask), 1)
                return ce, _accuracy(logits, labels, lmask > 0)

            (loss, acc), grads = jax.value_and_grad(loss_fn,
                                                    has_aux=True)(params)
            grads, _ = clip_by_global_norm(grads, tcfg.grad_clip)
            params, opt_state = adamw_update(grads, opt_state, params,
                                             lr=tcfg.lr, b1=0.9, b2=0.999,
                                             weight_decay=tcfg.weight_decay)
            return params, opt_state, loss, acc

        return step

    def fit(self, epochs: Optional[int] = None):
        out = []
        for _ in range(epochs or self.tcfg.epochs):
            self.rng.shuffle(self.train_nodes)
            for lo in range(0, len(self.train_nodes), self.bs):
                seeds = self.train_nodes[lo: lo + self.bs]
                layers, base = self._sample_batch(seeds)
                x_rows = self._x[jnp.asarray(np.where(base >= 0, base,
                                                      self.g.num_nodes))]
                layer_data = [(jnp.asarray(d), jnp.asarray(s), jnp.asarray(w))
                              for d, s, w in layers]
                seeds_pad = np.zeros(self.caps[0], np.int64)
                seeds_pad[:len(seeds)] = seeds
                lmask = jnp.asarray((np.arange(self.caps[0]) < len(seeds))
                                    .astype(np.float32))
                labels = self._y[jnp.asarray(seeds_pad)]
                self.params, self.opt_state, loss, acc = self._step(
                    self.params, self.opt_state, x_rows, layer_data, labels,
                    lmask)
                out.append({"loss": float(loss), "acc": float(acc)})
        return out

    def evaluate(self) -> Dict[str, float]:
        """Exact full-graph inference (no sampling at test time)."""
        dst, src, w = gcn_edge_weights(self.g)
        h = jnp.asarray(self.g.x)
        for ell in range(self.L):
            dummy = jnp.zeros((1, h.shape[-1]), h.dtype)
            h_all = jnp.concatenate([h, dummy], axis=0)
            h = L.gcn(self.params["layers"][ell], h_all,
                      (jnp.asarray(dst), jnp.asarray(src)), jnp.asarray(w),
                      self.g.num_nodes)
            if ell != self.L - 1:
                h = jax.nn.relu(h)
        y = jnp.asarray(self.g.y)
        return {f"{n}_acc": float(_accuracy(h, y, jnp.asarray(m)))
                for n, m in (("train", self.g.train_mask),
                             ("val", self.g.val_mask),
                             ("test", self.g.test_mask))}


# ---------------------------------------------------------------------------
# SGC: non-trainable propagation + linear head
# ---------------------------------------------------------------------------

class SGCTrainer:
    def __init__(self, graph: Graph, k: int = 2,
                 tcfg: Optional[TrainConfig] = None):
        tcfg = TrainConfig() if tcfg is None else tcfg
        self.g, self.tcfg = graph, tcfg
        dst, src, w = gcn_edge_weights(graph)
        x = jnp.asarray(graph.x)
        for _ in range(k):   # Â^k X precomputed once (decoupled propagation)
            msg = x[jnp.asarray(src)] * jnp.asarray(w)[:, None]
            x = jax.ops.segment_sum(msg, jnp.asarray(dst),
                                    num_segments=graph.num_nodes)
        self.features = x
        key = jax.random.key(tcfg.seed)
        self.params = {"w": L._glorot(key, (graph.x.shape[1],
                                            graph.num_classes)),
                       "b": jnp.zeros((graph.num_classes,))}
        self.opt_state = adamw_init(self.params)
        self._y = jnp.asarray(graph.y)
        self._m = jnp.asarray(graph.train_mask)
        self._step = jax.jit(self._make_step())

    def _make_step(self):
        tcfg = self.tcfg

        def step(params, opt_state, x, y, m):
            def loss_fn(p):
                logits = x @ p["w"] + p["b"]
                logz = jax.scipy.special.logsumexp(logits, axis=-1)
                gold = jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0]
                return jnp.sum((logz - gold) * m) / jnp.maximum(jnp.sum(m), 1)

            loss, grads = jax.value_and_grad(loss_fn)(params)
            params, opt_state = adamw_update(grads, opt_state, params,
                                             lr=tcfg.lr,
                                             weight_decay=tcfg.weight_decay)
            return params, opt_state, loss

        return step

    def fit(self, epochs: Optional[int] = None):
        for _ in range(epochs or self.tcfg.epochs):
            self.params, self.opt_state, _ = self._step(
                self.params, self.opt_state, self.features, self._y, self._m)

    def evaluate(self) -> Dict[str, float]:
        logits = self.features @ self.params["w"] + self.params["b"]
        return {f"{n}_acc": float(_accuracy(logits, self._y, jnp.asarray(m)))
                for n, m in (("train", self.g.train_mask),
                             ("val", self.g.val_mask),
                             ("test", self.g.test_mask))}
