"""Typed GAS batch structures: `GASBatch` + `BlockStructure` pytrees.

`GASBatch` is the single carrier for everything a GAS mini-batch step
needs to know about one padded cluster batch (or the whole stacked set of
them): node/halo index sets, the padded local COO, and up to four BCSR
block families, each a `BlockStructure`:

  * ``forward``          — GCN-normalized weights, [max_b, max_b+max_h+1]
  * ``transposed``       — the same adjacency transposed (backward-on-MXU)
  * ``unit``             — unit-weight (edge-multiplicity) values for the
                           ops that never read the normalized weights
                           (GIN's sum, GAT's edge softmax, PNA's reduce)
  * ``unit_transposed``  — its transpose

Both classes are frozen dataclasses registered as JAX pytrees: arrays are
leaves, the static pads/counts (`num_batches`/`max_b`/`max_h`/`max_e`/
`bn`) are hashable aux data. That buys, for free, everything the raw dict
needed ad-hoc plumbing for:

  * per-batch slicing is `jax.tree_util.tree_map(lambda a: a[b], stacked)`
    (or `stacked[b]`) — aux data rides along unchanged;
  * `jax.lax.scan` can scan a stacked `GASBatch` directly (fused epochs,
    `predict`);
  * two same-shaped batches share one jit trace, while presence/absence of
    a block family changes the treedef and correctly forces a re-trace;
  * feature gates are typed (`batch.transposed is not None`) instead of
    stringly (`"blk_vals_t" in batch`).

Leaves may be numpy (host side, as built by `core.gas.build_batches`) or
jnp arrays (`device()` / `device_batch()`). The legacy dict layout (and
its one-release `coerce_batch` deprecation shim) is gone — `GASBatch` is
the only batch type the executors accept.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, replace
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def _nbytes(a) -> int:
    if a is None:
        return 0
    return int(np.prod(a.shape)) * np.dtype(a.dtype).itemsize


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=["vals", "cols"], meta_fields=[])
@dataclass(frozen=True)
class BlockStructure:
    """One BCSR family: dense `vals` [..., R, K, bn, bn] at column blocks
    `cols` [..., R, K] (padding slots: all-zero blocks at column 0). The
    unit families share their `cols` arrays with the weighted ones when
    both exist — `cols` describes structure, `vals` the family."""
    vals: Any
    cols: Any

    @property
    def bn(self) -> int:
        return int(self.vals.shape[-1])

    def bytes(self) -> int:
        return _nbytes(self.vals) + _nbytes(self.cols)


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=["batch_nodes", "batch_mask", "halo_nodes", "halo_mask",
                 "edge_dst", "edge_src", "edge_w", "forward", "transposed",
                 "unit", "unit_transposed"],
    meta_fields=["num_batches", "max_b", "max_h", "max_e", "bn"])
@dataclass(frozen=True)
class GASBatch:
    """Padded per-cluster GAS batch (leading batch axis optional).

    Stacked form (from `core.gas.build_batches`): every array leaf has a
    leading `num_batches` axis. Single-batch form (`batch = stacked[b]`):
    that axis is sliced away; the static aux fields keep describing the
    per-batch padded shapes either way. Index conventions match the old
    dict: `batch_nodes`/`halo_nodes` are global ids padded with N,
    `edge_dst` is local in [0, max_b) (pad -> trash row max_b),
    `edge_src` is local in [0, max_b+max_h] (pad -> dummy zero row)."""
    batch_nodes: Any             # [*, max_b] int32, padded with N
    batch_mask: Any              # [*, max_b] bool
    halo_nodes: Any              # [*, max_h] int32, padded with N
    halo_mask: Any               # [*, max_h] bool
    edge_dst: Any                # [*, max_e] int32
    edge_src: Any                # [*, max_e] int32
    edge_w: Any                  # [*, max_e] float32, 0 for padding
    forward: Optional[BlockStructure] = None
    transposed: Optional[BlockStructure] = None
    unit: Optional[BlockStructure] = None
    unit_transposed: Optional[BlockStructure] = None
    num_batches: int = 1
    max_b: int = 0
    max_h: int = 0
    max_e: int = 0
    bn: int = 128

    # -- views ------------------------------------------------------------
    @property
    def blocks(self) -> Optional[Tuple]:
        """Weighted-SpMM block tuple for `kernels.ops`: (vals, cols[,
        vals_t, cols_t]) — the 4-tuple keeps the backward on the MXU."""
        if self.forward is None:
            return None
        out = (self.forward.vals, self.forward.cols)
        if self.transposed is not None:
            out += (self.transposed.vals, self.transposed.cols)
        return out

    @property
    def ublocks(self) -> Optional[Tuple]:
        """Unit-weight (multiplicity) 4-tuple for the GIN/GAT/PNA kernels.
        Unit blocks are only ever built alongside their transpose
        (`core.gas.build_batches`), so this is always a 4-tuple."""
        if self.unit is None:
            return None
        return (self.unit.vals, self.unit.cols,
                self.unit_transposed.vals, self.unit_transposed.cols)

    # -- movement / slicing ------------------------------------------------
    def device(self) -> "GASBatch":
        """All leaves to device arrays (aux unchanged)."""
        return jax.tree_util.tree_map(jnp.asarray, self)

    def __getitem__(self, b) -> "GASBatch":
        """Slice one batch off the leading axis of every leaf. An integer
        index also resets the `num_batches` aux field, so any two
        single-batch views share one treedef (and thus one jit trace)."""
        out = jax.tree_util.tree_map(lambda a: a[b], self)
        if isinstance(b, (int, np.integer)):
            out = replace(out, num_batches=1)
        return out

    def device_batch(self, b: int) -> "GASBatch":
        """Host-side slice first, then upload ONE batch (never the whole
        stack — the block-value buffers dominate)."""
        return self[b].device()

    # -- accounting --------------------------------------------------------
    def structural_bytes(self) -> Dict[str, int]:
        """Host/device bytes of each structure family (whole stack)."""
        out = {
            "nodes": sum(_nbytes(a) for a in
                         (self.batch_nodes, self.batch_mask,
                          self.halo_nodes, self.halo_mask)),
            "coo": sum(_nbytes(a) for a in
                       (self.edge_dst, self.edge_src, self.edge_w)),
        }
        for name in ("forward", "transposed", "unit", "unit_transposed"):
            s = getattr(self, name)
            out[f"blocks_{name}"] = s.bytes() if s is not None else 0
        out["total"] = sum(out.values())
        return out

    def replace(self, **kw) -> "GASBatch":
        return replace(self, **kw)


def ensure_batch(batch: GASBatch) -> GASBatch:
    """Type guard for the forward's entry: `GASBatch` is the only
    accepted batch type."""
    if not isinstance(batch, GASBatch):
        raise TypeError(
            f"expected core.batch.GASBatch, got {type(batch)} (the legacy "
            "dict shim was removed; build_batches returns a GASBatch)")
    return batch
