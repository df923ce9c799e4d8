"""Block-CSR SpMM Pallas TPU kernel — the GAS aggregation hot-spot.

TPU adaptation of the paper's sparse neighbor aggregation (DESIGN.md §4):
instead of a GPU gather-scatter (VPU/scalar-bound on TPU), the adjacency is
tiled into bn x bn node blocks. METIS clustering makes the matrix block-
diagonally dominant, so only the (few) non-empty blocks are stored, and each
becomes a dense bn x bn @ bn x bd MXU matmul accumulated in VMEM.

Layout:
  x         [Ncols*bn, D]      node features (zero-padded)
  blk_vals  [R, K, bn, bn]     dense adjacency blocks, zero-padded to K
  blk_cols  [R, K] int32       column-block index per block (scalar-prefetch)
  out       [R*bn, D]

Grid (R, D/bd, K): K innermost accumulates into the same VMEM out tile;
blk_cols drives the x BlockSpec index_map (runtime-prefetched scalars).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(cols_ref, x_ref, vals_ref, out_ref):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    block = vals_ref[0, 0]                      # [bn, bn]
    xblk = x_ref[...]                           # [bn, bd]
    # fp32 accumulation regardless of input dtype (MXU-native)
    out_ref[...] += jnp.dot(block, xblk, preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("bn", "bd", "interpret"))
def bcsr_spmm(x: jnp.ndarray, blk_vals: jnp.ndarray, blk_cols: jnp.ndarray,
              *, bn: int = 128, bd: int = 128,
              interpret: bool) -> jnp.ndarray:
    """See module docstring. interpret=True runs the Pallas interpreter
    (CPU); interpret=False compiles the kernel for the TPU."""
    R, K, bn_, bn2 = blk_vals.shape
    assert bn_ == bn and bn2 == bn, (blk_vals.shape, bn)
    N, D = x.shape
    assert N % bn == 0 and D % bd == 0, (x.shape, bn, bd)

    grid = (R, D // bd, K)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, bd), lambda i, d, k, cols: (cols[i, k], d)),
            pl.BlockSpec((1, 1, bn, bn), lambda i, d, k, cols: (i, k, 0, 0)),
        ],
        out_specs=pl.BlockSpec((bn, bd), lambda i, d, k, cols: (i, d)),
    )
    out = pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((R * bn, D), jnp.float32),
        interpret=interpret,
    )(blk_cols, x, blk_vals)
    return out.astype(x.dtype)
