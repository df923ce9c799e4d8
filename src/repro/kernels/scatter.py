"""History push (row scatter) Pallas kernels — the dual of `gather.py`.

A DMA cannot write one row into a tiled HBM table either (see
`tiles.py`), so the push is a read-modify-write of the row's aligned
8-row tile. The wrapper sorts the pushed rows by table index (stably), so
rows that share a tile are consecutive grid steps: grid step i's output
BlockSpec maps to the tile of sorted row i, the first step on a tile
copies the table's tile in, every step overwrites its own row, and Pallas
writes the tile back once the next step moves to another tile.
`input_output_aliases` donates the table into the output, so every tile
no row touches keeps its historical values.

Semantics (matching `core/history.push`):
  * masked rows must be pre-redirected to a trash row by the caller
    (`kernels/ops.push_rows` appends one and slices it off afterwards);
  * duplicate indices resolve to the LAST occurrence in row order (the
    stable sort keeps their order, and the sequential grid makes this
    deterministic, unlike raw XLA scatter). GAS batches never contain
    duplicates — each node is in one cluster.

With `scales` (`scatter_rows` quantizing mode) the f32 value rows stream
through VMEM, the symmetric divide-round-clip to int8 happens on the VPU
against the scalar-prefetched per-row scales (precomputed by one cheap
jnp row-max, `core.history.quantize_rows` semantics), and only int8
tiles are written back. `scatter_rows_vq` is the codebook dual of
`gather.gather_rows_vq`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .tiles import GROUP, pick_row


def _sorted_rows(idx, values, scales):
    """Stable sort of the pushed rows by table index; values are padded
    to a whole number of GROUP-row blocks."""
    M = idx.shape[0]
    order = jnp.argsort(idx, stable=True)
    Mp = -(-M // GROUP) * GROUP
    vals = jnp.pad(jnp.take(values, order, axis=0),
                   ((0, Mp - M), (0, 0)))
    prefetch = (jnp.take(idx, order),)
    if scales is not None:
        prefetch += (jnp.take(scales, order),)
    return prefetch, vals


def _rmw(idx_ref, i, tbl_ref, out_ref, row):
    """Copy the tile in on its first step, then overwrite row idx[i]."""
    t = idx_ref[i]
    prev = idx_ref[jnp.maximum(i - 1, 0)]

    @pl.when((i == 0) | (prev // GROUP != t // GROUP))
    def _load():
        out_ref[...] = tbl_ref[...]

    cur = out_ref[...]
    wide = cur.astype(row.dtype)
    rows = jax.lax.broadcasted_iota(jnp.int32, wide.shape, 0)
    out_ref[...] = jnp.where(rows == t % GROUP, row, wide).astype(cur.dtype)


def _scatter_kernel(*refs, quantize: bool):
    idx_ref = refs[0]
    scl_ref = refs[1] if quantize else None
    vals_ref, tbl_ref, out_ref = refs[1 + quantize:]
    i = pl.program_id(1)
    row = pick_row(vals_ref[...], i % GROUP)
    if quantize:
        # the in-kernel mirror of core.history.quantize_rows' round/clip —
        # keep in lockstep (scales themselves come from
        # history.row_scales via ops.push_rows_q, shared with the jnp path)
        row = jnp.clip(jnp.round(row / scl_ref[i]), -127.0, 127.0)
    _rmw(idx_ref, i, tbl_ref, out_ref, row)


@functools.partial(jax.jit, static_argnames=("bd", "interpret"))
def scatter_rows(table: jnp.ndarray, idx: jnp.ndarray,
                 values: jnp.ndarray, scales: jnp.ndarray = None, *,
                 bd: int = 128, interpret: bool) -> jnp.ndarray:
    """out = table; out[idx[i]] = values[i]. With `scales` the table is
    int8 and out[idx[i]] = int8(round(values[i] / scales[i])) — the
    quantizing scatter; `scales` is the per-PUSHED-row scale vector [M]
    (row i of `values`, NOT table row order; the caller scatters the
    scales into its [N] scale table separately). idx must be pre-clipped
    to [0, N); rows to drop must point at a sacrificial row. table's
    feature dim must be a multiple of bd."""
    N, D = table.shape
    M = idx.shape[0]
    assert values.shape == (M, D), (values.shape, (M, D))
    assert D % bd == 0, (D, bd)
    if scales is not None:
        assert table.dtype == jnp.int8, table.dtype
        assert scales.shape == (M,), (scales.shape, M)
        values = values.astype(jnp.float32)
    else:
        values = values.astype(table.dtype)
    prefetch, vals = _sorted_rows(idx, values, scales)
    tile = lambda d, i, idx, *_: (idx[i] // GROUP, d)          # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(D // bd, M),
        in_specs=[
            pl.BlockSpec((GROUP, bd), lambda d, i, *_: (i // GROUP, d)),
            pl.BlockSpec((GROUP, bd), tile),
        ],
        out_specs=pl.BlockSpec((GROUP, bd), tile),
    )
    return pl.pallas_call(
        functools.partial(_scatter_kernel, quantize=scales is not None),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((N, D), table.dtype),
        # alias table -> out (after the scalar-prefetch operands and the
        # value rows): untouched tiles keep their historical values; when
        # the caller's table buffer is donated (the train step donates
        # histories) XLA performs the push in place
        input_output_aliases={len(prefetch) + 1: 0},
        interpret=interpret,
    )(*prefetch, vals, table)


def _vq_kernel(idx_ref, scl_ref, cb_ref, vals_ref, tbl_ref, out_ref):
    # the in-kernel mirror of core.history.vq_encode_rows' nearest-entry
    # search — keep in lockstep (scales themselves come from
    # history.vq_row_scales via ops.push_rows_vq, shared with the jnp
    # path). One subvector at a time: Mosaic cannot reshape the [1, d] row
    # into [S, 1, ds]. argmin is spelled min-then-first-index (same ties).
    i = pl.program_id(1)
    s, c, ds = cb_ref.shape
    u = pick_row(vals_ref[...], i % GROUP) / scl_ref[i]
    entries = jax.lax.broadcasted_iota(jnp.int32, (c, 1), 0)
    lanes = jax.lax.broadcasted_iota(jnp.int32, (1, s), 1)
    codes = jnp.zeros((1, s), jnp.int32)
    for sub in range(s):
        d2 = jnp.sum(jnp.square(u[:, sub * ds:(sub + 1) * ds] - cb_ref[sub]),
                     axis=-1, keepdims=True)                  # [C, 1]
        best = jnp.min(d2, axis=0, keepdims=True)
        code = jnp.min(jnp.where(d2 == best, entries, c), axis=0,
                       keepdims=True)
        codes = jnp.where(lanes == sub, code, codes)
    _rmw(idx_ref, i, tbl_ref, out_ref, codes)


@functools.partial(jax.jit, static_argnames=("interpret",))
def scatter_rows_vq(table: jnp.ndarray, idx: jnp.ndarray,
                    values: jnp.ndarray, scales: jnp.ndarray,
                    codebook: jnp.ndarray, *,
                    interpret: bool) -> jnp.ndarray:
    """out = table; out[idx[i]] = vq_encode(values[i] / scales[i]) — the
    codebook-quantizing scatter (`history_dtype="vq"`). The
    nearest-codebook-entry search runs on the VPU between the value-row
    DMA and the uint8 code write-back, so only code tiles are written to
    HBM. `values` may be column-padded past d = S*ds (the kernel slices);
    `scales` is the per-PUSHED-row normalizer [M] from
    `history.vq_row_scales`; the codebook rides as a whole-VMEM operand.
    Same index contract as `scatter_rows`."""
    N, S = table.shape
    s_, c, ds = codebook.shape
    M = idx.shape[0]
    assert table.dtype == jnp.uint8, table.dtype
    assert s_ == S, (s_, S)
    assert values.shape[0] == M and values.shape[1] >= S * ds, \
        (values.shape, M, S * ds)
    assert scales.shape == (M,), (scales.shape, M)
    prefetch, vals = _sorted_rows(idx, values.astype(jnp.float32), scales)
    tile = lambda d, i, idx, *_: (idx[i] // GROUP, 0)          # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(1, M),
        in_specs=[
            pl.BlockSpec((S, c, ds), lambda d, i, *_: (0, 0, 0)),
            pl.BlockSpec((GROUP, vals.shape[1]),
                         lambda d, i, *_: (i // GROUP, 0)),
            pl.BlockSpec((GROUP, S), tile),
        ],
        out_specs=pl.BlockSpec((GROUP, S), tile),
    )
    return pl.pallas_call(
        _vq_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((N, S), jnp.uint8),
        # alias table -> out (after the two scalar-prefetch operands, the
        # codebook, and the value rows)
        input_output_aliases={4: 0},
        interpret=interpret,
    )(*prefetch, codebook, vals, table)
