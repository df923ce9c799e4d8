"""History pull (row gather) Pallas kernels.

Rows move in their aligned 8-row HBM tile and are picked out in VMEM
(`tiles.GROUP`, `tiles.pick_row`).

`gather_rows` moves GROUP output rows per grid step. The table is passed
GROUP times, each operand with a BlockSpec whose index_map selects the
tile group of one of the step's rows from the scalar-prefetched index
vector, so Pallas's automatic double buffering overlaps the next step's
tile DMAs with this step's picks, and Pallas handles the table's partial
last tile. With `scales` the table holds symmetric per-row int8 rows
(`core.history.quantize_rows`) and the dequant multiply runs on the VPU
after the pick: only int8 bytes cross HBM for the table.

`gather_rows_vq` is the codebook variant: uint8 code rows [N, S] move the
same way and are decoded in VMEM against the resident codebook.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .tiles import GROUP, pick_row, vq_decode_tile

def tile_specs(block_w: int, n_lane_blocks: bool):
    """GROUP BlockSpecs over one table: spec j fetches the tile group of
    row idx[g * GROUP + j] (lane block d when `n_lane_blocks`)."""
    def spec(j):
        if n_lane_blocks:
            return pl.BlockSpec(
                (GROUP, block_w),
                lambda g, d, idx, *_: (idx[g * GROUP + j] // GROUP, d))
        return pl.BlockSpec(
            (GROUP, block_w),
            lambda g, idx, *_: (idx[g * GROUP + j] // GROUP, 0))
    return [spec(j) for j in range(GROUP)]


def _pad_idx(idx):
    M = idx.shape[0]
    Mp = max(-(-M // GROUP) * GROUP, GROUP)
    return jnp.pad(idx, (0, Mp - M)) if Mp != M else idx


def _gather_kernel(*refs, dq: bool):
    idx_ref = refs[0]
    scl_ref = refs[1] if dq else None
    tiles, out_ref = refs[1 + dq:-1], refs[-1]
    g = pl.program_id(0)
    rows = []
    for j, tile in enumerate(tiles):
        i = g * GROUP + j
        row = pick_row(tile[...], idx_ref[i] % GROUP)
        if dq:
            row = row * scl_ref[i]
        rows.append(row)
    out_ref[...] = jnp.concatenate(rows, axis=0).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bd", "interpret"))
def gather_rows(table: jnp.ndarray, idx: jnp.ndarray,
                scales: jnp.ndarray = None, *, bd: int = 128,
                interpret: bool) -> jnp.ndarray:
    """out[i] = table[idx[i]], or with `scales` [N] f32 the dequantizing
    pull out[i] = table[idx[i]] * scales[idx[i]] in f32. idx must be
    pre-clipped to [0, N); table's feature dim must be a multiple of bd.
    Rows move in aligned tile groups (module docstring)."""
    N, D = table.shape
    M = idx.shape[0]
    assert D % bd == 0, (D, bd)
    idx_p = _pad_idx(idx)
    prefetch = (idx_p,)
    out_dtype = table.dtype
    if scales is not None:
        assert scales.shape == (N,), (scales.shape, N)
        # per-OUTPUT-row scales: the SMEM operand grows with M, not N
        prefetch += (jnp.take(scales, idx_p, mode="clip"),)
        out_dtype = jnp.float32
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(idx_p.shape[0] // GROUP, D // bd),
        in_specs=tile_specs(bd, True),
        out_specs=pl.BlockSpec((GROUP, bd), lambda g, d, *_: (g, d)),
    )
    out = pl.pallas_call(
        functools.partial(_gather_kernel, dq=scales is not None),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((idx_p.shape[0], D), out_dtype),
        interpret=interpret,
    )(*prefetch, *([table] * GROUP))
    return out[:M]


def _vq_gather_kernel(idx_ref, scl_ref, *refs, dp: int):
    tiles, cb_ref, out_ref = refs[:GROUP], refs[GROUP], refs[GROUP + 1]
    g = pl.program_id(0)
    codes = jnp.concatenate(
        [pick_row(tile[...], idx_ref[g * GROUP + j] % GROUP)
         for j, tile in enumerate(tiles)], axis=0)          # [GROUP, S]
    rec = vq_decode_tile(codes, cb_ref[...])
    svec = jnp.concatenate(
        [jnp.full((1, 1), scl_ref[g * GROUP + j], jnp.float32)
         for j in range(GROUP)], axis=0)
    out_ref[...] = jnp.pad(rec * svec, ((0, 0), (0, dp - rec.shape[1])))


@functools.partial(jax.jit, static_argnames=("interpret",))
def gather_rows_vq(table: jnp.ndarray, codebook: jnp.ndarray,
                   scales: jnp.ndarray, idx: jnp.ndarray, *,
                   interpret: bool) -> jnp.ndarray:
    """out[i] = decode(table[idx[i]], codebook) * scales[idx[i]] in f32 —
    the codebook-dequantizing gather (`history_dtype="vq"`). table [N, S]
    uint8 codes, codebook [S, C, ds] f32, scales [N] f32, idx pre-clipped
    to [0, N). Only code bytes cross HBM; the f32 row is born in VMEM.
    The codebook rides as a whole-VMEM operand, resident across the grid.
    Returns [M, Dp] with d = S*ds zero-padded to a 128-lane multiple —
    callers slice `[:, :d]`."""
    N, S = table.shape
    s_, c, ds = codebook.shape
    M = idx.shape[0]
    assert s_ == S, (s_, S)
    assert scales.shape == (N,), (scales.shape, N)
    Dp = max(-(-(S * ds) // 128) * 128, 128)
    idx_p = _pad_idx(idx)
    rscl = jnp.take(scales, idx_p, mode="clip")
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(idx_p.shape[0] // GROUP,),
        in_specs=tile_specs(S, False) + [
            pl.BlockSpec((S, c, ds), lambda g, *_: (0, 0, 0))],
        out_specs=pl.BlockSpec((GROUP, Dp), lambda g, *_: (g, 0)),
    )
    out = pl.pallas_call(
        functools.partial(_vq_gather_kernel, dp=Dp),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((idx_p.shape[0], Dp), jnp.float32),
        interpret=interpret,
    )(idx_p, rscl, *([table] * GROUP), codebook)
    return out[:M]
