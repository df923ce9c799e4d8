"""In-VMEM tile helpers shared by the Pallas kernels.

A TPU lays a 2-D HBM array out in tiles of 8 rows (times 128 lanes), and
a DMA cannot cut one row out of a tile: Mosaic refuses a 1-row slice of
a tiled table ("Slice shape along dimension 0 must be aligned to tiling
(8)"). So rows move in their aligned tile GROUP — row t lives in rows
[t - t % 8, t - t % 8 + 8) — and `pick_row` selects the row out of the
staged tile in VMEM. That reads GROUP x the row's own bytes from HBM:
4 KiB per 128 lanes for an f32 table, 1 KiB for int8. A push writes
rows back the same way, as a read-modify-write of their tile:
`scatter.scatter_rows` stages each distinct tile a chunk of sorted rows
touches once, merges the chunk's rows into it, and writes it back.

A block's last two dims must be (8, 128)-aligned or whole, so per-node
vectors arrive as rows; `to_col` / `to_row` swap a vector's orientation
with one n x n transpose.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

GROUP = 8  # rows per HBM tile: the sublane tiling of every table dtype


def pick_row(tile: jnp.ndarray, sub) -> jnp.ndarray:
    """Row `sub` of a [GROUP, w] tile as a [1, w] 32-bit row, bit-exact:
    floats and int8 widen to f32, uint8 to int32. The select runs on the
    integer bit patterns, so -0.0 and NaN payloads survive."""
    wide = tile.astype(jnp.int32 if tile.dtype == jnp.uint8
                       else jnp.float32)
    bits = jax.lax.bitcast_convert_type(wide, jnp.int32)
    rows = jax.lax.broadcasted_iota(jnp.int32, bits.shape, 0)
    row = jnp.sum(jnp.where(rows == sub, bits, 0), axis=0, keepdims=True)
    return jax.lax.bitcast_convert_type(row, wide.dtype)


def to_col(row):
    """[1, n] -> [n, n] with out[a, b] = row[0, a] (exact)."""
    n = row.shape[-1]
    return jnp.transpose(jnp.broadcast_to(row, (n, n)))


def to_row(col):
    """[n, 1] -> [1, n] (exact)."""
    n = col.shape[0]
    return jnp.transpose(jnp.broadcast_to(col, (n, n)))[0:1, :]


def vq_decode_tile(codes, cb):
    """[m, S] int32 codes -> [m, S*ds] f32 via one one-hot matmul per
    subvector: every output element is exactly one codebook element * 1.0
    plus exact zeros, so this is bitwise `core.history.vq_decode_rows`.
    HIGHEST precision keeps the codebook operand f32 on the MXU (at the
    default precision a TPU rounds f32 dot operands to bf16)."""
    s, c, _ = cb.shape
    iota_c = jax.lax.broadcasted_iota(jnp.int32, (codes.shape[0], c), 1)
    return jnp.concatenate(
        [jnp.dot((codes[:, sub][:, None] == iota_c).astype(jnp.float32),
                 cb[sub], preferred_element_type=jnp.float32,
                 precision=jax.lax.Precision.HIGHEST)
         for sub in range(s)], axis=1)
