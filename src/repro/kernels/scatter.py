"""History push (row scatter) Pallas kernels — the dual of `gather.py`.

A DMA cannot write one row into a tiled HBM table either (see
`tiles.py`), so the push is a read-modify-write of the row's aligned
8-row tile.

`scatter_rows` carries a chunk of C pushed rows per grid step, at full
table width (`chunk_rows`: at most `CHUNK` rows, fewer where their VMEM
would pass `VMEM_BUDGET`). The wrapper sorts the rows by table index
(stably), so rows that share a tile are consecutive, and the chunk's
value rows arrive as one pipelined (C, D) block. The table stays whole in
HBM (`pl.ANY`), aliased to the output (`input_output_aliases`): when the
caller donates it (the train step donates histories) the push is in
place, and every tile no row touches keeps its historical values. A step

  1. starts one DMA per distinct tile its rows touch, HBM -> a VMEM slot
     of its own (one slot per tile, never per row: two copies of a tile
     in flight would lose a row), all before the first wait;
  2. merges each row into its tile's slot, in row order, with the
     bitwise select of `tiles.pick_row`'s kind (-0.0 and NaN payloads
     survive);
  3. writes each slot back with one DMA, started as soon as the slot's
     last row is merged, and waits for all of them before the step
     ends, so a tile whose rows straddle two chunks is re-read by the
     next step after this one wrote it.

No DMA touches the table's partial last tile (rows past N // 8 * 8): the
kernel skips those rows, and the wrapper sets the last occurrence of each
with an XLA `.at[].set` of at most 7 rows on the kernel's output. Rows
whose index lies outside [0, N) are dropped and cost no DMA; callers
point masked rows there (`kernels/ops.push_rows`).

Duplicate indices resolve to the LAST occurrence in row order: the
stable sort keeps their order, the merge runs in it, and the grid is
sequential — deterministic, unlike raw XLA scatter. GAS batches never
contain duplicates (each node is in one cluster).

With `scales` (quantizing mode) the f32 value rows stream through VMEM
with their per-row scales beside them as a column (precomputed by one
cheap jnp row-max, `core.history.quantize_rows` semantics); the
symmetric divide-round-clip to int8 runs on the VPU over the whole chunk
at once, before the merge, and only int8 tiles move back.

`scatter_rows_vq` is the codebook dual of `gather.gather_rows_vq`, one
grid step per row: grid step i's output BlockSpec maps to the tile of
sorted row i, the first step on a tile copies the table's tile in, every
step overwrites its own row, and Pallas writes the tile back once the
next step moves to another tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .tiles import GROUP, pick_row

# pushed rows one `scatter_rows` grid step carries, at most, and the VMEM
# their tile slots and value rows may fill (under Mosaic's default 16 MiB
# scoped limit)
CHUNK = 256
VMEM_BUDGET = 12 * 2**20


def chunk_rows(m: int, d: int) -> int:
    """Rows C of each `scatter_rows` grid step for m pushed rows of width
    d: the fewest steps of at most `CHUNK` rows that fit `VMEM_BUDGET`,
    balanced, in whole GROUPs. A row takes a (GROUP, d) tile slot, padded
    to 32-bit sublanes and 128 lanes in every dtype, two f32 value rows,
    and, quantizing, its quantized row and two lane-padded scales."""
    per_row = (GROUP + 3) * 4 * (-(-d // 128) * 128) + 2 * 4 * 128
    cap = max(GROUP, min(CHUNK, VMEM_BUDGET // per_row) // GROUP * GROUP)
    steps = max(1, -(-m // cap))
    return -(-m // (steps * GROUP)) * GROUP


def _sorted_rows(idx, values, scales, mult=GROUP, fill=0):
    """Stable sort of the pushed rows by table index, padded to a multiple
    of `mult` rows: padding indices read `fill`, values 0, scales 1."""
    pad = -idx.shape[0] % mult
    order = jnp.argsort(idx, stable=True)
    vals = jnp.pad(jnp.take(values, order, axis=0), ((0, pad), (0, 0)))
    prefetch = (jnp.pad(jnp.take(idx, order), (0, pad),
                        constant_values=fill),)
    if scales is not None:
        prefetch += (jnp.pad(jnp.take(scales, order), (0, pad),
                             constant_values=1.0),)
    return prefetch, vals


def _quantize(row, scale):
    # the in-kernel mirror of core.history.quantize_rows' round/clip —
    # keep in lockstep (scales themselves come from history.row_scales
    # via ops.push_rows_q, shared with the jnp path)
    return jnp.clip(jnp.round(row / scale), -127.0, 127.0)


def _put_row(ref, sub, row):
    """Overwrite row `sub` of the (GROUP, w) tile in `ref` with the [1, w]
    row by a select: -0.0 and NaN payloads survive."""
    cur = ref[...]
    wide = cur.astype(row.dtype)
    rows = jax.lax.broadcasted_iota(jnp.int32, wide.shape, 0)
    ref[...] = jnp.where(rows == sub, row, wide).astype(cur.dtype)


def _rmw(idx_ref, i, tbl_ref, out_ref, row):
    """Copy the tile in on its first step, then overwrite row idx[i]."""
    t = idx_ref[i]
    prev = idx_ref[jnp.maximum(i - 1, 0)]

    @pl.when((i == 0) | (prev // GROUP != t // GROUP))
    def _load():
        out_ref[...] = tbl_ref[...]

    _put_row(out_ref, t % GROUP, row)


def _unrolled(c, body, init):
    """`fori_loop(0, c, body, init)`, GROUP iterations a loop step (c is
    whole GROUPs): Mosaic unrolls a loop only whole, and the per-row
    scalar work is cheaper without a branch back every row."""
    def step(g, carry):
        for k in range(GROUP):
            carry = body(g * GROUP + k, carry)
        return carry
    return jax.lax.fori_loop(0, c // GROUP, step, init)


def _make_chunk_kernel(*, c, n8, quantize):
    def kernel(*refs):
        idx_ref, vals_ref = refs[:2]
        scl_ref = refs[2] if quantize else None
        (_, out_ref, slot_ref, base_ref, buf_ref,
         sem_ref) = refs[2 + quantize:8 + quantize]
        # the rows to merge: quantized over the whole chunk at once
        rows_ref = refs[-1] if quantize else vals_ref
        if quantize:
            rows_ref[...] = _quantize(vals_ref[...], scl_ref[...])
        i0 = pl.program_id(0) * c

        def tile_dma(s, *, write):
            hbm = out_ref.at[pl.ds(pl.multiple_of(base_ref[s], GROUP),
                                   GROUP)]
            if write:
                return pltpu.make_async_copy(buf_ref.at[s], hbm,
                                             sem_ref.at[1])
            return pltpu.make_async_copy(hbm, buf_ref.at[s], sem_ref.at[0])

        def plan(r, carry):
            # give each row the slot of its tile, and start the read of
            # each distinct tile (sorted: a tile's rows are consecutive)
            n, last = carry
            t = idx_ref[i0 + r]
            base = t // GROUP * GROUP
            ok = t < n8
            new = ok & (base != last)

            @pl.when(new)
            def _read():
                base_ref[n] = base
                tile_dma(n, write=False).start()

            n = n + new.astype(jnp.int32)
            slot_ref[r] = jnp.where(ok, n - 1, -1)
            return n, jnp.where(ok, base, last)

        n, _ = _unrolled(c, plan, (jnp.int32(0), jnp.int32(-1)))

        def drain(s, carry, *, write):
            tile_dma(s, write=write).wait()
            return carry

        jax.lax.fori_loop(0, n, functools.partial(drain, write=False), None)

        def merge(r, carry):
            s = slot_ref[r]

            @pl.when(s >= 0)
            def _put():
                _put_row(buf_ref.at[s], idx_ref[i0 + r] % GROUP,
                         rows_ref[pl.ds(r, 1), :])

                # the tile's last row: write it back while the rest merge
                @pl.when((r == c - 1)
                         | (slot_ref[jnp.minimum(r + 1, c - 1)] != s))
                def _write():
                    tile_dma(s, write=True).start()
            return carry

        _unrolled(c, merge, None)
        jax.lax.fori_loop(0, n, functools.partial(drain, write=True), None)
    return kernel


@functools.partial(jax.jit, static_argnames=("interpret",))
def scatter_rows(table: jnp.ndarray, idx: jnp.ndarray,
                 values: jnp.ndarray, scales: jnp.ndarray = None, *,
                 interpret: bool) -> jnp.ndarray:
    """out = table; out[idx[i]] = values[i]. With `scales` the table is
    int8 and out[idx[i]] = int8(round(values[i] / scales[i])) — the
    quantizing scatter; `scales` is the per-PUSHED-row scale vector [M]
    (row i of `values`, NOT table row order; the caller scatters the
    scales into its [N] scale table separately). Rows whose index lies
    outside [0, N) are dropped; duplicates resolve to the last. Chunks of
    `chunk_rows` rows a grid step (module docstring)."""
    N, D = table.shape
    M = idx.shape[0]
    assert values.shape == (M, D), (values.shape, (M, D))
    quantize = scales is not None
    if quantize:
        assert table.dtype == jnp.int8, table.dtype
        assert scales.shape == (M,), (scales.shape, M)
        values = values.astype(jnp.float32)
    else:
        # 32-bit value rows, so the kernel reads one at any offset; the
        # round trip through the table dtype is exact
        values = values.astype(table.dtype).astype(jnp.float32)
    c = chunk_rows(M, D)
    n8 = N // GROUP * GROUP
    idx = jnp.where((idx >= 0) & (idx < N), idx, N).astype(jnp.int32)
    (sidx, *scol), vals = _sorted_rows(idx, values, scales, mult=c, fill=N)
    # the chunk's sorted value rows and, quantizing, their scales as a
    # column; the table stays in HBM
    block = lambda w: pl.BlockSpec((c, w), lambda i, *_: (i, 0))  # noqa: E731
    in_specs = [block(D)] + [block(1)] * quantize
    operands = [vals] + [s[:, None] for s in scol]
    scratch = [pltpu.SMEM((c,), jnp.int32),                     # row slot
               pltpu.SMEM((c,), jnp.int32),                     # slot tile
               pltpu.VMEM((c, GROUP, D), table.dtype),
               pltpu.SemaphoreType.DMA((2,))]
    if quantize:
        scratch.append(pltpu.VMEM((c, D), jnp.float32))  # quantized rows
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(vals.shape[0] // c,),
        in_specs=in_specs + [pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=scratch,
    )
    out = pl.pallas_call(
        _make_chunk_kernel(c=c, n8=n8, quantize=quantize),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((N, D), table.dtype),
        # alias table -> out (after the indices and the chunk operands);
        # the kernel reads tiles through the output, which holds the
        # steps before it
        input_output_aliases={1 + len(operands): 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(sidx, *operands, table)
    if n8 == N:
        return out
    # the partial last tile: each row's last occurrence, by XLA
    tail = jnp.arange(n8, N, dtype=jnp.int32)
    pos = jnp.searchsorted(sidx, tail, side="right") - 1
    rows = jnp.take(vals, pos, axis=0, mode="clip")
    if quantize:
        rows = _quantize(rows, jnp.take(scol[0], pos, mode="clip")[:, None])
    hit = jnp.take(sidx, pos, mode="clip") == tail
    return out.at[jnp.where(hit, tail, N)].set(rows.astype(table.dtype),
                                               mode="drop")


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
