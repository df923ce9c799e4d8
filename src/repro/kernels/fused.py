"""Fused history-gather + block-CSR SpMM Pallas kernel (GAS aggregation).

The unfused GAS layer materializes

    x_all = concat([x_in, pull(table, halo_nodes) * halo_mask, 0])

and then runs the BCSR SpMM over x_all — a full halo gather plus a full
concatenate copy of the layer input, per layer, per batch, that exist only
to be read once by the matmul. This kernel removes both: the virtual x_all
never exists in HBM. A *gather plan* (`gather_plan`, one entry per
virtual row) says where row `v = c * bn + row` of column block c actually
lives:

    in-batch                     -> x_in[v]      (layer activations)
    halo                         -> table[trow]  (historical embedding,
                                    read straight out of the history table)
    masked halo, dummy, padding  -> exact zeros

Panel path. x_all has only Ncols = ceil((n_in + max_h + 1) / bn) column
blocks, and nearly every row block reads nearly all of them, so each
column block is staged once per call and feature tile into a VMEM panel
[Ncols, bn, pw] f32. Grid (D/pw, R, K), every dimension sequential: step
(d, 0, 0) stages all Ncols blocks of feature tile d, and every step then
only contracts `out[r] += vals[r, k] @ panel[blk_cols[r, k]]`. The
plan's table rows `trow` are [Ncols, bn], a function of n_in and the
halo alone, held whole in SMEM; the route's per-row vectors (route
codes, and `rscl` below) are [Ncols, bn] VMEM rows. The panel takes Ncols * bn * pw * 4 bytes of
VMEM: 81 blocks of 128 rows at pw = 256 are 10.6 MB. `panel_width`
takes pw = D where the panel, the staging buffers and the pipelined
blocks fit `VMEM_BUDGET` (one tile-group DMA then serves every feature
tile of a halo row), else pw = bd; the kernel asks Mosaic for that need
(plus `VMEM_HEADROOM`) as its scoped VMEM limit.

Per-block fallback, for shapes whose panel does not fit, or whose plan
does not fit `SMEM_PLAN_BUDGET`: grid (R, D/bd, K), and every step
stages the bn columns of its own adjacency block into a [bn, bd] buffer
before it contracts them, so a column block is staged once per row block
that reads it. Its plan, the column plan's rows of each adjacency block,
is [R, K, bn] and reaches SMEM one (GROUP, bn) block of rows at a time:
SMEM holds 1 MiB, and an [R, K, bn] int32 plan outgrows it at R * K >
~2k blocks (a serving refresh of a 10k-node graph needs ~5k). Its route
rows arrive as one [1, bn] block a step, chosen by `blk_cols`.

Staging is the same on both paths, one column block at a time, with
hand-issued `pltpu.make_async_copy` DMAs — x_in and the history table
stay whole (`pl.ANY`):

  issue    one aligned group DMA per 8 in-batch rows into `sx`, one
           tile-group DMA per halo row into its own GROUP rows of `st`;
           the halo DMAs are counted;
  drain    as many waits on one fixed descriptor of each kind as were
           issued — no plan entry is read;
  unstage  the staged tile groups are widened to 32 bits in one vector
           copy into `wide`, kept as lane tiles of 128 (Mosaic reads
           strided sublanes only of 32-bit buffers 128 lanes wide), and
           the x rows are parked in the block's destination. Both
           staging buffers are then free, and the next block's DMAs
           start: they overlap this block's route;
  route    halo row t is sublane t % 8 of its staged group, so GROUP
           strided sublane reads of `wide` give every row's candidates
           and a select on the row's route code keeps its own. The rows
           of the table's partial last tile, the dequant multiply, the
           zeros and the parked x rows are whole-block selects too, and
           the block is stored once.

A DMA moves whole 8-row HBM tiles (see `tiles.py`). In-batch rows are
contiguous — the 8 virtual rows of a group are 8 consecutive x_in rows —
so x_in moves in bn/8 aligned group DMAs per block at no extra cost (the
wrapper pads x_in to a multiple of 8 rows). A halo row moves as the tile
group that holds it; rows of the table's partial last tile come from a
small `tail` operand instead, so no DMA reads past the table.

Quantized histories (`scales` given): the table holds symmetric per-row
int8 rows; only int8 bytes cross HBM for halo columns (the staging buffer
is int8 too). The per-row dequant scale is pre-gathered into a route row
(`rscl = scales[trow]`), so the dequant multiply runs on the routed
block before the MXU contraction — the f32 halo tensor never exists in
HBM. With `codebook` as well, the table holds uint8 vq code rows: the
picked code rows are decoded against the resident VMEM codebook
(`tiles.vq_decode_tile`) before the contraction.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .tiles import GROUP, to_col, vq_decode_tile

# VMEM the panel path may fill: a quarter of a v5e core's 128 MiB (twice
# Mosaic's default 16 MiB scoped limit, which the kernel then raises)
VMEM_BUDGET = 32 * 2**20
# asked for beyond the computed need, for Mosaic's internal scratch
VMEM_HEADROOM = 4 * 2**20
# SMEM the panel path's whole plan and `blk_cols` may fill, of 1 MiB
SMEM_PLAN_BUDGET = 512 * 2**10
# route codes of a virtual row (`gather_plan`) past the halo picks: s <
# GROUP is sublane s of the row's staged tile group, GROUP + s row s of
# the table's partial last tile
IN_BATCH = 2 * GROUP
ZERO = 2 * GROUP + 1


def gather_plan(n_in: int, halo_nodes: jnp.ndarray,
                halo_mask: jnp.ndarray, n_table: int, bn: int):
    """Per-(column block, row) source plan for `gather_spmm` (module
    docstring).

    Returns (trow, code), each [Ncols, bn] int32 with Ncols =
    ceil((n_in + max_h + 1) / bn): virtual row c * bn + row is in-batch
    below n_in, halo row (c * bn + row - n_in) below n_in + max_h, and a
    dummy or padding zero past that. `trow` is the table row of an
    unmasked halo row (clipped to the table) and -1 for every other row;
    `code` is the row's route code: IN_BATCH, ZERO for masked halo,
    dummy and padding rows, and for a halo row in table row t, t % GROUP,
    plus GROUP where t lies in the table's partial last tile (`tail`
    then holds it). The halo rows are contiguous in the bracket, so the
    plan is the halo vectors padded in place — no gather.
    """
    max_h = halo_nodes.shape[0]
    ncols = _ncols(n_in, max_h, bn)
    n8 = n_table // GROUP * GROUP
    pad = (n_in, ncols * bn - n_in - max_h)
    ok = jnp.pad(halo_mask, pad)
    t = jnp.pad(jnp.clip(halo_nodes, 0, n_table - 1), pad)
    halo = t % GROUP + jnp.where(t < n8, 0, GROUP)
    code = jnp.where(jnp.arange(ncols * bn) < n_in, IN_BATCH,
                     jnp.where(ok, halo, ZERO))
    return (jnp.where(ok, t, -1).astype(jnp.int32).reshape(ncols, bn),
            code.astype(jnp.int32).reshape(ncols, bn))


def _round(n: int, m: int) -> int:
    return -(-n // m) * m


def _ncols(n_in: int, max_h: int, bn: int) -> int:
    """Column blocks of [x_in ; halo ; dummy zero row]."""
    return -(-(n_in + max_h + 1) // bn)


def _vmem_bytes(shape, dtype) -> int:
    """Bytes of a VMEM buffer, its last two dims padded to the native
    tile: 128 lanes by 8 sublanes of 32 bits (16 of 16 bits, 32 of 8)."""
    item = jnp.dtype(dtype).itemsize
    *lead, s, lanes = shape
    return (math.prod(lead) * _round(s, 32 // item) * _round(lanes, 128)
            * item)


def _panel_vmem(ncols: int, pw: int, *, bn: int, x_dtype, table,
                vals_dtype, codebook) -> int:
    """VMEM bytes of the panel path at panel width pw: the panel, the
    staging buffers, the route rows (and dequant scale rows for a
    quantized table), and the double-buffered blocks Pallas pipelines."""
    st_w = table.shape[1] if codebook is not None else pw
    lanes, nl = _lanes(st_w)
    rows = 1 + jnp.issubdtype(table.dtype, jnp.integer)
    need = (_vmem_bytes((ncols, bn, pw), jnp.float32)               # panel
            + _vmem_bytes((bn, pw), x_dtype)                          # sx
            + _vmem_bytes((bn * GROUP, st_w), table.dtype)            # st
            + _vmem_bytes((nl, bn * GROUP, lanes), jnp.int32)         # wide
            + rows * _vmem_bytes((ncols, bn), jnp.int32)   # codes, rscl
            + 2 * _vmem_bytes((GROUP, st_w), table.dtype)            # tail
            + 2 * _vmem_bytes((bn, bn), vals_dtype)                  # vals
            + 2 * _vmem_bytes((bn, pw), jnp.float32))                # out
    if codebook is not None:
        need += 2 * _vmem_bytes(codebook.shape, codebook.dtype)
    return need


def panel_width(x_in, table, blk_vals, halo_nodes, codebook=None, *,
                bd: int = 128) -> Optional[int]:
    """Feature width of `gather_spmm`'s VMEM panel for these shapes (the
    arguments need only `.shape` and `.dtype`): D where the whole-width
    panel path fits `VMEM_BUDGET`, else bd where one tile's does, else
    None — the per-block path. Also None where the panel path's plan
    would not fit `SMEM_PLAN_BUDGET`."""
    R, K, bn, _ = blk_vals.shape
    n_in, D = x_in.shape
    ncols = _ncols(n_in, halo_nodes.shape[0], bn)
    smem = _round(ncols, 8) * bn * 4 + _round(R, 8) * _round(K, 128) * 4
    if smem > SMEM_PLAN_BUDGET:
        return None
    for pw in dict.fromkeys((D, bd)):
        if _panel_vmem(ncols, pw, bn=bn, x_dtype=x_in.dtype, table=table,
                       vals_dtype=blk_vals.dtype,
                       codebook=codebook) <= VMEM_BUDGET:
            return pw
    return None


def _x_groups(c, *, bn, n_in):
    """Aligned 8-row groups of x_in in column block c (n_in is padded to
    whole groups): 0 past the in-batch rows, bn / GROUP before them."""
    return jnp.clip((n_in - c * bn + GROUP - 1) // GROUP, 0, bn // GROUP)


def _x_dma(x_ref, sx_ref, sem, v0, g, *, d, w):
    """DMA of the aligned x_in rows [v0, v0 + GROUP) into group g of sx,
    feature lanes [d*w, (d+1)*w)."""
    return pltpu.make_async_copy(
        x_ref.at[pl.ds(pl.multiple_of(v0, GROUP), GROUP), pl.ds(d * w, w)],
        sx_ref.at[pl.ds(pl.multiple_of(g * GROUP, GROUP), GROUP)], sem)


def _halo_dma(tbl_ref, st_ref, sem, t, row, *, d, w, vq):
    """DMA of the tile group holding table row t into the GROUP rows of
    st that stage virtual row `row` (a vq code row moves whole)."""
    base = pl.ds(pl.multiple_of(t // GROUP * GROUP, GROUP), GROUP)
    src = tbl_ref.at[base] if vq else tbl_ref.at[base, pl.ds(d * w, w)]
    return pltpu.make_async_copy(
        src, st_ref.at[pl.ds(pl.multiple_of(row * GROUP, GROUP), GROUP)],
        sem)


def _issue(x_ref, tbl_ref, sx_ref, st_ref, sem, cnt_ref, trow_ref, c, j, *,
           d, bn, w, n_in, n8, vq):
    """Start the DMAs of column block c, whose plan is row j of
    `trow_ref`: one aligned group DMA per 8 in-batch virtual rows into
    sx, and one tile-group DMA per halo row in the table's whole tiles
    into its own GROUP rows of st. The halo DMAs are counted into
    `cnt_ref` for `_drain`."""

    def x_group(g, carry):
        _x_dma(x_ref, sx_ref, sem, c * bn + g * GROUP, g, d=d, w=w).start()
        return carry

    def halo_row(row, n):
        t = trow_ref[j, row]
        ok = (t >= 0) & (t < n8)

        @pl.when(ok)
        def _():
            _halo_dma(tbl_ref, st_ref, sem, t, row, d=d, w=w, vq=vq).start()
        return n + ok.astype(jnp.int32)

    jax.lax.fori_loop(0, _x_groups(c, bn=bn, n_in=n_in), x_group, None)
    cnt_ref[0] = jax.lax.fori_loop(0, bn, halo_row, jnp.int32(0))


def _drain(x_ref, tbl_ref, sx_ref, st_ref, sem, cnt_ref, c, *, d, bn, w,
           n_in, vq):
    """Wait for every DMA `_issue` started for column block c, reading no
    plan entry: as many waits on one fixed descriptor of each kind's
    size as `_x_groups` and the issue's count say."""
    zero = jnp.int32(0)

    def waits(dma):
        def body(i, carry):
            dma.wait()
            return carry
        return body

    jax.lax.fori_loop(0, _x_groups(c, bn=bn, n_in=n_in),
                      waits(_x_dma(x_ref, sx_ref, sem, zero, zero, d=d,
                                   w=w)), None)
    jax.lax.fori_loop(0, cnt_ref[0],
                      waits(_halo_dma(tbl_ref, st_ref, sem, zero, zero, d=d,
                                      w=w, vq=vq)), None)


def _lanes(st_w: int):
    """(L, nl): a strided sublane read needs a buffer 128 lanes wide, so
    the widened slot is kept as nl lane tiles of L lanes."""
    lanes = 128 if st_w % 128 == 0 else st_w
    return lanes, st_w // lanes


def _unstage(st_ref, wide_ref, sx_ref, dst):
    """Empty both staging buffers for the next block's DMAs: widen the
    staged tile groups to 32 bits, lane tile h of every row into
    `wide_ref[h]` [bn*GROUP, L], and park the block's x rows in dst."""
    nl, _, lanes = wide_ref.shape
    for h in range(nl):
        wide_ref[h] = st_ref[:, pl.ds(h * lanes, lanes)].astype(
            wide_ref.dtype)
    dst[...] = sx_ref[...].astype(jnp.float32)


def _route(dst, code, scl, wide_ref, tail_ref, cb_ref, *, d, bn, w, nw,
           ntail, vq):
    """Route the unstaged block into the [bn, w] f32 ref dst, as whole-
    block vector ops: row i keeps its parked x row, takes its halo row
    (dequantized by `scl` for int8 and vq tables, decoded for vq), or
    zeros, as its route code `code[0, i]` says (`gather_plan`). A halo
    row is sublane t % GROUP of its staged tile group: GROUP strided
    reads of `wide_ref` give that sublane of every group, and a select
    keeps each row's own; rows of the table's partial last tile come
    from `tail_ref`."""
    nl, _, lanes = wide_ref.shape
    col = to_col(code)[:, :1]
    cl = jnp.broadcast_to(col, (bn, lanes))
    tail = tail_ref[...].astype(wide_ref.dtype)
    parts = []
    for h in range(nl):
        tv = jnp.zeros((bn, lanes), wide_ref.dtype)
        for s in range(GROUP):
            tv = jnp.where(cl == s,
                           wide_ref[h, pl.ds(s, bn, stride=GROUP), :], tv)
        for s in range(ntail):
            tv = jnp.where(cl == GROUP + s,
                           tail[s:s + 1, h * lanes:(h + 1) * lanes], tv)
        parts.append(tv)
    tv = parts[0] if nl == 1 else jnp.concatenate(parts, axis=1)
    if vq:
        rec = vq_decode_tile(tv, cb_ref[...])
        rec = jnp.pad(rec, ((0, 0), (0, nw * w - rec.shape[1])))
        tv = rec[:, :w]
        for i in range(1, nw):
            tv = jnp.where(d == i, rec[:, i * w:(i + 1) * w], tv)
    if scl is not None:
        tv = tv * to_col(scl)[:, :1]
    cw = jnp.broadcast_to(col, (bn, w))
    dst[...] = jnp.where(cw == IN_BATCH, dst[...],
                         jnp.where(cw == ZERO, 0.0, tv))


def _unpack(refs, nplan, dq, vq):
    """(cols, SMEM plan refs, route code rows, rscl rows, x, table, tail,
    vals, codebook, out, scratch refs) of a kernel's flat ref list."""
    cols_ref, plan = refs[0], refs[1:1 + nplan]
    code_ref = refs[1 + nplan]
    scl_ref = refs[2 + nplan] if dq else None
    refs = refs[2 + nplan + dq:]
    x_ref, tbl_ref, tail_ref, vals_ref = refs[:4]
    cb_ref = refs[4] if vq else None
    return (cols_ref, plan, code_ref, scl_ref, x_ref, tbl_ref, tail_ref,
            vals_ref, cb_ref, refs[4 + vq], refs[5 + vq:])


def _make_panel_kernel(*, bn, pw, npw, ncols, n_in, n8, ntail, dq, vq):
    def kernel(*refs):
        (cols_ref, (trow_ref,), code_ref, scl_ref, x_ref, tbl_ref,
         tail_ref, vals_ref, cb_ref, out_ref, scratch) = _unpack(refs, 1,
                                                                 dq, vq)
        panel_ref, sx_ref, st_ref, wide_ref, sem, cnt_ref = scratch
        d = pl.program_id(0)
        r = pl.program_id(1)
        k = pl.program_id(2)
        kw = dict(d=d, bn=bn, w=pw, n_in=n_in, vq=vq)
        bufs = (x_ref, tbl_ref, sx_ref, st_ref, sem, cnt_ref)

        @pl.when((r == 0) & (k == 0))
        def _stage_panel():
            _issue(*bufs, trow_ref, 0, 0, n8=n8, **kw)

            def block(c, carry):
                _drain(*bufs, c, **kw)
                _unstage(st_ref, wide_ref, sx_ref, panel_ref.at[c])

                # block c+1's DMAs overlap the route of c
                @pl.when(c + 1 < ncols)
                def _prefetch():
                    _issue(*bufs, trow_ref, c + 1, c + 1, n8=n8, **kw)

                row = pl.ds(c, 1)
                _route(panel_ref.at[c], code_ref[row, :],
                       scl_ref[row, :] if dq else None, wide_ref, tail_ref,
                       cb_ref, d=d, bn=bn, w=pw, nw=npw, ntail=ntail, vq=vq)
                return carry

            jax.lax.fori_loop(0, ncols, block, None)

        @pl.when(k == 0)
        def _init():
            out_ref[...] = jnp.zeros_like(out_ref)

        out_ref[...] += jnp.dot(vals_ref[0, 0], panel_ref[cols_ref[r, k]],
                                preferred_element_type=jnp.float32)
    return kernel


def _make_block_kernel(*, bn, bd, nd, n_in, n8, ntail, dq, vq):
    def kernel(*refs):
        (cols_ref, (trow_ref, trow_nx), code_ref, scl_ref, x_ref, tbl_ref,
         tail_ref, vals_ref, cb_ref, out_ref, scratch) = _unpack(refs, 2,
                                                                 dq, vq)
        gx_ref, sx_ref, st_ref, wide_ref, sem, cnt_ref = scratch
        r = pl.program_id(0)
        d = pl.program_id(1)
        k = pl.program_id(2)
        nk = pl.num_programs(2)
        # rows of blocks k and k+1 within their (GROUP, bn) plan blocks
        j = jax.lax.rem(r * nk + k, GROUP)
        j_nx = jax.lax.rem(r * nk + jnp.minimum(k + 1, nk - 1), GROUP)
        kw = dict(d=d, bn=bn, w=bd, n_in=n_in, vq=vq)
        bufs = (x_ref, tbl_ref, sx_ref, st_ref, sem, cnt_ref)

        @pl.when(k == 0)
        def _init():
            out_ref[...] = jnp.zeros_like(out_ref)
            # warm-up: block 0's rows were never prefetched on this (r, d)
            _issue(*bufs, trow_ref, cols_ref[r, 0], j, n8=n8, **kw)

        _drain(*bufs, cols_ref[r, k], **kw)
        _unstage(st_ref, wide_ref, sx_ref, gx_ref)

        # block k+1's DMAs overlap the route of block k and the MXU work
        @pl.when(k + 1 < nk)
        def _prefetch():
            _issue(*bufs, trow_nx, cols_ref[r, k + 1], j_nx, n8=n8, **kw)

        _route(gx_ref, code_ref[0], scl_ref[0] if dq else None, wide_ref,
               tail_ref, cb_ref, d=d, bn=bn, w=bd, nw=nd, ntail=ntail,
               vq=vq)
        out_ref[...] += jnp.dot(vals_ref[0, 0], gx_ref[...],
                                preferred_element_type=jnp.float32)
    return kernel


@functools.partial(jax.jit, static_argnames=("bn", "bd", "interpret"))
def gather_spmm(x_in: jnp.ndarray, table: jnp.ndarray,
                blk_vals: jnp.ndarray, blk_cols: jnp.ndarray,
                halo_nodes: jnp.ndarray, halo_mask: jnp.ndarray,
                scales: jnp.ndarray = None,
                codebook: jnp.ndarray = None,
                *, bn: int = 128, bd: int = 128,
                interpret: bool) -> jnp.ndarray:
    """out [R*bn, D] = A @ [x_in ; dequant(table)[halo] * mask ; 0]
    without building the bracket in HBM. x_in [n_in, D] with D % bd == 0;
    halo_nodes [max_h] int32 rows of the table (clipped to its range) and
    halo_mask [max_h] bool; every blk_cols entry lies below
    ceil((n_in + max_h + 1) / bn), as `core.gas.build_batches` builds
    them. With `scales` [N] f32 the table rows are int8 and dequantized
    in-kernel (module docstring); with `codebook` [S, C, ds] too, the
    table holds uint8 vq code rows [N, S] that are staged whole (S bytes
    per halo row) and codebook-decoded in VMEM right before the
    contraction — the codebook rides as a whole-VMEM operand. Takes the
    panel path where `panel_width` finds one, else the per-block path.
    Output is fp32 (MXU-native accumulation); the caller casts."""
    R, K, bn_, bn2 = blk_vals.shape
    assert bn_ == bn and bn2 == bn, (blk_vals.shape, bn)
    n_in, D = x_in.shape
    assert D % bd == 0, (x_in.shape, bd)
    assert codebook is not None or table.shape[1] == D, (table.shape, D)
    dq = scales is not None
    vq = codebook is not None
    N, tw = table.shape
    n8 = N // GROUP * GROUP
    ntail = N - n8
    # rows past the last whole tile: a [GROUP, tw] VMEM operand
    tail = jnp.pad(table[n8:], ((0, GROUP - ntail), (0, 0)))
    xp = jnp.pad(x_in, ((0, -n_in % GROUP), (0, 0)))
    pw = panel_width(x_in, table, blk_vals, halo_nodes, codebook, bd=bd)
    w = bd if pw is None else pw
    if pw is None:
        kernel = _make_block_kernel(bn=bn, bd=bd, nd=D // bd, n_in=n_in,
                                    n8=n8, ntail=ntail, dq=dq, vq=vq)
        grid, params = (R, D // bd, K), None
        dst = pltpu.VMEM((bn, bd), jnp.float32)                     # gx

        def idx(f):
            return lambda r, d, k, *_: f(r, d, k)
    else:
        ncols = _ncols(n_in, halo_nodes.shape[0], bn)
        kernel = _make_panel_kernel(bn=bn, pw=pw, npw=D // pw, ncols=ncols,
                                    n_in=n_in, n8=n8, ntail=ntail, dq=dq,
                                    vq=vq)
        grid = (D // pw, R, K)
        params = pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 3,
            vmem_limit_bytes=VMEM_HEADROOM + _panel_vmem(
                ncols, pw, bn=bn, x_dtype=x_in.dtype, table=table,
                vals_dtype=blk_vals.dtype, codebook=codebook))
        dst = pltpu.VMEM((ncols, bn, pw), jnp.float32)              # panel

        def idx(f):  # the (d, r, k) grid, handed to (r, d, k) index maps
            return lambda d, r, k, *_: f(r, d, k)
    trow, code = gather_plan(n_in, halo_nodes, halo_mask, N, bn)
    # per-row vectors of the route, VMEM rows: codes, dequant scales
    rows = [code]
    if dq:
        assert scales.shape == (N,), (scales.shape, table.shape)
        rows.append(jnp.take(scales, trow, mode="clip"))
    if pw is None:
        # Each adjacency block's column plan as one of [R*K, bn] rows, in
        # (GROUP, bn) SMEM blocks (Mosaic's tiling): the group holding
        # block k's row (its warm-up issue) and the group holding block
        # k+1's (prefetch). Block k's route rows are row blk_cols[r, k]
        # of [Ncols, 1, bn].
        nb = R * K
        plan = jnp.pad(jnp.take(trow, blk_cols.reshape(nb), axis=0,
                                mode="clip"),
                       ((0, _round(nb, GROUP) - nb), (0, 0)))
        plan = [plan, plan]
        plan_specs = [
            pl.BlockSpec((GROUP, bn),
                         idx(lambda r, d, k: ((r * K + k) // GROUP, 0)),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((GROUP, bn),
                         idx(lambda r, d, k: (
                             (r * K + jnp.minimum(k + 1, K - 1)) // GROUP,
                             0)),
                         memory_space=pltpu.SMEM)]
        rows = [a[:, None, :] for a in rows]
        row_specs = [pl.BlockSpec((1, 1, bn),
                                  lambda r, d, k, cols: (cols[r, k], 0, 0))
                     ] * len(rows)
    else:
        plan = [trow]
        plan_specs = [pl.BlockSpec(memory_space=pltpu.SMEM)]
        row_specs = [pl.BlockSpec(memory_space=pltpu.VMEM)] * len(rows)
    st_w = tw if vq else w
    lanes, nl = _lanes(st_w)
    in_specs = plan_specs + row_specs + [
        pl.BlockSpec(memory_space=pl.ANY),
        pl.BlockSpec(memory_space=pl.ANY),
        pl.BlockSpec((GROUP, st_w),
                     idx(lambda r, d, k: (0, 0)) if vq else
                     idx(lambda r, d, k: (0, d))),
        pl.BlockSpec((1, 1, bn, bn), idx(lambda r, d, k: (r, k, 0, 0)))]
    operands = plan + rows + [xp, table, tail, blk_vals]
    scratch = [dst,
               pltpu.VMEM((bn, w), x_in.dtype),                       # sx
               pltpu.VMEM((bn * GROUP, st_w), table.dtype),           # st
               pltpu.VMEM((nl, bn * GROUP, lanes),                    # wide
                          jnp.int32 if vq else jnp.float32),
               pltpu.SemaphoreType.DMA(()),
               pltpu.SMEM((1,), jnp.int32)]                   # halo DMAs
    if vq:
        s_, c, ds = codebook.shape
        assert tw == s_ and s_ * ds <= D, (table.shape, codebook.shape, D)
        in_specs.append(pl.BlockSpec((s_, c, ds),
                                     idx(lambda r, d, k: (0, 0, 0))))
        operands.append(codebook)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bn, w), idx(lambda r, d, k: (r, d))),
        scratch_shapes=scratch,
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((R * bn, D), jnp.float32),
        compiler_params=params,
        interpret=interpret,
    )(blk_cols, *operands)
