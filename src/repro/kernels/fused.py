"""Fused history-gather + block-CSR SpMM Pallas kernel (GAS aggregation).

The unfused GAS layer materializes

    x_all = concat([x_in, pull(table, halo_nodes) * halo_mask, 0])

and then runs the BCSR SpMM over x_all — a full halo gather plus a full
concatenate copy of the layer input, per layer, per batch, that exist only
to be read once by the matmul. This kernel removes both: the virtual x_all
never exists in HBM. A *gather plan* (sel/trow, one entry per virtual row,
see `gather_plan`) says where row `v = c * bn + row` of column block c
actually lives:

    sel == 0 : in-batch  -> x_in[v]      (current layer activations)
    sel == 1 : halo      -> table[trow]  (historical embedding, read
                                          directly out of the history table)
    sel == 2 : masked halo / dummy / padding -> exact zeros

Panel path. x_all has only Ncols = ceil((n_in + max_h + 1) / bn) column
blocks, and nearly every row block reads nearly all of them, so each
column block is staged once per call and feature tile into a VMEM panel
[Ncols, bn, pw] f32. Grid (D/pw, R, K), every dimension sequential: step
(d, 0, 0) stages all Ncols blocks of feature tile d, and every step then
only contracts `out[r] += vals[r, k] @ panel[blk_cols[r, k]]`. The plan
is [Ncols, bn], a function of n_in and the halo alone, held whole in
SMEM (with `rscl`, below). The panel takes Ncols * bn * pw * 4 bytes of
VMEM: 81 blocks of 128 rows at pw = 256 are 10.6 MB. `panel_width`
takes pw = D where the panel, the staging slots and the pipelined
blocks fit `VMEM_BUDGET` (one tile-group DMA then serves every feature
tile of a halo row), else pw = bd; the kernel asks Mosaic for that need
(plus `VMEM_HEADROOM`) as its scoped VMEM limit.

Per-block fallback, for shapes whose panel does not fit, or whose plan
does not fit `SMEM_PLAN_BUDGET`: grid (R, D/bd, K), and every step
stages the bn columns of its own adjacency block into a [bn, bd] buffer
before it contracts them, so a column block is staged once per row block
that reads it. Its plan, the column plan's rows of each adjacency block,
is [R, K, bn] and reaches SMEM one (GROUP, bn) block of rows at a time: SMEM holds 1 MiB, and an [R, K, bn] int32
plan outgrows it at R * K > ~2k blocks (a serving refresh of a 10k-node
graph needs ~5k).

Staging is the same on both paths. The row DMAs are HAND-PIPELINED with
`pltpu.make_async_copy` double buffering — x_in and the history table
stay whole (`pl.ANY`): the DMAs of the next column block start into the
other slot, then the current block's slot is waited on, and only then
are its staged rows routed and dequantized into VMEM.

A DMA moves whole 8-row HBM tiles (see `tiles.py`). In-batch rows are
contiguous — the 8 virtual rows of a group are 8 consecutive x_in rows —
so x_in moves in bn/8 aligned group DMAs per block at no extra cost (the
wrapper pads x_in to a multiple of 8 rows). A halo row moves as the tile
group that holds it and is picked out in VMEM (`tiles.pick_row`); rows
of the table's partial last tile come from a small `tail` operand
instead, so no DMA reads past the table.

Quantized histories (`scales` given): the table holds symmetric per-row
int8 rows; only int8 bytes cross HBM for halo columns (the staging buffer
is int8 too). The per-row dequant scale is pre-gathered into a plan
operand shaped like `trow` (`rscl = scales[trow]`), so the dequant
multiply runs between the pick and the MXU contraction — the f32 halo
tensor never exists in HBM. With `codebook` as well, the table holds
uint8 vq code rows: the picked code rows are decoded against the
resident VMEM codebook (`tiles.vq_decode_tile`) before the contraction.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .tiles import GROUP, pick_row, vq_decode_tile

# VMEM the panel path may fill: a quarter of a v5e core's 128 MiB (twice
# Mosaic's default 16 MiB scoped limit, which the kernel then raises)
VMEM_BUDGET = 32 * 2**20
# asked for beyond the computed need, for Mosaic's internal scratch
VMEM_HEADROOM = 4 * 2**20
# SMEM the panel path's whole plan and `blk_cols` may fill, of 1 MiB
SMEM_PLAN_BUDGET = 512 * 2**10


def gather_plan(n_in: int, halo_nodes: jnp.ndarray,
                halo_mask: jnp.ndarray, n_table: int, bn: int):
    """Per-(column block, row) source plan for `gather_spmm` (module
    docstring).

    Returns (sel, trow), each [Ncols, bn] int32 with Ncols =
    ceil((n_in + max_h + 1) / bn): virtual row c * bn + row is in-batch
    below n_in, halo row (c * bn + row - n_in) below n_in + max_h, and a
    dummy or padding zero past that. The halo rows are contiguous in the
    bracket, so the plan is the halo vectors padded in place — no gather.
    """
    max_h = halo_nodes.shape[0]
    ncols = _ncols(n_in, max_h, bn)
    pad = (n_in, ncols * bn - n_in - max_h)
    ok = jnp.pad(halo_mask, pad)
    trow = jnp.where(ok, jnp.pad(jnp.clip(halo_nodes, 0, n_table - 1), pad),
                     0)
    sel = jnp.where(jnp.arange(ncols * bn) < n_in, 0,
                    jnp.where(ok, 1, 2))
    return (sel.astype(jnp.int32).reshape(ncols, bn),
            trow.astype(jnp.int32).reshape(ncols, bn))


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
    staging slots, and the double-buffered blocks Pallas pipelines."""
    st_w = table.shape[1] if codebook is not None else pw
    need = (_vmem_bytes((ncols, bn, pw), jnp.float32)               # panel
            + _vmem_bytes((2, bn // GROUP, GROUP, pw), x_dtype)      # sx
            + _vmem_bytes((2, bn, GROUP, st_w), table.dtype)         # st
            + 2 * _vmem_bytes((GROUP, st_w), table.dtype)            # tail
            + 2 * _vmem_bytes((bn, bn), vals_dtype)                  # vals
            + 2 * _vmem_bytes((bn, pw), jnp.float32))                # out
    if codebook is not None:
        need += (2 * _vmem_bytes(codebook.shape, codebook.dtype)
                 + _vmem_bytes((bn, table.shape[1]), jnp.int32))     # codes
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
    smem = 3 * _round(ncols, 8) * bn * 4 + _round(R, 8) * _round(K, 128) * 4
    if smem > SMEM_PLAN_BUDGET:
        return None
    for pw in dict.fromkeys((D, bd)):
        if _panel_vmem(ncols, pw, bn=bn, x_dtype=x_in.dtype, table=table,
                       vals_dtype=blk_vals.dtype,
                       codebook=codebook) <= VMEM_BUDGET:
            return pw
    return None


def _row_dmas(x_ref, tbl_ref, sx_ref, st_ref, sem_ref, sel_ref, trow_ref,
              c, j, slot, *, d, bn, w, n_in, n8, vq, start):
    """Issue (start=True) or drain (start=False) the DMAs of column block
    c, whose plan is row j of `sel_ref`/`trow_ref`, into double-buffer
    slot `slot`: one aligned group DMA per 8 in-batch virtual rows, one
    tile-group DMA per halo row, feature lanes [d*w, (d+1)*w). Waits
    rebuild the same descriptors, so one per-slot semaphore balances."""

    def x_group(g, carry):
        v0 = c * bn + g * GROUP

        @pl.when(v0 < n_in)
        def _():
            dma = pltpu.make_async_copy(
                x_ref.at[pl.ds(pl.multiple_of(v0, GROUP), GROUP),
                         pl.ds(d * w, w)],
                sx_ref.at[slot, g], sem_ref.at[slot])
            dma.start() if start else dma.wait()
        return carry

    def halo_row(row, carry):
        t = trow_ref[j, row]

        @pl.when((sel_ref[j, row] == 1) & (t < n8))
        def _():
            base = pl.ds(pl.multiple_of(t // GROUP * GROUP, GROUP), GROUP)
            src = (tbl_ref.at[base] if vq else
                   tbl_ref.at[base, pl.ds(d * w, w)])
            dma = pltpu.make_async_copy(src, st_ref.at[slot, row],
                                        sem_ref.at[slot])
            dma.start() if start else dma.wait()
        return carry

    jax.lax.fori_loop(0, bn // GROUP, x_group, None)
    jax.lax.fori_loop(0, bn, halo_row, None)


def _route_block(dst, sel_ref, trow_ref, rscl_ref, sx_ref, st_ref,
                 tail_ref, cb_ref, code_ref, j, slot, *, d, bn, w, nw, n8,
                 dq, vq):
    """Route each staged row of the block in `slot` (plan row j) into the
    [bn, w] f32 ref `dst`: in-batch (sx), halo (st, dequantized for
    int8/vq tables), or exact zeros."""

    def halo_tile(row):
        t = trow_ref[j, row]
        return jnp.where(t < n8, st_ref[slot, row], tail_ref[...]), t

    if vq:
        # decode every staged code row at once; rows that are not halo
        # rows decode garbage that the route below discards
        def stage_codes(row, carry):
            tile, t = halo_tile(row)
            code_ref[pl.ds(row, 1), :] = pick_row(tile, t % GROUP)
            return carry

        jax.lax.fori_loop(0, bn, stage_codes, None)
        rec = vq_decode_tile(code_ref[...], cb_ref[...])
        rec = jnp.pad(rec, ((0, 0), (0, nw * w - rec.shape[1])))
        dec = rec[:, :w]
        for i in range(1, nw):
            dec = jnp.where(d == i, rec[:, i * w:(i + 1) * w], dec)
        # park the decoded block in dst; the route reads it row-wise
        # before overwriting the same row
        dst[...] = dec

    def route(row, carry):
        s = sel_ref[j, row]
        xv = pick_row(sx_ref[slot, row // GROUP], row % GROUP)
        if vq:
            tv = dst[pl.ds(row, 1), :]
        else:
            tile, t = halo_tile(row)
            tv = pick_row(tile, t % GROUP)
        if dq:
            tv = tv * rscl_ref[j, row]
        dst[pl.ds(row, 1), :] = jnp.where(
            s == 0, xv, jnp.where(s == 1, tv, 0.0))
        return carry

    jax.lax.fori_loop(0, bn, route, None)


def _unpack(refs, nplan, dq, vq):
    """(cols, plan refs, rscl, x, table, tail, vals, codebook, out,
    scratch refs) of a kernel's flat ref list."""
    cols_ref, plan = refs[0], refs[1:1 + nplan]
    rscl_ref = refs[1 + nplan] if dq else None
    refs = refs[1 + nplan + dq:]
    x_ref, tbl_ref, tail_ref, vals_ref = refs[:4]
    cb_ref = refs[4] if vq else None
    return (cols_ref, plan, rscl_ref, x_ref, tbl_ref, tail_ref, vals_ref,
            cb_ref, refs[4 + vq], refs[5 + vq:])


def _make_panel_kernel(*, bn, pw, npw, ncols, n_in, n8, dq, vq):
    def kernel(*refs):
        (cols_ref, (sel_ref, trow_ref), rscl_ref, x_ref, tbl_ref, tail_ref,
         vals_ref, cb_ref, out_ref, scratch) = _unpack(refs, 2, dq, vq)
        panel_ref, sx_ref, st_ref, sem_ref = scratch[:4]
        code_ref = scratch[4] if vq else None
        d = pl.program_id(0)
        r = pl.program_id(1)
        k = pl.program_id(2)
        kw = dict(d=d, bn=bn, w=pw, n8=n8, vq=vq)
        dmas = functools.partial(_row_dmas, x_ref, tbl_ref, sx_ref, st_ref,
                                 sem_ref, sel_ref, trow_ref, n_in=n_in,
                                 **kw)

        @pl.when((r == 0) & (k == 0))
        def _stage_panel():
            dmas(0, 0, 0, start=True)

            def block(c, carry):
                slot = jax.lax.rem(c, 2)

                # block c+1's DMAs overlap the wait and the route of c
                @pl.when(c + 1 < ncols)
                def _prefetch():
                    dmas(c + 1, c + 1, 1 - slot, start=True)

                dmas(c, c, slot, start=False)
                _route_block(panel_ref.at[c], sel_ref, trow_ref, rscl_ref,
                             sx_ref, st_ref, tail_ref, cb_ref, code_ref, c,
                             slot, nw=npw, dq=dq, **kw)
                return carry

            jax.lax.fori_loop(0, ncols, block, None)

        @pl.when(k == 0)
        def _init():
            out_ref[...] = jnp.zeros_like(out_ref)

        out_ref[...] += jnp.dot(vals_ref[0, 0], panel_ref[cols_ref[r, k]],
                                preferred_element_type=jnp.float32)
    return kernel


def _make_block_kernel(*, bn, bd, nd, n_in, n8, dq, vq):
    def kernel(*refs):
        (cols_ref, (sel_ref, trow_ref, sel_nx, trow_nx), rscl_ref, x_ref,
         tbl_ref, tail_ref, vals_ref, cb_ref, out_ref,
         scratch) = _unpack(refs, 4, dq, vq)
        gx_ref, sx_ref, st_ref, sem_ref = scratch[:4]
        code_ref = scratch[4] if vq else None
        r = pl.program_id(0)
        d = pl.program_id(1)
        k = pl.program_id(2)
        nk = pl.num_programs(2)
        slot = jax.lax.rem(k, 2)
        # rows of blocks k and k+1 within their (GROUP, bn) plan blocks
        j = jax.lax.rem(r * nk + k, GROUP)
        j_nx = jax.lax.rem(r * nk + jnp.minimum(k + 1, nk - 1), GROUP)
        kw = dict(d=d, bn=bn, w=bd, n8=n8, vq=vq)

        def dmas(blk, sel, trow, jj, sl, start):
            _row_dmas(x_ref, tbl_ref, sx_ref, st_ref, sem_ref, sel, trow,
                      cols_ref[r, blk], jj, sl, n_in=n_in, start=start,
                      **kw)

        @pl.when(k == 0)
        def _init():
            out_ref[...] = jnp.zeros_like(out_ref)
            # warm-up: block 0's rows were never prefetched on this (r, d)
            dmas(0, sel_ref, trow_ref, j, 0, start=True)

        # prefetch block k+1's rows into the other slot BEFORE waiting on
        # block k — these DMAs overlap the wait and the MXU work
        @pl.when(k + 1 < nk)
        def _prefetch():
            dmas(k + 1, sel_nx, trow_nx, j_nx, jax.lax.rem(k + 1, 2),
                 start=True)

        dmas(k, sel_ref, trow_ref, j, slot, start=False)
        _route_block(gx_ref, sel_ref, trow_ref, rscl_ref, sx_ref, st_ref,
                     tail_ref, cb_ref, code_ref, j, slot, nw=nd, dq=dq,
                     **kw)
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
    # rows past the last whole tile: a [GROUP, tw] VMEM operand
    tail = jnp.pad(table[n8:], ((0, GROUP - (N - n8)), (0, 0)))
    xp = jnp.pad(x_in, ((0, -n_in % GROUP), (0, 0)))
    pw = panel_width(x_in, table, blk_vals, halo_nodes, codebook, bd=bd)
    w = bd if pw is None else pw
    if pw is None:
        kernel = _make_block_kernel(bn=bn, bd=bd, nd=D // bd, n_in=n_in,
                                    n8=n8, dq=dq, vq=vq)
        grid, params = (R, D // bd, K), None
        dst = pltpu.VMEM((bn, bd), jnp.float32)                     # gx

        def idx(f):
            return lambda r, d, k, *_: f(r, d, k)
    else:
        ncols = _ncols(n_in, halo_nodes.shape[0], bn)
        kernel = _make_panel_kernel(bn=bn, pw=pw, npw=D // pw, ncols=ncols,
                                    n_in=n_in, n8=n8, dq=dq, vq=vq)
        grid = (D // pw, R, K)
        params = pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 3,
            vmem_limit_bytes=VMEM_HEADROOM + _panel_vmem(
                ncols, pw, bn=bn, x_dtype=x_in.dtype, table=table,
                vals_dtype=blk_vals.dtype, codebook=codebook))
        dst = pltpu.VMEM((ncols, bn, pw), jnp.float32)              # panel

        def idx(f):  # the (d, r, k) grid, handed to (r, d, k) index maps
            return lambda d, r, k, *_: f(r, d, k)
    sel, trow = gather_plan(n_in, halo_nodes, halo_mask, N, bn)
    plan = [sel, trow]
    if dq:
        assert scales.shape == (N,), (scales.shape, table.shape)
        plan.append(jnp.take(scales, trow, mode="clip"))
    if pw is None:
        # The plan of each adjacency block's columns as [R*K, bn] rows,
        # one per block, in (GROUP, bn) SMEM blocks (Mosaic's tiling): the
        # group holding block k's row (route, waits) and the group holding
        # block k+1's (prefetch).
        nb = R * K
        nbp = _round(nb, GROUP)
        rows = [jnp.pad(jnp.take(a, blk_cols.reshape(nb), axis=0,
                                 mode="clip"), ((0, nbp - nb), (0, 0)))
                for a in plan]
        cur = pl.BlockSpec((GROUP, bn),
                           idx(lambda r, d, k: ((r * K + k) // GROUP, 0)),
                           memory_space=pltpu.SMEM)
        nxt = pl.BlockSpec(
            (GROUP, bn),
            idx(lambda r, d, k: ((r * K + jnp.minimum(k + 1, K - 1))
                                 // GROUP, 0)),
            memory_space=pltpu.SMEM)
        plan_specs = [cur, cur, nxt, nxt] + [cur] * dq
        plan = rows[:2] * 2 + rows[2:]
    else:
        plan_specs = [pl.BlockSpec(memory_space=pltpu.SMEM)] * len(plan)
    st_w = tw if vq else w
    in_specs = plan_specs + [
        pl.BlockSpec(memory_space=pl.ANY),
        pl.BlockSpec(memory_space=pl.ANY),
        pl.BlockSpec((GROUP, st_w),
                     idx(lambda r, d, k: (0, 0)) if vq else
                     idx(lambda r, d, k: (0, d))),
        pl.BlockSpec((1, 1, bn, bn), idx(lambda r, d, k: (r, k, 0, 0)))]
    operands = plan + [xp, table, tail, blk_vals]
    scratch = [dst,
               pltpu.VMEM((2, bn // GROUP, GROUP, w), x_in.dtype),   # sx
               pltpu.VMEM((2, bn, GROUP, st_w), table.dtype),        # st
               pltpu.SemaphoreType.DMA((2,))]
    if vq:
        s_, c, ds = codebook.shape
        assert tw == s_ and s_ * ds <= D, (table.shape, codebook.shape, D)
        in_specs.append(pl.BlockSpec((s_, c, ds),
                                     idx(lambda r, d, k: (0, 0, 0))))
        operands.append(codebook)
        scratch.append(pltpu.VMEM((bn, s_), jnp.int32))           # codes
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
