"""Fused history-gather + block-CSR SpMM Pallas kernel (GAS aggregation).

The unfused GAS layer materializes

    x_all = concat([x_in, pull(table, halo_nodes) * halo_mask, 0])

and then runs the BCSR SpMM over x_all — a full halo gather plus a full
concatenate copy of the layer input, per layer, per batch, that exist only
to be read once by the matmul. This kernel removes both: the virtual x_all
is never built. A *gather plan* (sel/trow, one entry per adjacency-block
row, see `gather_plan`) tells each grid step where virtual column
`v = blk_cols[r, k] * bn + row` actually lives:

    sel == 0 : in-batch  -> x_in[v]      (current layer activations)
    sel == 1 : halo      -> table[trow]  (historical embedding, read
                                          directly out of the history table)
    sel == 2 : masked halo / dummy / padding -> exact zeros

Grid (R, D/bd, K): each step owns one bn x bn adjacency block. The plan
reaches SMEM one block row at a time (block k's and block k+1's), not as
whole scalar-prefetch arrays: SMEM holds 1 MiB, and an [R, K, bn] int32
plan outgrows it at R * K > ~2k blocks (a serving refresh of a 10k-node
graph needs ~5k). The row
DMAs are HAND-PIPELINED with `pltpu.make_async_copy` double buffering —
x_in and the history table stay whole (`pl.ANY`), and each step (a)
starts the DMAs for block k+1 into the other slot, (b) waits on the slot
that block k's rows were prefetched into, and only then (c) routes and
dequantizes the staged rows and contracts the bn x bn block on the MXU.

A DMA moves whole 8-row HBM tiles (see `tiles.py`). In-batch rows are
contiguous — the 8 virtual rows of a group are 8 consecutive x_in rows —
so x_in moves in bn/8 aligned group DMAs per block at no extra cost (the
wrapper pads x_in to a multiple of 8 rows). A halo row moves as the tile
group that holds it and is picked out in VMEM (`tiles.pick_row`); rows
of the table's partial last tile come from a small `tail` operand
instead, so no DMA reads past the table.

Quantized histories (`scales` given): the table holds symmetric per-row
int8 rows; only int8 bytes cross HBM for halo columns (the staging buffer
is int8 too). The per-row dequant scale is pre-gathered into an [R, K, bn]
plan operand (`rscl = scales[trow]`), so the dequant multiply
runs between the pick and the MXU contraction — the f32 halo tensor never
exists in HBM. With `codebook` as well, the table holds uint8 vq code
rows: the picked code rows are decoded against the resident VMEM codebook
(`tiles.vq_decode_tile`) before the contraction.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .tiles import GROUP, pick_row, vq_decode_tile


def gather_plan(blk_cols: jnp.ndarray, halo_nodes: jnp.ndarray,
                halo_mask: jnp.ndarray, n_in: int, n_table: int,
                bn: int):
    """Per-(block, row) source plan for `gather_spmm` (module docstring).

    Returns (sel, trow), each [R, K, bn] int32, computed from the block
    column ids and the batch's halo index vector. Cheap (R*K*bn elements)
    and jit-traceable — runs on device inside the train step.
    """
    row = jnp.arange(bn, dtype=jnp.int32)
    v = blk_cols[:, :, None].astype(jnp.int32) * bn + row    # virtual column
    max_h = halo_nodes.shape[0]
    is_in = v < n_in
    hidx = jnp.clip(v - n_in, 0, max_h - 1)
    halo_ok = (v >= n_in) & (v < n_in + max_h) & jnp.take(halo_mask, hidx)
    trow = jnp.where(halo_ok,
                     jnp.clip(jnp.take(halo_nodes, hidx), 0, n_table - 1),
                     0).astype(jnp.int32)
    sel = jnp.where(is_in, 0, jnp.where(halo_ok, 1, 2)).astype(jnp.int32)
    return sel, trow


def _row_dmas(cols_ref, x_ref, tbl_ref, sx_ref, st_ref, sem_ref, r, d,
              blk, sel_ref, trow_ref, j, slot, *, bn, bd, n_in, n8, vq,
              start):
    """Issue (start=True) or drain (start=False) the DMAs of adjacency
    block (r, blk), whose plan is row j of `sel_ref`/`trow_ref`, into
    double-buffer slot `slot`: one aligned group DMA per 8 in-batch
    virtual rows, one tile-group DMA per halo row. Waits rebuild the
    same descriptors, so one per-slot semaphore balances."""
    c = cols_ref[r, blk]

    def x_group(j, carry):
        v0 = c * bn + j * GROUP

        @pl.when(v0 < n_in)
        def _():
            dma = pltpu.make_async_copy(
                x_ref.at[pl.ds(pl.multiple_of(v0, GROUP), GROUP),
                         pl.ds(d * bd, bd)],
                sx_ref.at[slot, j], sem_ref.at[slot])
            dma.start() if start else dma.wait()
        return carry

    def halo_row(row, carry):
        t = trow_ref[j, row]

        @pl.when((sel_ref[j, row] == 1) & (t < n8))
        def _():
            base = pl.ds(pl.multiple_of(t // GROUP * GROUP, GROUP), GROUP)
            src = (tbl_ref.at[base] if vq else
                   tbl_ref.at[base, pl.ds(d * bd, bd)])
            dma = pltpu.make_async_copy(src, st_ref.at[slot, row],
                                        sem_ref.at[slot])
            dma.start() if start else dma.wait()
        return carry

    jax.lax.fori_loop(0, bn // GROUP, x_group, None)
    jax.lax.fori_loop(0, bn, halo_row, None)


def _make_kernel(*, bn, bd, nd, n_in, n8, dq, vq):
    def kernel(*refs):
        cols_ref = refs[0]
        sel_ref, trow_ref, sel_nx, trow_nx = refs[1:5]
        rscl_ref = refs[5] if dq else None
        refs = refs[5 + dq:]
        x_ref, tbl_ref, tail_ref, vals_ref = refs[:4]
        cb_ref = refs[4] if vq else None
        out_ref, sx_ref, st_ref, gx_ref, sem_ref = refs[4 + vq:9 + vq]
        code_ref = refs[9 + vq] if vq else None
        r = pl.program_id(0)
        d = pl.program_id(1)
        k = pl.program_id(2)
        nk = pl.num_programs(2)
        slot = jax.lax.rem(k, 2)
        # rows of blocks k and k+1 within their (GROUP, bn) plan blocks
        j = jax.lax.rem(r * nk + k, GROUP)
        j_nx = jax.lax.rem(r * nk + jnp.minimum(k + 1, nk - 1), GROUP)
        dmas = functools.partial(
            _row_dmas, cols_ref, x_ref, tbl_ref, sx_ref, st_ref, sem_ref,
            r, d, bn=bn, bd=bd, n_in=n_in, n8=n8, vq=vq)

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

        def halo_tile(row):
            t = trow_ref[j, row]
            return jnp.where(t < n8, st_ref[slot, row], tail_ref[...]), t

        if vq:
            # decode every staged code row at once; rows that are not
            # halo rows decode garbage that the route below discards
            def stage_codes(row, carry):
                tile, t = halo_tile(row)
                code_ref[pl.ds(row, 1), :] = pick_row(tile, t % GROUP)
                return carry

            jax.lax.fori_loop(0, bn, stage_codes, None)
            rec = vq_decode_tile(code_ref[...], cb_ref[...])
            rec = jnp.pad(rec, ((0, 0), (0, nd * bd - rec.shape[1])))
            dec = rec[:, :bd]
            for i in range(1, nd):
                dec = jnp.where(d == i, rec[:, i * bd:(i + 1) * bd], dec)
            # park the decoded block in gx; the route reads it row-wise
            # before overwriting the same row
            gx_ref[...] = dec

        # route each virtual row: in-batch (sx), halo (st, dequantized for
        # int8/vq tables), or exact zeros
        def route(row, carry):
            s = sel_ref[j, row]
            xv = pick_row(sx_ref[slot, row // GROUP], row % GROUP)
            if vq:
                tv = gx_ref[pl.ds(row, 1), :]
            else:
                tile, t = halo_tile(row)
                tv = pick_row(tile, t % GROUP)
            if dq:
                tv = tv * rscl_ref[j, row]
            gx_ref[pl.ds(row, 1), :] = jnp.where(
                s == 0, xv, jnp.where(s == 1, tv, 0.0))
            return carry

        jax.lax.fori_loop(0, bn, route, None)
        out_ref[...] += jnp.dot(vals_ref[0, 0], gx_ref[...],
                                preferred_element_type=jnp.float32)
    return kernel


@functools.partial(jax.jit, static_argnames=("bn", "bd", "interpret"))
def gather_spmm(x_in: jnp.ndarray, table: jnp.ndarray,
                blk_vals: jnp.ndarray, blk_cols: jnp.ndarray,
                sel: jnp.ndarray, trow: jnp.ndarray,
                scales: jnp.ndarray = None,
                codebook: jnp.ndarray = None,
                *, bn: int = 128, bd: int = 128,
                interpret: bool) -> jnp.ndarray:
    """out [R*bn, D] = A @ [x_in ; dequant(table)[halo] ; 0] without
    building the bracket. x_in [n_in, D] with D % bd == 0; trow must be
    pre-clipped to the table's row range (see `gather_plan`). With
    `scales` [N] f32 the table rows are int8 and dequantized in-kernel
    (module docstring); with `codebook` [S, C, ds] too, the table holds
    uint8 vq code rows [N, S] that are staged whole (S bytes per halo
    row) and codebook-decoded in VMEM right before the contraction — the
    codebook rides as a whole-VMEM operand. Output is fp32 (MXU-native
    accumulation); the caller casts."""
    R, K, bn_, bn2 = blk_vals.shape
    assert bn_ == bn and bn2 == bn, (blk_vals.shape, bn)
    n_in, D = x_in.shape
    assert D % bd == 0, (x_in.shape, bd)
    assert codebook is not None or table.shape[1] == D, (table.shape, D)
    assert sel.shape == (R, K, bn), (sel.shape, (R, K, bn))
    dq = scales is not None
    vq = codebook is not None
    N, tw = table.shape
    n8 = N // GROUP * GROUP
    # rows past the last whole tile: a [GROUP, tw] VMEM operand
    tail = jnp.pad(table[n8:], ((0, GROUP - (N - n8)), (0, 0)))
    xp = jnp.pad(x_in, ((0, -n_in % GROUP), (0, 0)))

    # The plan as [R*K, bn] rows, one per block, in (GROUP, bn) SMEM
    # blocks (Mosaic's tiling): the group holding block k's row (route,
    # waits) and the group holding block k+1's (prefetch).
    nb = R * K
    nbp = -(-nb // GROUP) * GROUP

    def rows(a):
        return jnp.pad(a.reshape(nb, bn), ((0, nbp - nb), (0, 0)))

    cur = pl.BlockSpec((GROUP, bn),
                       lambda r, d, k, *_: ((r * K + k) // GROUP, 0),
                       memory_space=pltpu.SMEM)
    nxt = pl.BlockSpec(
        (GROUP, bn),
        lambda r, d, k, *_: ((r * K + jnp.minimum(k + 1, K - 1)) // GROUP,
                             0),
        memory_space=pltpu.SMEM)
    plan_specs = [cur, cur, nxt, nxt]
    plan = [rows(sel), rows(trow)] * 2
    if dq:
        assert scales.shape == (N,), (scales.shape, table.shape)
        plan_specs.append(cur)
        plan.append(rows(jnp.take(scales, trow, mode="clip")))
    st_w = tw if vq else bd
    in_specs = plan_specs + [
        pl.BlockSpec(memory_space=pl.ANY),
        pl.BlockSpec(memory_space=pl.ANY),
        pl.BlockSpec((GROUP, st_w),
                     (lambda r, d, k, *_: (0, 0)) if vq else
                     (lambda r, d, k, *_: (0, d))),
        pl.BlockSpec((1, 1, bn, bn), lambda r, d, k, *_: (r, k, 0, 0))]
    operands = plan + [xp, table, tail, blk_vals]
    scratch = [pltpu.VMEM((2, bn // GROUP, GROUP, bd), x_in.dtype),  # sx
               pltpu.VMEM((2, bn, GROUP, st_w), table.dtype),        # st
               pltpu.VMEM((bn, bd), jnp.float32),                    # gx
               pltpu.SemaphoreType.DMA((2,))]
    if vq:
        s_, c, ds = codebook.shape
        assert tw == s_ and s_ * ds <= D, (table.shape, codebook.shape, D)
        in_specs.append(pl.BlockSpec((s_, c, ds),
                                     lambda r, d, k, *_: (0, 0, 0)))
        operands.append(codebook)
        scratch.append(pltpu.VMEM((bn, s_), jnp.int32))           # codes
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(R, D // bd, K),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bn, bd), lambda r, d, k, *_: (r, d)),
        scratch_shapes=scratch,
    )
    kernel = _make_kernel(bn=bn, bd=bd, nd=D // bd, n_in=n_in, n8=n8,
                          dq=dq, vq=vq)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((R * bn, D), jnp.float32),
        interpret=interpret,
    )(blk_cols, *operands)
