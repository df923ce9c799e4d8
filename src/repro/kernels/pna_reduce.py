"""Blockwise multi-aggregator reduction Pallas kernels — PNA on the MXU path.

PNA aggregates per-edge messages msg_e = relu(xd[dst_e] + xs[src_e]) with
mean / min / max (+ degree scalers). The per-edge transform decomposes into
two *per-node* linear maps (xd = x_all @ w1_dst, xs = x_all @ w1_src + b1),
so — like the fused gather kernel (`fused.py`) — each bn x bn adjacency
block can stream its destination rows through VMEM and reduce without
materializing the [E, f] message matrix: for destination row a the whole
[bn, f] message tile relu(xd[a] + xs_block) is formed on the VPU and
reduced against the multiplicity row m_a* of the unit-weight BCSR block.

Forward (`pna_reduce_fwd`), grid (R, F/bd, K, bn) with the destination row
innermost: running (sum, min, max, count) state persists in VMEM scratch
across the (K, row) dimensions — the same cross-grid online-state design
as the edge-softmax kernel. Tie *counts* at the running min/max are
maintained online too (multiplicity-weighted), because the backward pass
distributes min/max cotangents evenly across ties — exactly matching
`jax.ops.segment_min/max`'s even-split gradient.

Backward = one pass per block structure:
  * `pna_reduce_bwd_row` (forward blocks)    -> dxd (destination sums)
  * `pna_reduce_bwd_col` (transposed blocks) -> dxs (source sums)
Both recompute messages blockwise (bit-identical f32 arithmetic, so tie
detection against the saved min/max is exact) and apply
    dmsg = relu'(z) * m * (g_sum + tie_min * g_min/c_min
                                 + tie_max * g_max/c_max).

All internal compute is float32; callers pad to tile boundaries (see
`ops.pna_reduce`). A TPU block's last two dims must be (8, 128)-aligned
or whole, so the multiplicity row of destination a is read out of the
whole bn x bn block (fetched once per k) and turned into a column by one
bn x bn transpose (`tiles.to_col`).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .tiles import to_col, to_row

BIG = 1e30      # f32-internal min/max sentinel (kernels compute in f32)


def _fwd_kernel(cols_ref, xd_ref, xs_ref, mrow_ref, s_ref, mn_ref, mx_ref,
                cnt_ref, cmin_ref, cmax_ref,
                s_acc, mn_acc, mx_acc, cnt_scr, cmin_acc, cmax_acc):
    k = pl.program_id(2)
    a = pl.program_id(3)

    @pl.when((k == 0) & (a == 0))
    def _init():
        s_acc[...] = jnp.zeros_like(s_acc)
        mn_acc[...] = jnp.full_like(mn_acc, BIG)
        mx_acc[...] = jnp.full_like(mx_acc, -BIG)
        cnt_scr[...] = jnp.zeros_like(cnt_scr)
        cmin_acc[...] = jnp.zeros_like(cmin_acc)
        cmax_acc[...] = jnp.zeros_like(cmax_acc)

    m = to_col(mrow_ref[0, 0, pl.ds(a, 1), :])[:, 0:1]    # [bn, 1] mults
    xd_a = xd_ref[pl.ds(a, 1), :].astype(jnp.float32)   # [1, bd]
    xs = xs_ref[...].astype(jnp.float32)            # [bn, bd] source tile
    msg = jnp.maximum(xd_a + xs, 0.0)               # [bn, bd]
    valid = m > 0

    row = pl.ds(a, 1)
    s_acc[row, :] += (m * msg).sum(axis=0, keepdims=True)
    cnt_scr[row, :] += m.sum(axis=0, keepdims=True)

    # online min/max with multiplicity-weighted tie counts: a strictly
    # better block value resets the count, an equal one adds to it
    mn_blk = jnp.where(valid, msg, BIG).min(axis=0, keepdims=True)
    new_mn = jnp.minimum(mn_acc[row, :], mn_blk)
    here_mn = (m * jnp.where(valid & (msg == new_mn), 1.0, 0.0)
               ).sum(axis=0, keepdims=True)
    cmin_acc[row, :] = jnp.where(mn_acc[row, :] == new_mn,
                                 cmin_acc[row, :], 0.0) + here_mn
    mn_acc[row, :] = new_mn

    mx_blk = jnp.where(valid, msg, -BIG).max(axis=0, keepdims=True)
    new_mx = jnp.maximum(mx_acc[row, :], mx_blk)
    here_mx = (m * jnp.where(valid & (msg == new_mx), 1.0, 0.0)
               ).sum(axis=0, keepdims=True)
    cmax_acc[row, :] = jnp.where(mx_acc[row, :] == new_mx,
                                 cmax_acc[row, :], 0.0) + here_mx
    mx_acc[row, :] = new_mx

    @pl.when((k == pl.num_programs(2) - 1) & (a == pl.num_programs(3) - 1))
    def _finish():
        has = cnt_scr[...] > 0                      # [bn, 1]
        s_ref[...] = s_acc[...]
        mn_ref[...] = jnp.where(has, mn_acc[...], 0.0)
        mx_ref[...] = jnp.where(has, mx_acc[...], 0.0)
        cnt_ref[0] = to_row(cnt_scr[...])
        cmin_ref[...] = cmin_acc[...]
        cmax_ref[...] = cmax_acc[...]


@functools.partial(jax.jit, static_argnames=("bn", "bd", "interpret"))
def pna_reduce_fwd(xd: jnp.ndarray, xs: jnp.ndarray,
                   ublk_vals: jnp.ndarray, blk_cols: jnp.ndarray, *,
                   bn: int = 128, bd: int = 128, interpret: bool):
    """Blockwise sum/min/max/count of msg = relu(xd[dst] + xs[src]).

    xd [R*bn, Fp] destination-side transform; xs [C*bn, Fp] source-side;
    ublk_vals [R, K, bn, bn] edge multiplicities; blk_cols [R, K].
    Returns (s, mn, mx, cnt, cmin, cmax): s/mn/mx/cmin/cmax [R*bn, Fp]
    f32 (mn/mx are 0 for empty rows), cnt [R*bn] f32. cmin/cmax are the
    multiplicity-weighted tie counts at the min/max, consumed by the
    backward kernels' even-split gradient.
    """
    R, K, bn_, bn2 = ublk_vals.shape
    assert bn_ == bn and bn2 == bn, (ublk_vals.shape, bn)
    Rp, Fp = xd.shape
    assert Rp == R * bn and Fp % bd == 0, (xd.shape, bn, bd)
    assert xs.shape[1] == Fp

    grid = (R, Fp // bd, K, bn)
    tile = lambda r, f, k, a, cols: (r, f)                     # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, bd), tile),
            pl.BlockSpec((bn, bd), lambda r, f, k, a, cols: (cols[r, k], f)),
            pl.BlockSpec((1, 1, bn, bn),
                         lambda r, f, k, a, cols: (r, k, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bn, bd), tile),
            pl.BlockSpec((bn, bd), tile),
            pl.BlockSpec((bn, bd), tile),
            pl.BlockSpec((1, 1, bn), lambda r, f, k, a, cols: (r, 0, 0)),
            pl.BlockSpec((bn, bd), tile),
            pl.BlockSpec((bn, bd), tile),
        ],
        scratch_shapes=[pltpu.VMEM((bn, bd), jnp.float32),
                        pltpu.VMEM((bn, bd), jnp.float32),
                        pltpu.VMEM((bn, bd), jnp.float32),
                        pltpu.VMEM((bn, 1), jnp.float32),
                        pltpu.VMEM((bn, bd), jnp.float32),
                        pltpu.VMEM((bn, bd), jnp.float32)],
    )
    s, mn, mx, cnt, cmin, cmax = pl.pallas_call(
        _fwd_kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((Rp, Fp), jnp.float32),
                   jax.ShapeDtypeStruct((Rp, Fp), jnp.float32),
                   jax.ShapeDtypeStruct((Rp, Fp), jnp.float32),
                   jax.ShapeDtypeStruct((R, 1, bn), jnp.float32),
                   jax.ShapeDtypeStruct((Rp, Fp), jnp.float32),
                   jax.ShapeDtypeStruct((Rp, Fp), jnp.float32)],
        interpret=interpret,
    )(blk_cols, xd, xs, ublk_vals)
    return s, mn, mx, cnt.reshape(Rp), cmin, cmax


def _dmsg(msg, z, m, gs, gmn, gmx, mn, mx, cmin, cmax):
    """Even-split cotangent of (sum, min, max) w.r.t. one message tile.
    All stat operands broadcast against msg [*, bd]; m [*, 1] is the
    multiplicity aligned with msg's leading axis."""
    valid = m > 0
    tie_mn = jnp.where(valid & (msg == mn), 1.0, 0.0)
    tie_mx = jnp.where(valid & (msg == mx), 1.0, 0.0)
    grad = gs + tie_mn * gmn / jnp.maximum(cmin, 1.0) \
        + tie_mx * gmx / jnp.maximum(cmax, 1.0)
    return jnp.where(z > 0, 1.0, 0.0) * m * grad


def _bwd_row_kernel(cols_ref, xd_ref, xs_ref, mrow_ref, gs_ref, gmn_ref,
                    gmx_ref, mn_ref, mx_ref, cmin_ref, cmax_ref,
                    dxd_ref, acc):
    k = pl.program_id(2)
    a = pl.program_id(3)

    @pl.when((k == 0) & (a == 0))
    def _init():
        acc[...] = jnp.zeros_like(acc)

    m = to_col(mrow_ref[0, 0, pl.ds(a, 1), :])[:, 0:1]    # over sources
    row = pl.ds(a, 1)
    z = xd_ref[row, :].astype(jnp.float32) + \
        xs_ref[...].astype(jnp.float32)             # [bn_src, bd]
    msg = jnp.maximum(z, 0.0)
    d = _dmsg(msg, z, m, gs_ref[row, :], gmn_ref[row, :], gmx_ref[row, :],
              mn_ref[row, :], mx_ref[row, :], cmin_ref[row, :],
              cmax_ref[row, :])
    acc[row, :] += d.sum(axis=0, keepdims=True)

    @pl.when((k == pl.num_programs(2) - 1) & (a == pl.num_programs(3) - 1))
    def _finish():
        dxd_ref[...] = acc[...]


@functools.partial(jax.jit, static_argnames=("bn", "bd", "interpret"))
def pna_reduce_bwd_row(xd, xs, gs, gmn, gmx, mn, mx, cmin, cmax,
                       ublk_vals, blk_cols, *, bn: int = 128,
                       bd: int = 128, interpret: bool):
    """Destination-side cotangent dxd [R*bn, Fp] = sum_src dmsg over the
    forward block structure. gs/gmn/gmx are the (s, mn, mx) cotangents;
    mn/mx/cmin/cmax are the forward kernel's saved stats."""
    R, K, bn_, _ = ublk_vals.shape
    assert bn_ == bn
    Rp, Fp = xd.shape
    assert Rp == R * bn and Fp % bd == 0

    grid = (R, Fp // bd, K, bn)
    tile = lambda r, f, k, a, cols: (r, f)                     # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, bd), tile),
            pl.BlockSpec((bn, bd), lambda r, f, k, a, cols: (cols[r, k], f)),
            pl.BlockSpec((1, 1, bn, bn),
                         lambda r, f, k, a, cols: (r, k, 0, 0)),
        ] + [pl.BlockSpec((bn, bd), tile)] * 7,
        out_specs=pl.BlockSpec((bn, bd), tile),
        scratch_shapes=[pltpu.VMEM((bn, bd), jnp.float32)],
    )
    return pl.pallas_call(
        _bwd_row_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Rp, Fp), jnp.float32),
        interpret=interpret,
    )(blk_cols, xd, xs, ublk_vals, gs, gmn, gmx, mn, mx, cmin, cmax)


def _bwd_col_kernel(colst_ref, xs_ref, xd_ref, mrow_ref, gs_ref, gmn_ref,
                    gmx_ref, mn_ref, mx_ref, cmin_ref, cmax_ref,
                    dxs_ref, acc):
    k = pl.program_id(2)
    s_row = pl.program_id(3)

    @pl.when((k == 0) & (s_row == 0))
    def _init():
        acc[...] = jnp.zeros_like(acc)

    # transposed block: rows = sources, columns = destinations; all stat
    # tiles are destination-space (fetched via the transposed column ids)
    m = to_col(mrow_ref[0, 0, pl.ds(s_row, 1), :])[:, 0:1]  # over dsts
    row = pl.ds(s_row, 1)
    z = xs_ref[row, :].astype(jnp.float32) + \
        xd_ref[...].astype(jnp.float32)             # [bn_dst, bd]
    msg = jnp.maximum(z, 0.0)
    d = _dmsg(msg, z, m, gs_ref[...], gmn_ref[...], gmx_ref[...],
              mn_ref[...], mx_ref[...], cmin_ref[...], cmax_ref[...])
    acc[row, :] += d.sum(axis=0, keepdims=True)

    @pl.when((k == pl.num_programs(2) - 1) & (s_row == pl.num_programs(3) - 1))
    def _finish():
        dxs_ref[...] = acc[...]


@functools.partial(jax.jit, static_argnames=("bn", "bd", "interpret"))
def pna_reduce_bwd_col(xd, xs, gs, gmn, gmx, mn, mx, cmin, cmax,
                       ublk_vals_t, blk_cols_t, *, bn: int = 128,
                       bd: int = 128, interpret: bool):
    """Source-side cotangent dxs [C*bn, Fp] = sum_dst dmsg over the
    *transposed* block structure (destination-space stat tiles are fetched
    through the transposed column ids)."""
    R_t, K_t, bn_, _ = ublk_vals_t.shape
    assert bn_ == bn
    Cp, Fp = xs.shape
    assert Cp == R_t * bn and Fp % bd == 0

    grid = (R_t, Fp // bd, K_t, bn)
    tile = lambda r, f, k, a, cols: (r, f)                     # noqa: E731
    col_tile = lambda r, f, k, a, cols: (cols[r, k], f)        # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, bd), tile),
            pl.BlockSpec((bn, bd), col_tile),
            pl.BlockSpec((1, 1, bn, bn),
                         lambda r, f, k, a, cols: (r, k, 0, 0)),
        ] + [pl.BlockSpec((bn, bd), col_tile)] * 7,
        out_specs=pl.BlockSpec((bn, bd), tile),
        scratch_shapes=[pltpu.VMEM((bn, bd), jnp.float32)],
    )
    return pl.pallas_call(
        _bwd_col_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Cp, Fp), jnp.float32),
        interpret=interpret,
    )(blk_cols_t, xs, xd, ublk_vals_t, gs, gmn, gmx, mn, mx, cmin, cmax)
