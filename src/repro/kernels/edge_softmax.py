"""Block-sparse edge-softmax Pallas kernels — GAT aggregation on the MXU.

GAT's aggregation is a per-destination softmax over *data-dependent*
attention logits, so it cannot ride the fixed-weight BCSR SpMM
(`bcsr_spmm.py`). These kernels give it the same block-dense treatment
with a flash-attention-style **online softmax over column blocks**: for
destination row i with logits e_ij = leaky_relu(ad_i + as_j),

    out_i = sum_j softmax_j(e_ij) * wx_j

is computed without ever materializing per-edge scores in HBM. The edge
structure enters as the *unit-weight* BCSR blocks (`ublk_vals` from
`core.gas.build_batches`): entry [a, b] holds the edge *multiplicity*
m_ab (0 = no edge), so duplicate edges reproduce the COO `segment_*`
semantics exactly (each duplicate contributes its own exp term).

Forward (`edge_softmax_fwd`), grid (R, H, F/bd, K), K innermost:
running-max state (m, l, acc) lives in VMEM scratch across the K
dimension — the first kernel in this repo carrying online-softmax state
across a grid axis; each step rescales by exp(m_prev - m_new), adds
p = m_ab * exp(s - m_new), and feeds p through one bn x bn MXU matmul
against the value tile. The final row max M and normalizer L are written
out for the backward pass.

Backward = one pass per block structure (mirroring `ops._spmm_kernel_bwd`):
  * `edge_softmax_bwd_row` (forward blocks)  -> dad   (row/destination sums)
  * `edge_softmax_bwd_col` (transposed blocks) -> das, dwx (column/source
    sums + the attention-weighted value cotangent alpha^T @ g)
Both recompute alpha from (ad, as, M, L) blockwise — no per-edge residuals
— and accumulate the softmax Jacobian dz = alpha * (g.v - delta) *
lrelu'(z) with the delta term folded in once per K step, so the feature
dimension can be tiled and summed like any other contraction.

All internal compute is float32; callers pad rows/features to tile
boundaries (see `ops.edge_softmax_aggregate`). The per-node vectors
(ad/as/M/L/delta and the dad/das outputs, [H, n]) move as whole-H
[H, bn] blocks — a TPU block's second-minor dim must be a multiple of 8
or the array's whole dim — and each step reads or writes row h.
Per-destination columns and per-source rows swap orientation by one
bn x bn transpose (`tiles.to_col` / `tiles.to_row`).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .tiles import to_col, to_row

NEG = -1e30     # f32-internal mask value (kernels always compute in f32)
TINY = 1e-30


def _vec(ref, h):
    """Row h of a whole-H [H, bn] block as an f32 [1, bn] row."""
    return ref[pl.ds(h, 1), :].astype(jnp.float32)


def _scores(ad_col, as_row, mult, neg_slope):
    """Masked leaky-relu attention scores for one bn x bn block (f32).
    ad_col [bn, bn] (constant along columns) / as_row [1, bn]."""
    z = ad_col + as_row
    s = jnp.where(z > 0, z, neg_slope * z)
    return z, jnp.where(mult > 0, s, NEG)


def _fwd_kernel(cols_ref, ad_ref, as_ref, wx_ref, ublk_ref,
                out_ref, mmax_ref, lsum_ref, m_scr, l_scr, acc,
                *, neg_slope: float):
    h = pl.program_id(1)
    k = pl.program_id(3)

    @pl.when(k == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc[...] = jnp.zeros_like(acc)

    ad = to_col(_vec(ad_ref, h))                      # dst logits down rows
    as_ = _vec(as_ref, h)                           # src logits along lanes
    mult = ublk_ref[0, 0]                           # [bn, bn] multiplicities
    _, s = _scores(ad, as_, mult, neg_slope)

    m_prev = m_scr[...]                             # [bn, 1]
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = mult * jnp.exp(s - m_new)                   # [bn, bn]
    l_scr[...] = l_scr[...] * alpha + p.sum(axis=-1, keepdims=True)
    acc[...] = acc[...] * alpha + jnp.dot(
        p, wx_ref[0].astype(jnp.float32),
        preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(k == pl.num_programs(3) - 1)
    def _finish():
        out_ref[0] = acc[...] / jnp.maximum(l_scr[...], TINY)
        mmax_ref[pl.ds(h, 1), :] = to_row(m_scr[...])
        lsum_ref[pl.ds(h, 1), :] = to_row(l_scr[...])


@functools.partial(jax.jit,
                   static_argnames=("neg_slope", "bn", "bd", "interpret"))
def edge_softmax_fwd(ad: jnp.ndarray, as_: jnp.ndarray, wx: jnp.ndarray,
                     ublk_vals: jnp.ndarray, blk_cols: jnp.ndarray, *,
                     neg_slope: float = 0.2, bn: int = 128, bd: int = 128,
                     interpret: bool):
    """Online-softmax attention aggregation over BCSR blocks.

    ad [H, R*bn] destination logits; as_ [H, C*bn] source logits;
    wx [H, C*bn, Fp] per-head values (Fp % bd == 0); ublk_vals
    [R, K, bn, bn] edge multiplicities; blk_cols [R, K] (prefetched).
    Returns (out [H, R*bn, Fp], M [H, R*bn], L [H, R*bn]) — all f32;
    M/L are the per-row softmax stats the backward kernels reuse.
    """
    R, K, bn_, bn2 = ublk_vals.shape
    assert bn_ == bn and bn2 == bn, (ublk_vals.shape, bn)
    H, Cp = as_.shape
    Fp = wx.shape[-1]
    assert ad.shape == (H, R * bn) and wx.shape == (H, Cp, Fp)
    assert Fp % bd == 0, (Fp, bd)

    grid = (R, H, Fp // bd, K)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((H, bn), lambda r, h, f, k, cols: (0, r)),
            pl.BlockSpec((H, bn), lambda r, h, f, k, cols: (0, cols[r, k])),
            pl.BlockSpec((1, bn, bd),
                         lambda r, h, f, k, cols: (h, cols[r, k], f)),
            pl.BlockSpec((1, 1, bn, bn),
                         lambda r, h, f, k, cols: (r, k, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bn, bd), lambda r, h, f, k, cols: (h, r, f)),
            pl.BlockSpec((H, bn), lambda r, h, f, k, cols: (0, r)),
            pl.BlockSpec((H, bn), lambda r, h, f, k, cols: (0, r)),
        ],
        scratch_shapes=[pltpu.VMEM((bn, 1), jnp.float32),
                        pltpu.VMEM((bn, 1), jnp.float32),
                        pltpu.VMEM((bn, bd), jnp.float32)],
    )
    kern = functools.partial(_fwd_kernel, neg_slope=neg_slope)
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((H, R * bn, Fp), jnp.float32),
                   jax.ShapeDtypeStruct((H, R * bn), jnp.float32),
                   jax.ShapeDtypeStruct((H, R * bn), jnp.float32)],
        interpret=interpret,
    )(blk_cols, ad, as_, wx, ublk_vals)


def _alpha(ad_col, as_row, mult, mmax, lsum, neg_slope):
    """Recompute normalized attention + leaky-relu slope for one block.
    mmax/lsum [bn, bn] are constant along the destination rows (like
    ad_col)."""
    z, s = _scores(ad_col, as_row, mult, neg_slope)
    p = mult * jnp.exp(s - mmax)
    alpha = p / jnp.maximum(lsum, TINY)
    slope = jnp.where(z > 0, 1.0, neg_slope)
    return alpha, alpha * slope


def _bwd_row_kernel(cols_ref, ad_ref, as_ref, wx_ref, g_ref, mmax_ref,
                    lsum_ref, delta_ref, ublk_ref, dad_ref, dad_scr,
                    *, neg_slope: float):
    h = pl.program_id(1)
    ft = pl.program_id(2)
    k = pl.program_id(3)

    @pl.when((ft == 0) & (k == 0))
    def _init():
        dad_scr[...] = jnp.zeros_like(dad_scr)

    ad = to_col(_vec(ad_ref, h))
    as_ = _vec(as_ref, h)
    mult = ublk_ref[0, 0]
    mmax = to_col(_vec(mmax_ref, h))                   # dst rows
    lsum = to_col(_vec(lsum_ref, h))
    _, ap = _alpha(ad, as_, mult, mmax, lsum, neg_slope)

    # dz = alpha' * (g.v - delta): the f-contraction g.v is tiled over ft;
    # the delta term is folded in once (at ft == 0) per K step
    gv = jnp.dot(g_ref[0].astype(jnp.float32),
                 wx_ref[0].astype(jnp.float32).T,
                 preferred_element_type=jnp.float32)  # [bn_dst, bn_src]
    dad_scr[...] += (ap * gv).sum(axis=-1, keepdims=True)

    @pl.when(ft == 0)
    def _delta_term():
        delta = to_col(_vec(delta_ref, h))[:, 0:1]
        dad_scr[...] += -(ap.sum(axis=-1, keepdims=True) * delta)

    @pl.when((ft == pl.num_programs(2) - 1) & (k == pl.num_programs(3) - 1))
    def _finish():
        dad_ref[pl.ds(h, 1), :] = to_row(dad_scr[...])


@functools.partial(jax.jit,
                   static_argnames=("neg_slope", "bn", "bd", "interpret"))
def edge_softmax_bwd_row(ad, as_, wx, g, mmax, lsum, delta, ublk_vals,
                         blk_cols, *, neg_slope: float = 0.2, bn: int = 128,
                         bd: int = 128, interpret: bool):
    """Destination-side cotangent dad [H, R*bn] = rowsum(dz) over the
    forward block structure. g is the out cotangent [H, R*bn, Fp];
    delta [H, R*bn] = sum_f g * out (computed by the caller in XLA)."""
    R, K, bn_, _ = ublk_vals.shape
    assert bn_ == bn
    H, Rp = ad.shape
    Fp = wx.shape[-1]
    assert g.shape == (H, Rp, Fp) and Rp == R * bn

    grid = (R, H, Fp // bd, K)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((H, bn), lambda r, h, f, k, cols: (0, r)),
            pl.BlockSpec((H, bn), lambda r, h, f, k, cols: (0, cols[r, k])),
            pl.BlockSpec((1, bn, bd),
                         lambda r, h, f, k, cols: (h, cols[r, k], f)),
            pl.BlockSpec((1, bn, bd), lambda r, h, f, k, cols: (h, r, f)),
            pl.BlockSpec((H, bn), lambda r, h, f, k, cols: (0, r)),
            pl.BlockSpec((H, bn), lambda r, h, f, k, cols: (0, r)),
            pl.BlockSpec((H, bn), lambda r, h, f, k, cols: (0, r)),
            pl.BlockSpec((1, 1, bn, bn),
                         lambda r, h, f, k, cols: (r, k, 0, 0)),
        ],
        out_specs=pl.BlockSpec((H, bn), lambda r, h, f, k, cols: (0, r)),
        scratch_shapes=[pltpu.VMEM((bn, 1), jnp.float32)],
    )
    kern = functools.partial(_bwd_row_kernel, neg_slope=neg_slope)
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((H, Rp), jnp.float32),
        interpret=interpret,
    )(blk_cols, ad, as_, wx, g, mmax, lsum, delta, ublk_vals)


def _bwd_col_kernel(colst_ref, as_ref, ad_ref, wx_ref, g_ref, mmax_ref,
                    lsum_ref, delta_ref, ublkt_ref, dwx_ref, das_ref,
                    das_scr, *, neg_slope: float):
    h = pl.program_id(1)
    ft = pl.program_id(2)
    k = pl.program_id(3)

    @pl.when((ft == 0) & (k == 0))
    def _init_das():
        das_scr[...] = jnp.zeros_like(das_scr)

    @pl.when(k == 0)
    def _init_dwx():
        dwx_ref[0] = jnp.zeros_like(dwx_ref[0])

    # transposed block: rows = sources, columns = destinations; softmax
    # stats (mmax/lsum/delta) are destination-side -> broadcast over rows
    as_ = to_col(_vec(as_ref, h))                      # sources down rows
    ad = _vec(ad_ref, h)                             # dsts along lanes
    mult_t = ublkt_ref[0, 0]
    z_t = as_ + ad
    s_t = jnp.where(z_t > 0, z_t, neg_slope * z_t)
    s_t = jnp.where(mult_t > 0, s_t, NEG)
    mmax = _vec(mmax_ref, h)                         # [1, bn] dst cols
    lsum = _vec(lsum_ref, h)
    p_t = mult_t * jnp.exp(s_t - mmax)
    alpha_t = p_t / jnp.maximum(lsum, TINY)
    ap = alpha_t * jnp.where(z_t > 0, 1.0, neg_slope)

    gt = g_ref[0].astype(jnp.float32)                # [bn_dst, bd]
    dwx_ref[0] += jnp.dot(alpha_t, gt, preferred_element_type=jnp.float32)

    gv_t = jnp.dot(wx_ref[0].astype(jnp.float32), gt.T,
                   preferred_element_type=jnp.float32)  # [bn_src, bn_dst]
    das_scr[...] += (ap * gv_t).sum(axis=-1, keepdims=True)

    @pl.when(ft == 0)
    def _delta_term():
        delta = _vec(delta_ref, h)
        das_scr[...] += -(ap * delta).sum(axis=-1, keepdims=True)

    @pl.when((ft == pl.num_programs(2) - 1) & (k == pl.num_programs(3) - 1))
    def _finish():
        das_ref[pl.ds(h, 1), :] = to_row(das_scr[...])


@functools.partial(jax.jit,
                   static_argnames=("neg_slope", "bn", "bd", "interpret"))
def edge_softmax_bwd_col(ad, as_, wx, g, mmax, lsum, delta, ublk_vals_t,
                         blk_cols_t, *, neg_slope: float = 0.2,
                         bn: int = 128, bd: int = 128,
                         interpret: bool):
    """Source-side cotangents over the *transposed* block structure:
    dwx [H, C*bn, Fp] = alpha^T @ g and das [H, C*bn] = colsum(dz).
    All destination-side operands (ad, mmax, lsum, delta, g) are fetched
    through the transposed column ids (scalar-prefetched index maps)."""
    R_t, K_t, bn_, _ = ublk_vals_t.shape
    assert bn_ == bn
    H, Cp = as_.shape
    Fp = wx.shape[-1]
    assert Cp == R_t * bn and wx.shape == (H, Cp, Fp)

    grid = (R_t, H, Fp // bd, K_t)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((H, bn), lambda r, h, f, k, cols: (0, r)),
            pl.BlockSpec((H, bn), lambda r, h, f, k, cols: (0, cols[r, k])),
            pl.BlockSpec((1, bn, bd), lambda r, h, f, k, cols: (h, r, f)),
            pl.BlockSpec((1, bn, bd),
                         lambda r, h, f, k, cols: (h, cols[r, k], f)),
            pl.BlockSpec((H, bn), lambda r, h, f, k, cols: (0, cols[r, k])),
            pl.BlockSpec((H, bn), lambda r, h, f, k, cols: (0, cols[r, k])),
            pl.BlockSpec((H, bn), lambda r, h, f, k, cols: (0, cols[r, k])),
            pl.BlockSpec((1, 1, bn, bn),
                         lambda r, h, f, k, cols: (r, k, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bn, bd), lambda r, h, f, k, cols: (h, r, f)),
            pl.BlockSpec((H, bn), lambda r, h, f, k, cols: (0, r)),
        ],
        scratch_shapes=[pltpu.VMEM((bn, 1), jnp.float32)],
    )
    kern = functools.partial(_bwd_col_kernel, neg_slope=neg_slope)
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((H, Cp, Fp), jnp.float32),
                   jax.ShapeDtypeStruct((H, Cp), jnp.float32)],
        interpret=interpret,
    )(blk_cols_t, as_, ad, wx, g, mmax, lsum, delta, ublk_vals_t)
