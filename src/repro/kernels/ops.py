"""Backend dispatch for the GAS hot-path kernels + host-side BCSR builders.

Every history/aggregation op in the training hot path goes through the
three functions `spmm` / `pull_rows` / `push_rows` (plus the GAS-shaped
`gcn_aggregate` and the fused history-gather `gas_aggregate`), each of
which dispatches on a `backend` string:

  * ``"pallas"``    — the Pallas TPU kernels, compiled (`interpret=False`).
  * ``"interpret"`` — the *same* Pallas kernels in interpreter mode, so CPU
                      tests exercise the identical call sites, index maps
                      and aliasing that run on real TPUs.
  * ``"jnp"``       — pure jnp/XLA reference paths (`segment_sum`,
                      `jnp.take`, `.at[].set`): the oracle the kernel
                      paths are tested against, and the fast path on CPU.

`backend=None` auto-selects from `jax.default_backend()` ("pallas" on TPU,
"jnp" otherwise); the default is overridable per-process via
`set_default_backend` or the ``REPRO_KERNEL_BACKEND`` env var. Backend
choice only moves the computation between implementations — results agree
to dtype tolerance (see tests/test_backend_dispatch.py).

The kernel paths have TPU tiling constraints (feature dim multiple of
`bd`, node counts multiple of `bn`); the wrappers here zero-pad inputs up
to tile boundaries and slice the result back, so callers can pass
arbitrary GAS batch shapes. `ref.py` holds the pure-jnp oracles used by
the tests."""
from __future__ import annotations

import functools
import os
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .bcsr_spmm import bcsr_spmm
from .decode_attn import flash_decode
from .gather import gather_rows, gather_rows_vq
from . import scatter
from .scatter import scatter_rows, scatter_rows_vq
from . import edge_softmax as esk
from . import fused
from . import pna_reduce as pnk
from . import ref as kref
from ..utils import spans

BACKENDS = ("pallas", "interpret", "jnp")

_default_backend: Optional[str] = None


def set_default_backend(backend: Optional[str]) -> None:
    """Override the process-wide default (None restores auto-selection)."""
    if backend is not None and backend not in BACKENDS:
        raise ValueError(f"backend must be one of {BACKENDS}, got {backend}")
    global _default_backend
    _default_backend = backend


def resolve_backend(backend: Optional[str] = None) -> str:
    """backend arg > set_default_backend > $REPRO_KERNEL_BACKEND > auto."""
    for cand in (backend, _default_backend,
                 os.environ.get("REPRO_KERNEL_BACKEND") or None):
        if cand is not None:
            if cand not in BACKENDS:
                raise ValueError(
                    f"backend must be one of {BACKENDS}, got {cand}")
            return cand
    return "pallas" if jax.default_backend() == "tpu" else "jnp"


def _pad_dim(n: int, b: int) -> int:
    return -(-n // b) * b


# ---------------------------------------------------------------------------
# Host-side BCSR builders
# ---------------------------------------------------------------------------

def build_bcsr_rect(dst: np.ndarray, src: np.ndarray, w: np.ndarray,
                    n_rows: int, n_cols: int, bn: int = 128
                    ) -> Tuple[np.ndarray, np.ndarray, int, int]:
    """COO (dst, src, w) -> rectangular block-CSR.

    dst in [0, n_rows), src in [0, n_cols). R = ceil(n_rows/bn) row blocks;
    K = max non-empty column blocks over any row block (padding blocks:
    col 0 with all-zero values). Returns (vals [R,K,bn,bn], cols [R,K],
    rows_pad, cols_pad) with rows_pad = R*bn, cols_pad = ceil(n_cols/bn)*bn.

    Fully vectorized host-side setup: one stable sort by block key, slot
    assignment via cumcount over the unique blocks, and a single
    `np.add.at` over flat (block, row, col) indices — no Python per-block
    loop, so `build_batches` stays cheap on regrouped epochs.
    """
    R = max(-(-n_rows // bn), 1)
    C = max(-(-n_cols // bn), 1)
    if len(dst) == 0:
        return (np.zeros((R, 1, bn, bn), np.float32),
                np.zeros((R, 1), np.int32), R * bn, C * bn)
    bi = (dst // bn).astype(np.int64)
    bj = (src // bn).astype(np.int64)
    key = bi * C + bj
    order = np.argsort(key, kind="stable")
    dst_s, src_s, w_s = dst[order], src[order], w[order]
    uniq, inv = np.unique(key[order], return_inverse=True)

    ub_row = (uniq // C).astype(np.int64)
    # slot of each unique block within its row block = cumcount (uniq is
    # sorted, so blocks of one row are contiguous and in ascending j order)
    slot = np.arange(len(uniq)) - np.searchsorted(ub_row, ub_row,
                                                  side="left")
    K = max(int(slot.max()) + 1, 1)
    vals = np.zeros((R * K, bn, bn), np.float32)
    np.add.at(vals, ((ub_row * K + slot)[inv],
                     (dst_s % bn).astype(np.int64),
                     (src_s % bn).astype(np.int64)), w_s)
    cols = np.zeros((R, K), np.int32)
    cols[ub_row, slot] = (uniq % C).astype(np.int32)
    return vals.reshape(R, K, bn, bn), cols, R * bn, C * bn


def build_bcsr(dst: np.ndarray, src: np.ndarray, w: np.ndarray,
               num_nodes: int, bn: int = 128
               ) -> Tuple[np.ndarray, np.ndarray, int]:
    """Square block-CSR over one node space (dst and src in [0, num_nodes)).
    Returns (vals [R,K,bn,bn], cols [R,K], Np) with Np = R*bn."""
    vals, cols, rows_pad, _ = build_bcsr_rect(dst, src, w, num_nodes,
                                              num_nodes, bn=bn)
    return vals, cols, rows_pad


def bcsr_density(blk_cols: np.ndarray, blk_vals: np.ndarray) -> float:
    """Fraction of stored blocks that are structurally non-empty."""
    nonzero = (np.abs(blk_vals).sum(axis=(2, 3)) > 0).sum()
    return float(nonzero) / blk_cols.size


# ---------------------------------------------------------------------------
# Dispatched ops
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _spmm_kernel(x, blk_vals, blk_cols, blk_vals_t, blk_cols_t, bn, bd,
                 interpret):
    return bcsr_spmm(x, blk_vals, blk_cols, bn=bn, bd=bd,
                     interpret=interpret)


def _spmm_kernel_fwd(x, blk_vals, blk_cols, blk_vals_t, blk_cols_t, bn, bd,
                     interpret):
    out = _spmm_kernel(x, blk_vals, blk_cols, blk_vals_t, blk_cols_t, bn,
                       bd, interpret)
    # zero-size token carries x's static row count + dtype into the bwd
    return out, (blk_vals, blk_cols, blk_vals_t, blk_cols_t,
                 jnp.zeros((0, x.shape[0]), x.dtype))


def _spmm_kernel_bwd(bn, bd, interpret, res, g):
    # dx = A^T @ g. With the transposed block structure (blk_vals_t /
    # blk_cols_t, emitted by core.gas.build_batches) this is a second
    # bcsr_spmm call — the backward stays on the MXU kernel path. Without
    # it, fall back to an XLA einsum + block scatter-add (pallas_call has
    # no built-in transpose rule).
    # CONTRACT: blk_vals is treated as a constant (cotangent fixed to zero)
    # — the adjacency is precomputed on the host and never trained. A
    # caller learning edge weights through the kernel path would silently
    # get zero gradient; route such models through backend="jnp", whose
    # segment-sum path differentiates w.r.t. edge weights.
    blk_vals, blk_cols, blk_vals_t, blk_cols_t, x_token = res
    n_src = x_token.shape[1]
    if blk_vals_t is not None:
        dx = bcsr_spmm(g, blk_vals_t, blk_cols_t, bn=bn, bd=bd,
                       interpret=interpret)
        return (dx[:n_src].astype(x_token.dtype),
                jnp.zeros_like(blk_vals), jnp.zeros_like(blk_cols),
                None, None)
    R, K, bn_, _ = blk_vals.shape
    D = g.shape[1]
    gb = g.astype(jnp.float32).reshape(R, bn_, D)
    contrib = jnp.einsum("rkab,rad->rkbd", blk_vals, gb)
    dx = jax.ops.segment_sum(contrib.reshape(R * K, bn_, D),
                             blk_cols.reshape(-1),
                             num_segments=n_src // bn_)
    return (dx.reshape(n_src, D).astype(x_token.dtype),
            jnp.zeros_like(blk_vals), jnp.zeros_like(blk_cols), None, None)


_spmm_kernel.defvjp(_spmm_kernel_fwd, _spmm_kernel_bwd)


def spmm(x: jnp.ndarray, blk_vals, blk_cols, blk_vals_t=None,
         blk_cols_t=None, *, backend: Optional[str] = None, bn: int = 128,
         bd: int = 128) -> jnp.ndarray:
    """Block-CSR SpMM: out [R*bn, D] = A @ x with A given as BCSR blocks.
    x must already be padded to [cols_pad, D] with D % bd == 0 for the
    kernel backends (use `gcn_aggregate` for GAS-shaped inputs).
    Differentiable w.r.t. x on every backend; pass the transposed block
    structure (blk_vals_t/blk_cols_t) to keep the backward pass on the
    MXU kernel path too."""
    backend = resolve_backend(backend)
    if backend == "jnp":
        return kref.bcsr_spmm_ref(x, blk_vals, blk_cols)
    return _spmm_kernel(x, blk_vals, blk_cols, blk_vals_t, blk_cols_t, bn,
                        bd, backend == "interpret")


def gcn_aggregate(x_all: jnp.ndarray, edges, edge_w: jnp.ndarray,
                  n_out: int, blocks=None, *,
                  backend: Optional[str] = None,
                  bd: int = 128) -> jnp.ndarray:
    """GAS neighbor aggregation: out[d] = sum_e w_e * x_all[src_e].

    jnp backend (or blocks=None): XLA segment-sum over the padded COO.
    Kernel backends: block-dense MXU matmuls over `blocks = (blk_vals
    [R,K,bn,bn], blk_cols [R,K])` built by `core.gas.build_batches` —
    edge weights are baked into the blocks, bn is read off blk_vals. A
    4-tuple `blocks` additionally carries the transposed structure
    (blk_vals_t, blk_cols_t), keeping the backward pass on the MXU.
    x_all rows/features are zero-padded to tile boundaries here and the
    result sliced to n_out.
    """
    backend = resolve_backend(backend)
    if backend == "jnp" or blocks is None:
        dst, src = edges
        msg = x_all[src] * edge_w[:, None]
        return jax.ops.segment_sum(msg, dst, num_segments=n_out + 1)[:n_out]
    blk_vals, blk_cols = blocks[0], blocks[1]
    blk_vals_t = blocks[2] if len(blocks) > 2 else None
    blk_cols_t = blocks[3] if len(blocks) > 3 else None
    bn = blk_vals.shape[-1]
    M, D = x_all.shape
    # blocks are built with n_cols = len(x_all), so every referenced column
    # block lies inside ceil(M/bn)*bn padded rows
    src_pad = _pad_dim(M, bn)
    d_pad = _pad_dim(D, bd)
    xp = jnp.pad(x_all, ((0, src_pad - M), (0, d_pad - D)))
    out = spmm(xp, blk_vals, blk_cols, blk_vals_t, blk_cols_t,
               backend=backend, bn=bn, bd=bd)
    return out[:n_out, :D]


# ---------------------------------------------------------------------------
# Fused history-gather aggregation (kernels/fused.py)
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(10, 11, 12))
def _gather_spmm_kernel(x_in, table, scales, codebook, blk_vals, blk_cols,
                        blk_vals_t, blk_cols_t, halo_nodes, halo_mask, bn,
                        bd, interpret):
    return fused.gather_spmm(x_in, table, blk_vals, blk_cols, halo_nodes,
                             halo_mask, scales, codebook, bn=bn, bd=bd,
                             interpret=interpret)


def _gather_spmm_fwd(x_in, table, scales, codebook, blk_vals, blk_cols,
                     blk_vals_t, blk_cols_t, halo_nodes, halo_mask, bn, bd,
                     interpret):
    out = _gather_spmm_kernel(x_in, table, scales, codebook, blk_vals,
                              blk_cols, blk_vals_t, blk_cols_t, halo_nodes,
                              halo_mask, bn, bd, interpret)
    return out, (blk_vals, blk_cols, blk_vals_t, blk_cols_t, halo_nodes,
                 halo_mask, scales, codebook,
                 jnp.zeros((0, x_in.shape[0]), x_in.dtype),
                 jnp.zeros((0,) + table.shape, table.dtype))


def _gather_spmm_bwd(bn, bd, interpret, res, g):
    # The virtual operand is [x_in ; dequant(table)[halo] * mask ; 0], so
    # its cotangent is one transposed-BCSR SpMM (second MXU pass) split by
    # row range: rows < n_in belong to x_in, the next max_h rows scatter
    # back into the table at the halo indices. When the table is a history
    # (pulls are detached, hist is not a diff argument), XLA dead-code
    # eliminates the dtable scatter; it is live only when the caller
    # differentiates the table (e.g. GCNII/APPNP layer-0 halo transforms).
    # A quantized (int8 + scales, or vq codes + codebook) table is
    # non-differentiable by construction — its cotangents (including the
    # f32 codebook's) are hard zeros.
    (blk_vals, blk_cols, blk_vals_t, blk_cols_t, halo_nodes, halo_mask,
     scales, codebook, x_token, t_token) = res
    n_in = x_token.shape[1]
    n_table = t_token.shape[1]
    max_h = halo_nodes.shape[0]
    dx_all = bcsr_spmm(g, blk_vals_t, blk_cols_t, bn=bn, bd=bd,
                       interpret=interpret)
    dx_in = dx_all[:n_in].astype(x_token.dtype)
    if scales is None:
        dh = dx_all[n_in:n_in + max_h] * halo_mask[:, None]
        safe = jnp.where(halo_mask, jnp.clip(halo_nodes, 0, n_table - 1),
                         n_table)
        dtable = jnp.zeros((n_table, t_token.shape[2]),
                           t_token.dtype).at[safe].add(
            dh.astype(t_token.dtype), mode="drop")
        dscales = None
    else:
        dtable = jnp.zeros((n_table, t_token.shape[2]), t_token.dtype)
        dscales = jnp.zeros_like(scales)
    dcb = None if codebook is None else jnp.zeros_like(codebook)
    return (dx_in, dtable, dscales, dcb, jnp.zeros_like(blk_vals),
            jnp.zeros_like(blk_cols), jnp.zeros_like(blk_vals_t),
            jnp.zeros_like(blk_cols_t), jnp.zeros_like(halo_nodes),
            jnp.zeros_like(halo_mask))


_gather_spmm_kernel.defvjp(_gather_spmm_fwd, _gather_spmm_bwd)


def gas_aggregate(x_in: jnp.ndarray, table: jnp.ndarray,
                  halo_nodes: jnp.ndarray, halo_mask: jnp.ndarray,
                  n_out: int, blocks, *, scales: Optional[jnp.ndarray] = None,
                  codebook: Optional[jnp.ndarray] = None,
                  backend: Optional[str] = None,
                  bd: int = 128) -> jnp.ndarray:
    """Fused GAS aggregation: out = A @ [x_in ; dequant(table)[halo]*mask
    ; 0].

    The kernel backends never materialize the bracket in HBM: the fused
    `gather_spmm` kernel reads halo rows directly out of the history
    table, in-batch rows out of x_in, and zeros for masked/padding
    columns — eliminating the per-layer `pull_rows` + `jnp.concatenate`
    copies of the unfused path. It stages each of the bracket's
    Ncols = ceil((n_in + max_h + 1) / bn) column blocks once per call
    into a VMEM panel of Ncols * bn * pw * 4 bytes (pw = D, or bd where
    only one feature tile fits `fused.VMEM_BUDGET`), and every adjacency
    block then contracts against the panel; where neither fits, it
    falls back to staging each adjacency block's columns on its own, once
    per row block (`fused` module docstring). The choice depends only on
    shapes; tracing a call counts `gas/agg/panel_calls` or
    `gas/agg/per_block_calls` (`repro.utils.spans`). With `scales` [N]
    f32 the table is symmetric per-row int8
    (`core.history.quantize_rows`) and the dequant multiply is fused into
    the halo-row load too; with `codebook` [S, C, ds] as well, the table
    holds uint8 vq code rows that are codebook-decoded in VMEM — either
    way no f32 copy of the table (or any halo row) ever exists in HBM.
    `blocks` must be the 4-tuple (blk_vals, blk_cols, blk_vals_t,
    blk_cols_t) from `core.gas.build_batches`; the transposed pair keeps
    the backward on the MXU. The jnp backend runs the materialized
    oracle (`kref.gather_spmm_ref`). Differentiable w.r.t. x_in on every
    backend, and w.r.t. a float table (quantized tables get zero
    cotangents).
    """
    backend = resolve_backend(backend)
    D = x_in.shape[1]
    if backend == "jnp":
        out = kref.gather_spmm_ref(x_in, table, halo_nodes, halo_mask,
                                   blocks[0], blocks[1], scales, codebook)
        return out[:n_out, :D].astype(x_in.dtype)
    if len(blocks) != 4:
        raise ValueError(
            "kernel-path gas_aggregate needs the 4-tuple (blk_vals, "
            "blk_cols, blk_vals_t, blk_cols_t) — build batches with "
            "build_blocks=True (transposed structure included) or use "
            "the unfused path")
    if codebook is not None and backend == "pallas":
        # Mosaic lays the uint8 [N, S] code table out 128 lanes wide and
        # refuses the S-lane row-group DMA the vq form needs; the
        # interpret backend still runs it
        raise NotImplementedError(
            "fused gas_aggregate with vq histories does not compile for "
            "TPU (Mosaic: 'Slice shape along dimension 1 must be aligned "
            "to tiling (128), but is S'); on the pallas backend use "
            "fuse_halo=False (vq pulls compile), history_dtype "
            "f32/bf16/int8, or backend='jnp'")
    blk_vals, blk_cols, blk_vals_t, blk_cols_t = blocks
    bn = blk_vals.shape[-1]
    d_pad = _pad_dim(D, bd)
    xp = jnp.pad(x_in, ((0, 0), (0, d_pad - D)))
    if codebook is not None:
        tp = table                      # vq code rows are never padded
    else:
        tp = jnp.pad(table, ((0, 0), (0, d_pad - D))) \
            if d_pad != D else table
    panel = fused.panel_width(xp, tp, blk_vals, halo_nodes, codebook,
                              bd=bd)
    spans.count("gas/agg/panel_calls" if panel else
                "gas/agg/per_block_calls", 1)
    out = _gather_spmm_kernel(xp, tp, scales, codebook, blk_vals,
                              blk_cols, blk_vals_t, blk_cols_t,
                              halo_nodes.astype(jnp.int32),
                              halo_mask, bn, bd, backend == "interpret")
    return out[:n_out, :D].astype(x_in.dtype)


# ---------------------------------------------------------------------------
# Edge softmax (GAT) — kernels/edge_softmax.py
# ---------------------------------------------------------------------------

def neg_cap(dtype) -> jnp.ndarray:
    """Largest safely-representable negative score mask for `dtype`.

    Hard-coded ``-1e30`` sentinels overflow to -inf in bf16/f16 (and the
    matching ``1e30`` to +inf), poisoning segment_max/min results for
    empty segments; finfo-derived caps stay finite in every dtype."""
    return jnp.asarray(jnp.finfo(dtype).min / 2, dtype)


def _unit_blocks4(ublocks):
    if ublocks is None or len(ublocks) != 4:
        raise ValueError(
            "kernel-path edge_softmax_aggregate/pna_reduce need the "
            "4-tuple (ublk_vals, blk_cols, ublk_vals_t, blk_cols_t) — "
            "build batches with unit_weights=True (GIN/GAT/PNA) or use "
            "backend='jnp'")
    return ublocks


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9, 10))
def _edge_softmax_kernel(ad, as_, wx, uv, uc, uvt, uct, neg_slope, bn, bd,
                         interpret):
    out, _, _ = esk.edge_softmax_fwd(ad, as_, wx, uv, uc,
                                     neg_slope=neg_slope, bn=bn, bd=bd,
                                     interpret=interpret)
    return out


def _edge_softmax_kernel_fwd(ad, as_, wx, uv, uc, uvt, uct, neg_slope, bn,
                             bd, interpret):
    out, mmax, lsum = esk.edge_softmax_fwd(ad, as_, wx, uv, uc,
                                           neg_slope=neg_slope, bn=bn,
                                           bd=bd, interpret=interpret)
    return out, (ad, as_, wx, uv, uc, uvt, uct, out, mmax, lsum)


def _edge_softmax_kernel_bwd(neg_slope, bn, bd, interpret, res, g):
    # Softmax backward, block-dense on both structures: the row pass
    # (forward blocks) accumulates the destination-side dz sums (dad);
    # the column pass (transposed blocks) yields the source-side sums
    # (das) and the attention-weighted value cotangent (dwx = alpha^T g).
    # delta = sum_f g*out folds the softmax Jacobian's rank-1 term.
    ad, as_, wx, uv, uc, uvt, uct, out, mmax, lsum = res
    g = g.astype(jnp.float32)
    delta = (g * out).sum(axis=-1)
    dad = esk.edge_softmax_bwd_row(ad, as_, wx, g, mmax, lsum, delta, uv,
                                   uc, neg_slope=neg_slope, bn=bn, bd=bd,
                                   interpret=interpret)
    dwx, das = esk.edge_softmax_bwd_col(ad, as_, wx, g, mmax, lsum, delta,
                                        uvt, uct, neg_slope=neg_slope,
                                        bn=bn, bd=bd, interpret=interpret)
    return (dad.astype(ad.dtype), das.astype(as_.dtype),
            dwx.astype(wx.dtype), jnp.zeros_like(uv), jnp.zeros_like(uc),
            jnp.zeros_like(uvt), jnp.zeros_like(uct))


_edge_softmax_kernel.defvjp(_edge_softmax_kernel_fwd, _edge_softmax_kernel_bwd)


def edge_softmax_aggregate(wx: jnp.ndarray, ad: jnp.ndarray,
                           as_: jnp.ndarray, edges, edge_w: jnp.ndarray,
                           n_out: int, ublocks=None, *,
                           backend: Optional[str] = None,
                           neg_slope: float = 0.2,
                           bd: int = 128) -> jnp.ndarray:
    """GAT aggregation: out[i, h] = sum_j softmax_j(e_ijh) * wx[j, h] with
    e_ijh = leaky_relu(ad[i, h] + as_[j, h]) over the valid edges.

    wx [M, H, F] per-head values, ad/as_ [M, H] per-node logit halves
    (destinations are rows 0..n_out-1 of the x_all layout). jnp backend
    (or ublocks=None): the per-edge segment_* softmax with dtype-aware
    mask sentinels. Kernel backends: the flash-style online-softmax
    kernel over `ublocks = (ublk_vals, blk_cols, ublk_vals_t,
    blk_cols_t)` (unit-weight blocks from `core.gas.build_batches`; the
    multiplicity entries reproduce duplicate-edge softmax semantics).
    Differentiable w.r.t. wx/ad/as_ on every backend; the custom VJP runs
    one pass per block structure. Returns [n_out, H, F] in wx.dtype.
    """
    backend = resolve_backend(backend)
    if backend == "jnp" or ublocks is None:
        dst, src = edges
        e = ad[dst] + as_[src]
        e = jnp.where(e > 0, e, neg_slope * e)
        neg = neg_cap(e.dtype)
        e = jnp.where(edge_w[:, None] > 0, e, neg)
        emax = jax.ops.segment_max(e, dst, num_segments=n_out + 1)[:n_out]
        emax = jnp.clip(emax, neg, -neg)
        ee = jnp.exp(e - emax[dst])
        ee = jnp.where(edge_w[:, None] > 0, ee, 0.0)
        denom = jax.ops.segment_sum(ee, dst,
                                    num_segments=n_out + 1)[:n_out]
        msg = ee[:, :, None] * wx[src]
        out = jax.ops.segment_sum(msg, dst, num_segments=n_out + 1)[:n_out]
        # dtype-aware floor: a hard-coded 1e-16 underflows to 0 in f16,
        # turning empty destinations into 0/0 = NaN
        tiny = jnp.finfo(denom.dtype).tiny
        return out / jnp.clip(denom, tiny)[:, :, None]
    uv, uc, uvt, uct = _unit_blocks4(ublocks)
    bn = uv.shape[-1]
    M, H, F = wx.shape
    Rp = uv.shape[0] * bn
    Cp = uvt.shape[0] * bn
    Fp = _pad_dim(F, bd)
    adk = jnp.pad(ad[:n_out].T, ((0, 0), (0, Rp - n_out)))
    ask = jnp.pad(as_.T, ((0, 0), (0, Cp - M)))
    wxk = jnp.pad(wx.transpose(1, 0, 2), ((0, 0), (0, Cp - M), (0, Fp - F)))
    out = _edge_softmax_kernel(adk, ask, wxk, uv, uc, uvt, uct, neg_slope,
                               bn, bd, backend == "interpret")
    return out.transpose(1, 0, 2)[:n_out, :, :F].astype(wx.dtype)


# ---------------------------------------------------------------------------
# PNA multi-aggregator reduction — kernels/pna_reduce.py
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def _pna_kernel(xd, xs, uv, uc, uvt, uct, bn, bd, interpret):
    s, mn, mx, cnt, _, _ = pnk.pna_reduce_fwd(xd, xs, uv, uc, bn=bn, bd=bd,
                                              interpret=interpret)
    return s, mn, mx, cnt


def _pna_kernel_fwd(xd, xs, uv, uc, uvt, uct, bn, bd, interpret):
    s, mn, mx, cnt, cmin, cmax = pnk.pna_reduce_fwd(
        xd, xs, uv, uc, bn=bn, bd=bd, interpret=interpret)
    return (s, mn, mx, cnt), (xd, xs, uv, uc, uvt, uct, mn, mx, cmin, cmax)


def _pna_kernel_bwd(bn, bd, interpret, res, cts):
    # Min/max cotangents are split evenly across (multiplicity-weighted)
    # ties — the saved cmin/cmax counts — matching jax.ops.segment_min/max
    # gradients. cnt is structure-only (its cotangent is dropped, like the
    # adjacency blocks'). One recompute pass per block structure.
    xd, xs, uv, uc, uvt, uct, mn, mx, cmin, cmax = res
    gs, gmn, gmx, _gcnt = (c.astype(jnp.float32) for c in cts)
    dxd = pnk.pna_reduce_bwd_row(xd, xs, gs, gmn, gmx, mn, mx, cmin, cmax,
                                 uv, uc, bn=bn, bd=bd, interpret=interpret)
    dxs = pnk.pna_reduce_bwd_col(xd, xs, gs, gmn, gmx, mn, mx, cmin, cmax,
                                 uvt, uct, bn=bn, bd=bd,
                                 interpret=interpret)
    return (dxd.astype(xd.dtype), dxs.astype(xs.dtype),
            jnp.zeros_like(uv), jnp.zeros_like(uc), jnp.zeros_like(uvt),
            jnp.zeros_like(uct))


_pna_kernel.defvjp(_pna_kernel_fwd, _pna_kernel_bwd)


def pna_reduce(xd: jnp.ndarray, xs: jnp.ndarray, edges,
               edge_w: jnp.ndarray, n_out: int, ublocks=None, *,
               backend: Optional[str] = None, bd: int = 128):
    """PNA reduction of msg_e = relu(xd[dst_e] + xs[src_e]) per
    destination: returns (s, mn, mx, cnt) = (sum, min, max, edge count),
    with mn/mx equal to 0 for empty destinations.

    xd/xs [M, F] are the destination/source halves of PNA's per-edge
    pre-MLP (the concat-matmul split into two per-node matmuls). jnp
    backend (or ublocks=None): segment_sum/min/max with dtype-aware
    sentinels. Kernel backends: the streaming block reduction over the
    unit-weight blocks; the custom VJP even-splits min/max cotangents
    across ties exactly like segment_min/max. Differentiable w.r.t.
    xd/xs on every backend.
    """
    backend = resolve_backend(backend)
    if backend == "jnp" or ublocks is None:
        dst, src = edges
        valid = edge_w[:, None] > 0
        pre = jax.nn.relu(xd[dst] + xs[src])
        big = -neg_cap(pre.dtype)
        cnt = jax.ops.segment_sum((edge_w > 0).astype(jnp.float32), dst,
                                  num_segments=n_out + 1)[:n_out]
        s = jax.ops.segment_sum(jnp.where(valid, pre, 0), dst,
                                num_segments=n_out + 1)[:n_out]
        mn = jax.ops.segment_min(jnp.where(valid, pre, big), dst,
                                 num_segments=n_out + 1)[:n_out]
        mx = jax.ops.segment_max(jnp.where(valid, pre, -big), dst,
                                 num_segments=n_out + 1)[:n_out]
        has = (cnt > 0)[:, None]
        return (s, jnp.where(has, mn, 0).astype(pre.dtype),
                jnp.where(has, mx, 0).astype(pre.dtype), cnt)
    uv, uc, uvt, uct = _unit_blocks4(ublocks)
    bn = uv.shape[-1]
    M, F = xs.shape
    Rp = uv.shape[0] * bn
    Cp = uvt.shape[0] * bn
    Fp = _pad_dim(F, bd)
    xdk = jnp.pad(xd[:n_out], ((0, Rp - n_out), (0, Fp - F)))
    xsk = jnp.pad(xs, ((0, Cp - M), (0, Fp - F)))
    s, mn, mx, cnt = _pna_kernel(xdk, xsk, uv, uc, uvt, uct, bn, bd,
                                 backend == "interpret")
    dt = xs.dtype
    return (s[:n_out, :F].astype(dt), mn[:n_out, :F].astype(dt),
            mx[:n_out, :F].astype(dt), cnt[:n_out])


def pull_rows(table: jnp.ndarray, idx: jnp.ndarray, *,
              scales: Optional[jnp.ndarray] = None,
              codebook: Optional[jnp.ndarray] = None,
              backend: Optional[str] = None, bd: int = 128,
              pad_out: bool = False) -> jnp.ndarray:
    """History pull: out[i] = table[idx[i]] (idx clipped to [0, N)).

    With `scales` [N] f32 the table holds symmetric per-row int8 rows and
    the pull dequantizes: out[i] = table[idx[i]] * scales[idx[i]] in f32.
    On the kernel backends the multiply is fused into the row gather
    (`gather_rows` with its scales — the per-row scales ride the
    scalar-prefetch lane), so only int8 table bytes cross HBM. With
    `codebook` [S, C, ds] as well, the table holds uint8 vq code rows
    and the pull decodes them
    (`gather_rows_vq` on the kernel backends — only S code bytes per row
    cross HBM).

    `pad_out=True` returns the rows zero-padded to the kernel lane width
    (a multiple of `bd`) instead of slicing back to d — callers that feed
    the pulled halo straight into padded matmuls use this to avoid ever
    shaping a [M, d] float tensor."""
    backend = resolve_backend(backend)
    idx = jnp.clip(idx, 0, table.shape[0] - 1).astype(jnp.int32)
    if codebook is not None:
        from repro.core.history import vq_decode_rows
        d = codebook.shape[0] * codebook.shape[2]
        if backend == "jnp":
            codes = jnp.take(table, idx, axis=0, mode="clip")
            out = vq_decode_rows(codes, codebook,
                                 jnp.take(scales, idx, mode="clip"))
        else:
            out = gather_rows_vq(table, codebook, scales, idx,
                                 interpret=backend == "interpret")
            if not pad_out:
                return out[:, :d]
            return out
        if pad_out:
            out = jnp.pad(out, ((0, 0), (0, _pad_dim(d, bd) - d)))
        return out
    if backend == "jnp":
        out = jnp.take(table, idx, axis=0, mode="clip")
        if scales is not None:
            out = out.astype(jnp.float32) * \
                jnp.take(scales, idx, mode="clip")[:, None]
        if pad_out:
            D = table.shape[1]
            out = jnp.pad(out, ((0, 0), (0, _pad_dim(D, bd) - D)))
        return out
    N, D = table.shape
    d_pad = _pad_dim(D, bd)
    tp = jnp.pad(table, ((0, 0), (0, d_pad - D))) if d_pad != D else table
    interpret = backend == "interpret"
    out = gather_rows(tp, idx, scales, bd=bd, interpret=interpret)
    return out if pad_out else out[:, :D]


def push_rows(table: jnp.ndarray, idx: jnp.ndarray, values: jnp.ndarray,
              mask: jnp.ndarray, *, backend: Optional[str] = None,
              bd: int = 128, scratch_last_row: bool = False) -> jnp.ndarray:
    """History push: table[idx[i]] = values[i] where mask[i]; padding rows
    (mask False) are dropped. Matches `core.history.push` semantics.

    `scratch_last_row=True` declares that the caller's last table row is a
    sentinel (GAS history tables are allocated [N+1, d] with a sentinel
    row that is only ever read through a mask): valid indices are then
    clipped below it. Either way the kernel path scatters into the
    caller's buffer directly, in place when it is donated, for tables
    whose width is a multiple of bd (narrower ones are lane-padded and
    sliced, two copies); masked rows point past the table, where
    `scatter_rows` drops them at no cost. Tracing a kernel-path call
    counts its grid steps, `gas/push/chunks` (`repro.utils.spans`).
    """
    backend = resolve_backend(backend)
    N, D = table.shape
    if backend == "jnp":
        safe_idx = jnp.where(mask, idx, N)  # OOB -> dropped
        return table.at[safe_idx].set(values.astype(table.dtype),
                                      mode="drop", unique_indices=False)
    new_t, _ = _kernel_push(table, idx, values, mask, None,
                            backend=backend, bd=bd,
                            scratch_last_row=scratch_last_row)
    return new_t


def _kernel_push(table, idx, values, mask, row_scale, *, backend, bd,
                 scratch_last_row):
    """`scatter_rows` for `push_rows` / `push_rows_q`: valid rows clipped
    into the table (below the sentinel with `scratch_last_row`), masked
    rows to N, where the kernel drops them; tables narrower than a
    multiple of bd lane-padded and sliced back. Counts the kernel's grid
    steps, `gas/push/chunks`. Returns (new_table, indices)."""
    N, D = table.shape
    hi = N - 2 if scratch_last_row else N - 1
    safe_idx = jnp.where(mask, jnp.clip(idx, 0, hi), N).astype(jnp.int32)
    d_pad = _pad_dim(D, bd)
    if d_pad != D:
        table = jnp.pad(table, ((0, 0), (0, d_pad - D)))
        values = jnp.pad(values, ((0, 0), (0, d_pad - D)))
    m = idx.shape[0]
    spans.count("gas/push/chunks", -(-m // scatter.chunk_rows(m, d_pad)))
    out = scatter_rows(table, safe_idx, values, row_scale,
                       interpret=backend == "interpret")
    return (out[:, :D] if d_pad != D else out), safe_idx


def push_rows_q(table: jnp.ndarray, scales: jnp.ndarray, idx: jnp.ndarray,
                values: jnp.ndarray, mask: jnp.ndarray, *,
                backend: Optional[str] = None, bd: int = 128,
                scratch_last_row: bool = False
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Quantizing history push: the dual of the dequantizing pull.

    `table` [N, D] int8 / `scales` [N] f32. Each pushed f32 row is
    symmetric-per-row quantized (`core.history.quantize_rows` semantics:
    s = max|v| / 127, q = round(v / s)) and scattered as int8, and its
    scale lands in the scale table at the same row. On the kernel
    backends the divide-round-clip runs inside the scatter kernel
    (`scatter_rows` with its scales), so the quantized copy of the
    payload is never materialized in HBM; only the [M] row-max reduction
    happens outside.
    Returns (new_table, new_scales); masking, `scratch_last_row` and the
    `gas/push/chunks` counter match `push_rows`.
    """
    from repro.core.history import quantize_rows, row_scales
    backend = resolve_backend(backend)
    N, D = table.shape
    v = values.astype(jnp.float32)
    if backend == "jnp":
        q, row_scale = quantize_rows(v)
        safe_idx = jnp.where(mask, idx, N)  # OOB -> dropped
        new_t = table.at[safe_idx].set(q, mode="drop",
                                       unique_indices=False)
        new_s = scales.at[safe_idx].set(row_scale, mode="drop",
                                        unique_indices=False)
        return new_t, new_s
    # kernel path: the divide-round-clip runs inside scatter_rows; the
    # per-row scale comes from the SAME row_scales the jnp path uses, so
    # backends agree bit-for-bit
    row_scale = row_scales(v)
    new_t, safe_idx = _kernel_push(table, idx, v, mask, row_scale,
                                   backend=backend, bd=bd,
                                   scratch_last_row=scratch_last_row)
    new_s = scales.at[safe_idx].set(row_scale, mode="drop",
                                    unique_indices=False)
    return new_t, new_s


def push_rows_vq(table: jnp.ndarray, scales: jnp.ndarray, idx: jnp.ndarray,
                 values: jnp.ndarray, mask: jnp.ndarray,
                 codebook: jnp.ndarray, *, backend: Optional[str] = None,
                 scratch_last_row: bool = False
                 ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Codebook-quantizing history push (`history_dtype="vq"`).

    `table` [N, S] uint8 codes / `scales` [N] f32 / `codebook` [S, C, ds].
    Each pushed f32 row is normalized by its max-|v| scale
    (`core.history.vq_row_scales`), nearest-codebook-entry encoded per
    ds-subvector (`vq_encode_rows` semantics) and scattered as S uint8
    code bytes; its scale lands in the scale table at the same row. On
    the kernel backends the nearest-entry search runs inside the scatter
    kernel (`scatter_rows_vq`), so neither the normalized payload nor the
    code rows are ever materialized in HBM outside the table itself.
    Returns (new_table, new_scales); masking / `scratch_last_row` match
    `push_rows` (the sentinel row's code/scale become garbage — sentinel
    reads are masked everywhere).
    """
    from repro.core.history import vq_encode_rows, vq_row_scales
    backend = resolve_backend(backend)
    N, S = table.shape
    v = values.astype(jnp.float32)
    if backend == "jnp":
        codes, row_scale = vq_encode_rows(v, codebook)
        safe_idx = jnp.where(mask, idx, N)  # OOB -> dropped
        new_t = table.at[safe_idx].set(codes, mode="drop",
                                       unique_indices=False)
        new_s = scales.at[safe_idx].set(row_scale, mode="drop",
                                        unique_indices=False)
        return new_t, new_s
    interpret = backend == "interpret"
    # kernel path: the nearest-entry search runs inside scatter_rows_vq;
    # the per-row scale comes from the SAME vq_row_scales the jnp path
    # uses, so backends agree bit-for-bit
    row_scale = vq_row_scales(v)
    if scratch_last_row:
        safe_idx = jnp.where(mask, jnp.clip(idx, 0, N - 2),
                             N - 1).astype(jnp.int32)
        new_t = scatter_rows_vq(table, safe_idx, v, row_scale, codebook,
                                interpret=interpret)
        new_s = scales.at[safe_idx].set(row_scale, unique_indices=False)
        return new_t, new_s
    # general path: appended sacrificial row (pad + slice copies the code
    # table; scatter_rows_vq has no lane-width constraint on values)
    safe_idx = jnp.where(mask, jnp.clip(idx, 0, N - 1), N).astype(jnp.int32)
    tp = jnp.pad(table, ((0, 1), (0, 0)))
    new_t = scatter_rows_vq(tp, safe_idx, v, row_scale, codebook,
                            interpret=interpret)
    new_s = scales.at[safe_idx].set(row_scale, mode="drop",
                                    unique_indices=False)
    return new_t[:N], new_s


__all__ = ["BACKENDS", "set_default_backend", "resolve_backend",
           "bcsr_spmm", "gather_rows", "gather_rows_vq",
           "scatter_rows", "scatter_rows_vq",
           "flash_decode",
           "build_bcsr", "build_bcsr_rect", "bcsr_density",
           "spmm", "gcn_aggregate", "gas_aggregate",
           "edge_softmax_aggregate", "pna_reduce", "neg_cap", "pull_rows",
           "push_rows", "push_rows_q", "push_rows_vq",
           "esk", "fused", "pnk", "kref"]
