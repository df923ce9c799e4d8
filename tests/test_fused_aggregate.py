"""Tentpole coverage: the fully block-dense GAS step.

(1) transposed-BCSR backward — gradient equivalence of the kernel spmm
    custom VJP (second `bcsr_spmm` pass) against jnp autodiff, on every
    backend, float32 and bfloat16;
(2) fused `gather_spmm` aggregation — forward + gradients (w.r.t. both
    the in-batch activations and the gathered table) against the jnp
    oracle, on every backend, float32 and bfloat16;
(3) operator generalization — the whole zoo (GCN/GIN/GCNII/APPNP via the
    BCSR SpMM, GAT via the online edge-softmax kernel, PNA via the
    streaming multi-aggregator kernel) runs the block route, and the
    kernel-path train-step jaxpr contains NO edge-indexed gather/scatter
    (i.e. no segment_sum-style aggregation), forward or backward;
(4) satellites — vectorized `build_bcsr_rect`, jitted `gas_predict`,
    staleness diagnostics.

The "pallas" backend is the same kernel compiled for real TPUs; it is
skipped automatically off-TPU (the "interpret" backend runs the identical
kernel code paths on CPU).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import gas as G
from repro.core import history as H
from repro.core import runtime as R
from repro.data.graphs import citation_graph
from repro.gnn.model import (BLOCK_OPS, UNIT_BLOCK_OPS, GNNSpec,
                             gas_batch_forward, init_gnn)
from repro.kernels import fused, ops
from repro.kernels import ref as kref

KERNEL_BACKENDS = ("interpret", "pallas")
ALL_BACKENDS = ("jnp",) + KERNEL_BACKENDS


def _backend_or_skip(backend):
    if backend == "pallas" and jax.default_backend() != "tpu":
        pytest.skip("compiled Pallas kernels need a TPU")


def _rand_bcsr(seed=0, n_rows=100, n_cols=230, ne=600, bn=64):
    rng = np.random.default_rng(seed)
    dst = rng.integers(0, n_rows, ne).astype(np.int32)
    src = rng.integers(0, n_cols, ne).astype(np.int32)
    w = rng.normal(size=ne).astype(np.float32)
    v, c, rp, cp = ops.build_bcsr_rect(dst, src, w, n_rows, n_cols, bn=bn)
    vt, ct, _, _ = ops.build_bcsr_rect(src, dst, w, n_cols, n_rows, bn=bn)
    return (dst, src, w), (v, c, vt, ct), (rp, cp)


def _dense_from_bcsr(vals, cols, n_rows, n_cols, bn):
    R, K = cols.shape
    C = max(int(cols.max()) + 1, -(-n_cols // bn))
    A = np.zeros((R * bn, C * bn), np.float32)
    for r in range(R):
        for k in range(K):
            j = cols[r, k]
            A[r * bn:(r + 1) * bn, j * bn:(j + 1) * bn] += vals[r, k]
    return A[:n_rows, :n_cols]


# ---------------------------------------------------------------------------
# build_bcsr_rect: vectorized host setup (satellite 1)
# ---------------------------------------------------------------------------

def _build_bcsr_rect_naive(dst, src, w, n_rows, n_cols, bn):
    """The pre-vectorization per-block Python loop, kept as the oracle."""
    R = max(-(-n_rows // bn), 1)
    C = max(-(-n_cols // bn), 1)
    bi = (dst // bn).astype(np.int64)
    bj = (src // bn).astype(np.int64)
    key = bi * C + bj
    order = np.argsort(key, kind="stable")
    dst_s, src_s, w_s = dst[order], src[order], w[order]
    uniq, starts = np.unique(key[order], return_index=True)
    starts = np.append(starts, len(key))
    bpr = np.bincount((uniq // C).astype(np.int64), minlength=R)
    K = max(int(bpr.max(initial=1)), 1)
    vals = np.zeros((R, K, bn, bn), np.float32)
    cols = np.zeros((R, K), np.int32)
    slot = np.zeros(R, np.int64)
    for u, s0, s1 in zip(uniq, starts[:-1], starts[1:]):
        i, j = int(u // C), int(u % C)
        k = slot[i]
        slot[i] += 1
        cols[i, k] = j
        np.add.at(vals[i, k], (dst_s[s0:s1] - i * bn, src_s[s0:s1] - j * bn),
                  w_s[s0:s1])
    return vals, cols, R * bn, C * bn


@pytest.mark.parametrize("seed,nr,nc,ne", [(0, 100, 230, 600), (1, 7, 500, 1),
                                           (2, 300, 300, 2000), (3, 64, 64, 0)])
def test_build_bcsr_rect_vectorized_matches_naive(seed, nr, nc, ne):
    rng = np.random.default_rng(seed)
    dst = rng.integers(0, nr, ne).astype(np.int32)
    src = rng.integers(0, nc, ne).astype(np.int32)
    w = rng.normal(size=ne).astype(np.float32)
    got = ops.build_bcsr_rect(dst, src, w, nr, nc, bn=64)
    ref = _build_bcsr_rect_naive(dst, src, w, nr, nc, 64)
    assert got[2:] == ref[2:]
    np.testing.assert_array_equal(got[1], ref[1])
    np.testing.assert_array_equal(got[0], ref[0])


def test_transposed_blocks_are_the_transpose():
    (dst, src, w), (v, c, vt, ct), _ = _rand_bcsr()
    A = _dense_from_bcsr(v, c, 100, 230, 64)
    At = _dense_from_bcsr(vt, ct, 230, 100, 64)
    np.testing.assert_allclose(At, A.T, rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# Tentpole (1): transposed-BCSR backward on every backend
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ALL_BACKENDS)
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-4),
                                       (jnp.bfloat16, 7e-2)])
def test_spmm_transposed_backward_matches_jnp(backend, dtype, tol):
    _backend_or_skip(backend)
    _, (v, c, vt, ct), (rp, cp) = _rand_bcsr(seed=5)
    x = jnp.asarray(np.random.default_rng(6).normal(
        size=(cp, 128)).astype(np.float32), dtype)
    blocks = tuple(jnp.asarray(a) for a in (v, c, vt, ct))

    def loss(xx, bk, blks):
        return jnp.sum(ops.spmm(xx, *blks, backend=bk, bn=64) ** 2)

    g_ref = jax.grad(lambda xx: loss(xx, "jnp", blocks[:2]))(x)
    g_t = jax.grad(lambda xx: loss(xx, backend, blocks))(x)
    # the einsum + segment-sum fallback (no transposed blocks) must agree too
    g_fb = jax.grad(lambda xx: loss(xx, backend, blocks[:2]))(x)
    np.testing.assert_allclose(np.asarray(g_t, np.float32),
                               np.asarray(g_ref, np.float32),
                               rtol=tol, atol=tol)
    np.testing.assert_allclose(np.asarray(g_fb, np.float32),
                               np.asarray(g_ref, np.float32),
                               rtol=tol, atol=tol)


# ---------------------------------------------------------------------------
# Tentpole (2): fused gather_spmm forward + gradients on every backend
# ---------------------------------------------------------------------------

def _fused_problem(dtype, seed=7, n_in=90, max_h=40, N=250, D=96, bn=64):
    rng = np.random.default_rng(seed)
    n_cols = n_in + max_h + 1
    ne = 500
    dst = rng.integers(0, n_in, ne).astype(np.int32)
    src = rng.integers(0, n_cols - 1, ne).astype(np.int32)
    w = rng.normal(size=ne).astype(np.float32)
    v, c, _, _ = ops.build_bcsr_rect(dst, src, w, n_in, n_cols, bn=bn)
    vt, ct, _, _ = ops.build_bcsr_rect(src, dst, w, n_cols, n_in, bn=bn)
    blocks = tuple(jnp.asarray(a) for a in (v, c, vt, ct))
    x_in = jnp.asarray(rng.normal(size=(n_in, D)).astype(np.float32), dtype)
    table = jnp.asarray(rng.normal(size=(N, D)).astype(np.float32), dtype)
    halo_nodes = jnp.asarray(rng.integers(0, N, max_h).astype(np.int32))
    halo_mask = jnp.asarray(rng.random(max_h) < 0.8)
    return x_in, table, halo_nodes, halo_mask, blocks, n_in


@pytest.mark.parametrize("backend", KERNEL_BACKENDS)
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-4),
                                       (jnp.bfloat16, 7e-2)])
def test_gas_aggregate_fwd_and_grad_match_oracle(backend, dtype, tol):
    _backend_or_skip(backend)
    x_in, table, hn, hm, blocks, n_out = _fused_problem(dtype)

    def loss(xi, tb, bk, blks):
        out = ops.gas_aggregate(xi, tb, hn, hm, n_out, blks, backend=bk)
        return jnp.sum(out.astype(jnp.float32) ** 2), out

    (_, o_ref), g_ref = jax.value_and_grad(
        lambda xi, tb: loss(xi, tb, "jnp", blocks[:2]), argnums=(0, 1),
        has_aux=True)(x_in, table)
    (_, o_ker), g_ker = jax.value_and_grad(
        lambda xi, tb: loss(xi, tb, backend, blocks), argnums=(0, 1),
        has_aux=True)(x_in, table)
    np.testing.assert_allclose(np.asarray(o_ker, np.float32),
                               np.asarray(o_ref, np.float32),
                               rtol=tol, atol=tol)
    for gk, gr, name in zip(g_ker, g_ref, ("dx_in", "dtable")):
        np.testing.assert_allclose(np.asarray(gk, np.float32),
                                   np.asarray(gr, np.float32),
                                   rtol=tol, atol=tol, err_msg=name)


def test_gas_aggregate_masked_halo_rows_are_zeroed():
    """Masked halo columns must contribute exactly zero (the oracle zeroes
    pulled rows; the fused kernel routes sel==2 to a hard zero)."""
    x_in, table, hn, hm, blocks, n_out = _fused_problem(jnp.float32, seed=9)
    poisoned = table.at[:].set(jnp.nan)  # any unmasked read would leak NaN
    hm_none = jnp.zeros_like(hm)
    out = ops.gas_aggregate(x_in, poisoned, hn, hm_none, n_out, blocks,
                            backend="interpret")
    assert np.isfinite(np.asarray(out)).all()


# ---------------------------------------------------------------------------
# gather_spmm's staging paths (VMEM panel at full width or one feature
# tile, per-block fallback) == the materialized oracle
# ---------------------------------------------------------------------------

PANEL_CASES = ("shared_cols", "padded_k", "masked_halo", "tail_tile",
               "one_tile_panel", "over_budget")


@pytest.fixture
def vmem_budget(monkeypatch):
    """Sets `fused.VMEM_BUDGET` for one test. The budget steers tracing
    but is no part of a jit cache key, so caches are cleared both ways."""
    def set_budget(n):
        monkeypatch.setattr(fused, "VMEM_BUDGET", n)
        jax.clear_caches()
    yield set_budget
    jax.clear_caches()


def _panel_problem(case, hd, seed=21, n_in=200, max_h=150, N=325, D=256,
                   bn=64):
    """R = 4 row blocks over 6 column blocks. Each case adds its feature
    to a plain problem (dense random edges, every halo row unmasked and
    inside the table's whole 8-row tiles); the last two cases have all:
      padded_k    — the last row block reads one column block, so its
                    other K entries are padding (column 0, zero values);
      masked_halo — about half the halo rows are masked;
      tail_tile   — halo rows in the table's partial last tile (N % 8).
    """
    rng = np.random.default_rng(seed)
    every = case in ("one_tile_panel", "over_budget")
    n_cols = n_in + max_h + 1
    padded = every or case == "padded_k"
    dst = rng.integers(0, 192 if padded else n_in, 1200)
    src = rng.integers(0, n_cols, 1200)
    if padded:
        dst = np.append(dst, rng.integers(192, n_in, 16))
        src = np.append(src, rng.integers(4 * bn, 5 * bn, 16))
    dst, src = dst.astype(np.int32), src.astype(np.int32)
    w = rng.normal(size=len(dst)).astype(np.float32)
    v, c, _, _ = ops.build_bcsr_rect(dst, src, w, n_in, n_cols, bn=bn)
    vt, ct, _, _ = ops.build_bcsr_rect(src, dst, w, n_cols, n_in, bn=bn)
    blocks = tuple(jnp.asarray(a) for a in (v, c, vt, ct))
    n8 = N // 8 * 8
    hn = rng.integers(0, n8, max_h).astype(np.int32)
    if every or case == "tail_tile":
        hn[::6] = rng.integers(n8, N, len(hn[::6]))
    hm = np.ones(max_h, bool)
    if every or case == "masked_halo":
        hm = rng.random(max_h) < 0.5
    x_in = jnp.asarray(rng.normal(size=(n_in, D)).astype(np.float32))
    table = jnp.asarray(rng.normal(size=(N, D)).astype(np.float32))
    scales = codebook = None
    if hd == "int8":
        table, scales = H.quantize_rows(table)
    elif hd == "vq":
        codebook = H.vq_init_codebook(D)
        table, scales = H.vq_encode_rows(table, codebook)
    return (x_in, table, scales, codebook, jnp.asarray(hn),
            jnp.asarray(hm), blocks, n_in)


@pytest.mark.parametrize("hd", ("f32", "int8", "vq"))
@pytest.mark.parametrize("case", PANEL_CASES)
def test_gas_aggregate_staging_paths_match_oracle(case, hd, vmem_budget):
    x_in, table, scales, cb, hn, hm, blocks, n_out = _panel_problem(case,
                                                                    hd)
    v, c = np.asarray(blocks[0]), np.asarray(blocks[1])
    R, K, bn, _ = v.shape
    # the feature each case stands for is there
    assert R >= 3
    if case == "shared_cols":
        readers = [len({r for r in range(R) if j in c[r]})
                   for j in range(int(c.max()) + 1)]
        assert min(readers) >= 3, readers
    if case in ("padded_k", "one_tile_panel", "over_budget"):
        assert not np.abs(v[-1]).sum(axis=(1, 2)).all()
    if case in ("masked_halo", "one_tile_panel", "over_budget"):
        assert (~np.asarray(hm)).any()
    if case in ("tail_tile", "one_tile_panel", "over_budget"):
        n8 = table.shape[0] // 8 * 8
        assert (np.asarray(hm) & (np.asarray(hn) >= n8)).any()
    ncols = -(-(x_in.shape[0] + hn.shape[0] + 1) // bn)
    if case == "one_tile_panel":      # the whole width just misses
        vmem_budget(fused._panel_vmem(
            ncols, x_in.shape[1], bn=bn, x_dtype=x_in.dtype, table=table,
            vals_dtype=v.dtype, codebook=cb) - 1)
    elif case == "over_budget":
        vmem_budget(1)
    want = {"one_tile_panel": 128, "over_budget": None}.get(case, 256)
    assert fused.panel_width(x_in, table, blocks[0], hn, cb,
                             bd=128) == want

    def loss(xi, tb):
        out = ops.gas_aggregate(xi, tb, hn, hm, n_out, blocks,
                                scales=scales, codebook=cb,
                                backend="interpret")
        return jnp.sum(out ** 2), out

    def loss_ref(xi, tb):
        out = kref.gather_spmm_ref(xi, tb, hn, hm, blocks[0], blocks[1],
                                   scales, cb)[:n_out]
        return jnp.sum(out ** 2), out

    # a quantized table is integer: no gradient to take there
    argnums = (0, 1) if hd == "f32" else (0,)
    (_, o_ker), g_ker = jax.value_and_grad(loss, argnums, has_aux=True)(
        x_in, table)
    (_, o_ref), g_ref = jax.value_and_grad(loss_ref, argnums,
                                           has_aux=True)(x_in, table)
    np.testing.assert_allclose(np.asarray(o_ker), np.asarray(o_ref),
                               rtol=1e-4, atol=1e-4)
    for gk, gr, name in zip(g_ker, g_ref, ("dx_in", "dtable")):
        np.testing.assert_allclose(np.asarray(gk), np.asarray(gr),
                                   rtol=1e-4, atol=1e-4, err_msg=name)


# ---------------------------------------------------------------------------
# Edge-softmax (GAT) + multi-aggregator (PNA) kernels: fwd + grad vs the
# segment_* reference, float32 and bfloat16, on every backend
# ---------------------------------------------------------------------------

def _unit_block_problem(seed=11, n_out=100, M=230, bn=64, ne=600):
    """Random ragged GAS-shaped edge set with duplicate edges and padding
    edges, plus its unit-weight (multiplicity) block structures."""
    rng = np.random.default_rng(seed)
    dst = rng.integers(0, n_out, ne).astype(np.int32)
    src = rng.integers(0, M - 1, ne).astype(np.int32)
    dst[:40], src[:40] = dst[40:80], src[40:80]     # duplicate edges
    w = np.ones(ne, np.float32)
    w[-30:] = 0.0                                    # padding edges
    v = w > 0
    ones = np.ones(int(v.sum()), np.float32)
    uv, uc, _, _ = ops.build_bcsr_rect(dst[v], src[v], ones, n_out, M,
                                       bn=bn)
    uvt, uct, _, _ = ops.build_bcsr_rect(src[v], dst[v], ones, M, n_out,
                                         bn=bn)
    ublocks = tuple(jnp.asarray(a) for a in (uv, uc, uvt, uct))
    return (jnp.asarray(dst), jnp.asarray(src)), jnp.asarray(w), ublocks, rng


@pytest.mark.parametrize("backend", KERNEL_BACKENDS)
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-4),
                                       (jnp.bfloat16, 7e-2)])
def test_edge_softmax_fwd_and_grad_match_segment(backend, dtype, tol):
    """GAT kernel path == segment_* reference, forward and all three
    gradients (values, destination logits, source logits). The bf16 case
    compares against the reference on the f32 upcast of the same inputs
    (the kernels compute in f32 internally), so both paths see identical
    message values and softmax routing."""
    _backend_or_skip(backend)
    edges, ew, ublocks, rng = _unit_block_problem()
    n_out, M, H, F = 100, 230, 2, 8
    wx = jnp.asarray(rng.normal(size=(M, H, F)).astype(np.float32), dtype)
    ad = jnp.asarray(rng.normal(size=(M, H)).astype(np.float32), dtype)
    as_ = jnp.asarray(rng.normal(size=(M, H)).astype(np.float32), dtype)

    def loss(wx, ad, as_, bk, blk):
        out = ops.edge_softmax_aggregate(wx, ad, as_, edges, ew, n_out,
                                         blk, backend=bk)
        return jnp.sum(out.astype(jnp.float32) ** 2), out

    f32 = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32),
                                 (wx, ad, as_))
    (_, o_ref), g_ref = jax.value_and_grad(
        lambda *a: loss(*a, "jnp", None), argnums=(0, 1, 2),
        has_aux=True)(*f32)
    (_, o_ker), g_ker = jax.value_and_grad(
        lambda *a: loss(*a, backend, ublocks), argnums=(0, 1, 2),
        has_aux=True)(wx, ad, as_)
    np.testing.assert_allclose(np.asarray(o_ker, np.float32),
                               np.asarray(o_ref, np.float32),
                               rtol=tol, atol=tol)
    for gk, gr, name in zip(g_ker, g_ref, ("dwx", "dad", "das")):
        np.testing.assert_allclose(np.asarray(gk, np.float32),
                                   np.asarray(gr, np.float32),
                                   rtol=tol, atol=tol, err_msg=name)


@pytest.mark.parametrize("backend", KERNEL_BACKENDS)
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-4),
                                       (jnp.bfloat16, 7e-2)])
def test_pna_reduce_fwd_and_grad_match_segment(backend, dtype, tol):
    """PNA kernel path == segment_* reference: (sum, min, max, count)
    forward plus both gradients, including even-split min/max tie
    handling (relu clamping + duplicate edges make ties the common
    case). bf16 compares against the reference on the f32 upcast so both
    paths agree on tie locations."""
    _backend_or_skip(backend)
    edges, ew, ublocks, rng = _unit_block_problem(seed=12)
    n_out, M, F = 100, 230, 16
    xd = jnp.asarray(rng.normal(size=(M, F)).astype(np.float32), dtype)
    xs = jnp.asarray(rng.normal(size=(M, F)).astype(np.float32), dtype)

    def loss(xd, xs, bk, blk):
        s, mn, mx, cnt = ops.pna_reduce(xd, xs, edges, ew, n_out, blk,
                                        backend=bk)
        outs = tuple(a.astype(jnp.float32) for a in (s, mn, mx, cnt))
        s, mn, mx, _ = outs
        return jnp.sum(s ** 2 + mn ** 2 + 2.0 * mx ** 2), outs

    f32 = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), (xd, xs))
    (_, o_ref), g_ref = jax.value_and_grad(
        lambda *a: loss(*a, "jnp", None), argnums=(0, 1),
        has_aux=True)(*f32)
    (_, o_ker), g_ker = jax.value_and_grad(
        lambda *a: loss(*a, backend, ublocks), argnums=(0, 1),
        has_aux=True)(xd, xs)
    for ok, orf, name in zip(o_ker, o_ref, ("s", "mn", "mx", "cnt")):
        np.testing.assert_allclose(np.asarray(ok, np.float32),
                                   np.asarray(orf, np.float32),
                                   rtol=tol, atol=tol, err_msg=name)
    for gk, gr, name in zip(g_ker, g_ref, ("dxd", "dxs")):
        np.testing.assert_allclose(np.asarray(gk, np.float32),
                                   np.asarray(gr, np.float32),
                                   rtol=tol, atol=tol, err_msg=name)


def test_edge_softmax_and_pna_multi_feature_tile_backward():
    """F > bd splits the feature contraction over multiple grid tiles
    (Ft > 1): the backward kernels fold the softmax-Jacobian delta term /
    tie-split once per K step, so per-tile partial g.v sums must still
    add up to the exact gradient."""
    edges, ew, ublocks, rng = _unit_block_problem(seed=13)
    n_out, M = 100, 230
    F = 160                                          # Fp = 256 -> Ft = 2
    wx = jnp.asarray(rng.normal(size=(M, 1, F)).astype(np.float32))
    ad = jnp.asarray(rng.normal(size=(M, 1)).astype(np.float32))
    as_ = jnp.asarray(rng.normal(size=(M, 1)).astype(np.float32))

    def loss_gat(wx, ad, as_, bk, blk):
        o = ops.edge_softmax_aggregate(wx, ad, as_, edges, ew, n_out, blk,
                                       backend=bk)
        return jnp.sum(o ** 2)

    gr = jax.grad(loss_gat, argnums=(0, 1, 2))(wx, ad, as_, "jnp", None)
    gk = jax.grad(loss_gat, argnums=(0, 1, 2))(wx, ad, as_, "interpret",
                                               ublocks)
    for a, b, nm in zip(gk, gr, ("dwx", "dad", "das")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                                   atol=1e-4, err_msg=nm)

    xd = jnp.asarray(rng.normal(size=(M, F)).astype(np.float32))
    xs = jnp.asarray(rng.normal(size=(M, F)).astype(np.float32))

    def loss_pna(xd, xs, bk, blk):
        s, mn, mx, _ = ops.pna_reduce(xd, xs, edges, ew, n_out, blk,
                                      backend=bk)
        return jnp.sum(s ** 2 + mn ** 2 + 2 * mx ** 2)

    gr = jax.grad(loss_pna, argnums=(0, 1))(xd, xs, "jnp", None)
    gk = jax.grad(loss_pna, argnums=(0, 1))(xd, xs, "interpret", ublocks)
    for a, b, nm in zip(gk, gr, ("dxd", "dxs")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-3,
                                   atol=1e-3, err_msg=nm)


def test_edge_softmax_empty_rows_and_masked_sources_are_zero():
    """Destinations with no valid incoming edges must aggregate to exactly
    zero on the kernel path (the online softmax's l == 0 guard), and
    sources only reachable through padding (weight-0) edges must not
    contribute — their values are poisoned with a huge finite value so
    any leaked (attention-weighted) contribution blows the comparison."""
    n_out, M, bn = 70, 150, 64
    rng = np.random.default_rng(3)
    ne = 200
    dst = rng.integers(0, 50, ne).astype(np.int32)   # rows 50.. stay empty
    src = rng.integers(0, 100, ne).astype(np.int32)
    w = np.ones(ne, np.float32)
    # padding edges: weight 0, pointing at sources 100.. that no valid
    # edge references (the block structures are built from valid edges
    # only, mirroring core.gas.build_batches)
    w[-40:] = 0.0
    src[-40:] = rng.integers(100, M - 1, 40)
    v = w > 0
    ones = np.ones(int(v.sum()), np.float32)
    uv, uc, _, _ = ops.build_bcsr_rect(dst[v], src[v], ones, n_out, M,
                                       bn=bn)
    uvt, uct, _, _ = ops.build_bcsr_rect(src[v], dst[v], ones, M, n_out,
                                         bn=bn)
    ublocks = tuple(jnp.asarray(a) for a in (uv, uc, uvt, uct))
    H, F = 2, 8
    wx = jnp.asarray(rng.normal(size=(M, H, F)).astype(np.float32))
    poisoned = wx.at[100:].set(1e30)
    # masked-source *logits* are poisoned too: a leaked softmax slot for
    # a huge logit would dominate every destination it touches
    ad = jnp.asarray(rng.normal(size=(M, H)).astype(np.float32))
    as_ = jnp.asarray(rng.normal(size=(M, H)).astype(np.float32))
    as_p = as_.at[100:].set(50.0)
    edges = (jnp.asarray(dst), jnp.asarray(src))
    out = ops.edge_softmax_aggregate(poisoned, ad, as_p, edges,
                                     jnp.asarray(w), n_out, ublocks,
                                     backend="interpret")
    assert np.isfinite(np.asarray(out)).all()
    np.testing.assert_array_equal(np.asarray(out[50:]), 0.0)
    # and must agree with the clean-source jnp reference on everything
    ref = ops.edge_softmax_aggregate(wx, ad, as_, edges, jnp.asarray(w),
                                     n_out, backend="jnp")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-4,
                               atol=1e-4)


# ---------------------------------------------------------------------------
# Tentpole (3): the whole kernel-path train step is edge-gather/scatter free
# ---------------------------------------------------------------------------

def _iter_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _subjaxprs(eqn.params):
            yield from _iter_eqns(sub)


def _subjaxprs(v):
    if isinstance(v, dict):
        v = list(v.values())
    if isinstance(v, (list, tuple)):
        for x in v:
            yield from _subjaxprs(x)
        return
    if hasattr(v, "eqns"):            # Jaxpr
        yield v
    elif hasattr(v, "jaxpr") and hasattr(getattr(v, "jaxpr"), "eqns"):
        yield v.jaxpr                  # ClosedJaxpr


def _edge_indexed_ops(jaxpr, max_e):
    """(primitive, shape) for every gather/scatter/segment-style eqn whose
    operands or outputs are edge-indexed (leading dim == max_e)."""
    bad = []
    for eqn in _iter_eqns(jaxpr):
        name = eqn.primitive.name
        if not any(t in name for t in ("gather", "scatter", "segment")):
            continue
        for var in list(eqn.invars) + list(eqn.outvars):
            shape = getattr(getattr(var, "aval", None), "shape", ())
            if len(shape) >= 1 and shape[0] == max_e:
                bad.append((name, shape))
    return bad


@pytest.mark.parametrize("op", BLOCK_OPS)
def test_kernel_train_step_jaxpr_has_no_edge_aggregation(op):
    """Traced through the typed plan/state/step surface: the pure step
    (runtime.make_step_fn) over a GASBatch + GASState."""
    from repro.core import runtime as R
    g = citation_graph(num_nodes=150, num_features=16, num_classes=4, seed=8)
    spec = GNNSpec(op=op, d_in=16, d_hidden=16, num_classes=4, num_layers=3,
                   alpha=0.1)

    def step_jaxpr(backend):
        plan = R.build_plan(g, spec, R.GASConfig(num_parts=2,
                                                 backend=backend,
                                                 epochs=1, seed=0))
        state = R.init_state(plan)
        jaxpr = jax.make_jaxpr(R.make_step_fn(plan))(
            state, plan.batch_stack[0], plan.x, plan.y, plan.train_mask)
        return jaxpr.jaxpr, plan.batches.max_e

    # sanity: the detector fires on the segment-sum (jnp) path
    jaxpr_jnp, max_e = step_jaxpr("jnp")
    assert _edge_indexed_ops(jaxpr_jnp, max_e), \
        "detector found no edge-indexed aggregation on the jnp path"
    # the kernel path must contain none — fwd AND bwd are block-dense
    jaxpr_ker, max_e = step_jaxpr("interpret")
    bad = _edge_indexed_ops(jaxpr_ker, max_e)
    assert not bad, f"edge-indexed gather/scatter on kernel path: {bad}"


# ---------------------------------------------------------------------------
# Halo hygiene: no op may materialize a float halo tensor decoded from a
# quantized history table. Shape matching alone cannot tell a dequantized
# halo pull from the (allowed) exact layer-0 transform of the same width,
# so taint is tracked from the history-table invars through the jaxpr:
# only float [max_h, width] (or whole-table [N+1, width]) tensors that are
# data-dependent on a table count as violations.
# ---------------------------------------------------------------------------

def _call_subjaxpr(eqn):
    """The callee jaxpr of a call-like eqn whose invars align with a tail
    of eqn.invars (pjit, closed_call, custom_*_call) — None for opaque
    primitives (pallas_call kernels operate on refs, not these vars)."""
    if eqn.primitive.name == "pallas_call":
        return None
    for key in ("jaxpr", "call_jaxpr", "fun_jaxpr"):
        sub = eqn.params.get(key)
        if sub is None:
            continue
        j = getattr(sub, "jaxpr", sub)
        if (hasattr(j, "invars") and len(j.invars) <= len(eqn.invars)
                and len(j.outvars) == len(eqn.outvars)):
            return j
    return None


def _taint_walk(jaxpr, in_taint, hits, pred):
    """Forward taint propagation over one jaxpr (recursing into aligned
    subjaxprs, conservatively tainting all outputs of opaque eqns);
    appends (primitive, shape, dtype) to hits for tainted vars matching
    pred, and returns the taint of the jaxpr's outvars."""
    tainted = {v for v, t in zip(jaxpr.invars, in_taint) if t}

    def is_t(v):
        return not hasattr(v, "val") and v in tainted   # Literals have .val

    for eqn in jaxpr.eqns:
        tin = [is_t(v) for v in eqn.invars]
        sub = _call_subjaxpr(eqn)
        if sub is not None:
            skip = len(eqn.invars) - len(sub.invars)
            out_t = _taint_walk(sub, tin[skip:], hits, pred)
        else:
            out_t = [any(tin)] * len(eqn.outvars)
        for v, t in zip(eqn.outvars, out_t):
            if t:
                tainted.add(v)
                aval = getattr(v, "aval", None)
                if aval is not None and pred(aval):
                    hits.append((eqn.primitive.name, aval.shape, aval.dtype))
    return [is_t(v) for v in jaxpr.outvars]


def _tainted_history_halos(closed, store, max_h, width, n1):
    t_avals = {(t.shape, jnp.dtype(t.dtype)) for t in store.tables}
    jaxpr = closed.jaxpr
    in_taint = [(v.aval.shape, jnp.dtype(v.aval.dtype)) in t_avals
                for v in jaxpr.invars]
    assert any(in_taint), "history tables not found among jaxpr invars"

    def pred(aval):
        shape = aval.shape
        return (jnp.issubdtype(aval.dtype, jnp.floating)
                and ((len(shape) >= 2 and shape[0] == max_h
                      and shape[-1] == width)
                     or shape == (n1, width)))

    hits = []
    _taint_walk(jaxpr, in_taint, hits, pred)
    return hits


@pytest.mark.parametrize("hd", ("int8", "vq"))
@pytest.mark.parametrize("op", BLOCK_OPS)
def test_forward_jaxpr_no_quantized_halo_materialization(op, hd):
    """For EVERY op (including the GAT/PNA halo-split route and the
    class-width APPNP tables) the kernel-path forward never decodes a
    history table into a float [max_h, width] halo tensor or a float
    [N+1, width] whole-table copy."""
    from repro.core import runtime as R
    g = citation_graph(num_nodes=150, num_features=16, num_classes=8,
                       seed=8)
    spec = GNNSpec(op=op, d_in=16, d_hidden=24, num_classes=8,
                   num_layers=3, alpha=0.1, heads=4, log_deg_mean=1.5)

    def fwd_hits(backend):
        plan = R.build_plan(g, spec, R.GASConfig(
            num_parts=3, backend=backend, history_dtype=hd, epochs=1,
            seed=0))
        state = R.init_state(plan)
        batch = plan.batch_stack[0]

        def fwd(hist, x):
            return gas_batch_forward(state.params, plan.spec, x, batch,
                                     hist, backend=backend)[0]

        closed = jax.make_jaxpr(fwd)(state.histories, plan.x)
        width = plan.spec.hist_dims()[0]
        # precondition: max_h must not collide with the other row counts
        # the forward produces, or shape matching is ambiguous
        max_h, max_b = plan.batches.max_h, plan.batches.max_b
        assert max_h not in (max_b, -(-max_b // 64) * 64)
        return _tainted_history_halos(closed, state.histories, max_h,
                                      width, g.num_nodes + 1)

    # sanity: the jnp path decodes pulled halos into [max_h, width]
    # floats, so the taint detector is alive for this op/dtype
    assert fwd_hits("jnp"), "taint detector found nothing on the jnp path"
    hits = fwd_hits("interpret")
    assert not hits, f"history-derived float halo on {op}/{hd}: {hits}"


# ---------------------------------------------------------------------------
# End-to-end: fused == unfused == jnp for every block op (fwd through layers)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("op", BLOCK_OPS)
def test_gas_batch_forward_fused_matches_jnp(op):
    g = citation_graph(num_nodes=250, num_features=16, num_classes=4, seed=4)
    part = np.random.default_rng(4).integers(0, 3, g.num_nodes)
    part = np.unique(part, return_inverse=True)[1].astype(np.int32)
    b = G.build_batches(g, part, build_blocks=True,
                        unit_weights=(op in UNIT_BLOCK_OPS))
    spec = GNNSpec(op=op, d_in=16, d_hidden=16, num_classes=4, num_layers=3,
                   alpha=0.1, heads=4, log_deg_mean=1.5)
    params = init_gnn(jax.random.key(0), spec)
    x = jnp.asarray(g.x)

    outs = {}
    for backend, fuse in (("jnp", False), ("interpret", True),
                          ("interpret", False)):
        # f32 pinned: this is the exact-store equivalence baseline (the
        # bf16/int8 variants live in tests/test_quantized_history.py)
        hist = H.HistoryStore.create(g.num_nodes + 1, spec.hist_dims(),
                                     backend=backend, history_dtype="f32")
        logits = []
        for bb in range(b.num_batches):
            batch = b.device_batch(bb)
            lg, hist, _, diags = gas_batch_forward(
                params, spec, x, batch, hist, backend=backend,
                fuse_halo=fuse)
            logits.append(np.asarray(lg, np.float32))
        assert set(diags) == {"halo_age_mean", "halo_age_max",
                              "hist_quant_err"}
        assert float(diags["hist_quant_err"]) == 0.0   # f32 store
        outs[(backend, fuse)] = np.stack(logits)
    np.testing.assert_allclose(outs[("interpret", True)], outs[("jnp", False)],
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(outs[("interpret", False)],
                               outs[("jnp", False)], rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# Satellites: jitted gas_predict, staleness diagnostics
# ---------------------------------------------------------------------------

def test_gas_predict_jitted_scan_matches_manual_loop():
    g = citation_graph(num_nodes=200, num_features=16, num_classes=4, seed=6)
    spec = GNNSpec(op="gcn", d_in=16, d_hidden=16, num_classes=4,
                   num_layers=3)
    plan = R.build_plan(g, spec, R.GASConfig(num_parts=3, backend="jnp",
                                             epochs=2, seed=0))
    state, _ = R.fit(plan, R.init_state(plan), 2)
    got = np.asarray(R.predict(plan, state))

    N, C = g.num_nodes, spec.num_classes
    expect = np.zeros((N, C), np.float32)
    hist = state.histories
    for bi in range(plan.batches.num_batches):
        batch = plan.batch_stack[bi]
        logits, hist, _, _ = gas_batch_forward(
            state.params, spec, plan.x, batch, hist, backend="jnp")
        nodes = np.asarray(batch.batch_nodes)
        mask = np.asarray(batch.batch_mask)
        expect[nodes[mask]] = np.asarray(logits)[mask]
    np.testing.assert_allclose(got, expect, rtol=1e-5, atol=1e-5)


def test_staleness_diags_in_train_metrics():
    g = citation_graph(num_nodes=200, num_features=16, num_classes=4, seed=6)
    spec = GNNSpec(op="gcn", d_in=16, d_hidden=16, num_classes=4,
                   num_layers=3)
    plan = R.build_plan(g, spec, R.GASConfig(num_parts=4, epochs=3, seed=0))
    state = R.init_state(plan)
    state, m0 = R.train_epoch(plan, state, 0)
    assert {"halo_age_mean", "halo_age_max"} <= set(m0)
    state, _ = R.train_epoch(plan, state, 1)
    state, m2 = R.train_epoch(plan, state, 2)
    # after warmup, pulled halo rows are genuinely stale (age > 0) and the
    # max is at least the mean
    assert m2["halo_age_mean"] > 0.0
    assert m2["halo_age_max"] >= m2["halo_age_mean"]


def test_gas_forward_diags_and_fused_hook():
    """`gas_batch_forward` populates the staleness and quantization
    diagnostics, and its fused route (`fuse_halo=True`: layers >= 1
    aggregate straight out of the history tables) gives the same logits
    and pushes the same rows as the materialized route, on a second batch
    that pulls the first batch's pushes."""
    g = citation_graph(num_nodes=200, num_features=16, num_classes=4, seed=2)
    part = np.random.default_rng(0).integers(0, 2, g.num_nodes)
    part = np.unique(part, return_inverse=True)[1].astype(np.int32)
    b = G.build_batches(g, part, build_blocks=True)
    assert len(b.device_batch(0).blocks) == 4   # transposed family present
    spec = GNNSpec(op="gcn", d_in=16, d_hidden=16, num_classes=4,
                   num_layers=3)
    params = init_gnn(jax.random.key(0), spec)
    x = jnp.asarray(g.x)

    outs = {}
    for fuse in (False, True):
        hist = H.HistoryStore.create(g.num_nodes + 1, spec.hist_dims(),
                                     backend="interpret",
                                     history_dtype="f32")
        logits = []
        for bb in range(b.num_batches):
            lg, hist, _, diags = gas_batch_forward(
                params, spec, x, b.device_batch(bb), hist,
                backend="interpret", fuse_halo=fuse)
            logits.append(np.asarray(lg))
            assert set(diags) == {"halo_age_mean", "halo_age_max",
                                  "hist_quant_err"}
        assert all(np.abs(np.asarray(t)).sum() > 0 for t in hist.tables)
        outs[fuse] = (logits, hist)
    for a, c in zip(outs[True][0], outs[False][0]):
        np.testing.assert_allclose(a, c, rtol=1e-4, atol=1e-4)
    for a, c in zip(outs[True][1].tables, outs[False][1].tables):
        np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                   rtol=1e-4, atol=1e-4)
    np.testing.assert_array_equal(np.asarray(outs[True][1].age),
                                  np.asarray(outs[False][1].age))


def test_fused_vq_aggregate_refused_on_pallas():
    """Mosaic refuses the vq form of the fused gather-SpMM: the compiled
    backend says so instead of falling back."""
    from repro.kernels import ops
    blocks = (jnp.zeros((1, 1, 128, 128)), jnp.zeros((1, 1), jnp.int32),
              jnp.zeros((1, 1, 128, 128)), jnp.zeros((1, 1), jnp.int32))
    with pytest.raises(NotImplementedError, match="fuse_halo=False"):
        ops.gas_aggregate(jnp.zeros((8, 128)),
                          jnp.zeros((16, 16), jnp.uint8),
                          jnp.zeros((4,), jnp.int32), jnp.ones((4,), bool),
                          8, blocks, scales=jnp.ones((16,)),
                          codebook=jnp.zeros((16, 256, 8)),
                          backend="pallas")
