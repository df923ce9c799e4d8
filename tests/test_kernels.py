"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps + hypothesis
property tests (interpret mode on CPU; same kernels target real TPUs).

The property tests need `hypothesis` (see requirements-dev.txt); without
it this module skips at collection so the deterministic parametrized tests
in the other modules still run."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
st = pytest.importorskip("hypothesis.strategies")
from hypothesis import given, settings  # noqa: E402

from repro.core.gas import gcn_edge_weights
from repro.data.graphs import citation_graph
from repro.kernels import ops
from repro.kernels.ref import (bcsr_spmm_ref, gather_rows_ref,
                              scatter_rows_ref)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("bn,bd,R,K,D", [
    (128, 128, 2, 3, 256),
    (128, 128, 4, 1, 128),
    (128, 256, 3, 5, 512),
])
def test_bcsr_spmm_shapes(dtype, bn, bd, R, K, D):
    rng = np.random.default_rng(bn + R + K + D)
    Nc = R + 1
    x = rng.normal(size=(Nc * bn, D)).astype(np.float32)
    vals = (rng.random((R, K, bn, bn)) < 0.05).astype(np.float32) * \
        rng.normal(size=(R, K, bn, bn)).astype(np.float32)
    cols = rng.integers(0, Nc, size=(R, K)).astype(np.int32)
    xd = jnp.asarray(x, dtype)
    vd = jnp.asarray(vals, dtype)
    out = ops.spmm(xd, vd, jnp.asarray(cols), bn=bn, bd=bd,
                   backend="interpret")
    ref = bcsr_spmm_ref(xd, vd, jnp.asarray(cols))
    tol = 1e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("N,D,M,bd", [(64, 128, 17, 128), (256, 512, 64, 128),
                                      (32, 256, 1, 256)])
def test_gather_rows_shapes(dtype, N, D, M, bd):
    rng = np.random.default_rng(N + D + M)
    table = jnp.asarray(rng.normal(size=(N, D)).astype(np.float32), dtype)
    idx = jnp.asarray(rng.integers(0, N, size=M).astype(np.int32))
    out = ops.pull_rows(table, idx, bd=bd, backend="interpret")
    ref = gather_rows_ref(table, idx)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_bcsr_from_real_graph_matches_dense():
    g = citation_graph(num_nodes=500, seed=7)
    dst, src, w = gcn_edge_weights(g)
    vals, cols, Np = ops.build_bcsr(dst, src, w, g.num_nodes, bn=128)
    x = np.random.default_rng(0).normal(size=(Np, 128)).astype(np.float32)
    out = ops.spmm(jnp.asarray(x), jnp.asarray(vals), jnp.asarray(cols),
                   backend="interpret")
    A = np.zeros((Np, Np), np.float32)
    np.add.at(A, (dst, src), w)
    np.testing.assert_allclose(np.asarray(out)[:g.num_nodes],
                               (A @ x)[:g.num_nodes], rtol=1e-4, atol=1e-4)


@settings(max_examples=15, deadline=None)
@given(st.integers(1, 4), st.integers(1, 4), st.data())
def test_bcsr_spmm_property(R, K, data):
    """Random block structures: kernel == oracle."""
    rng = np.random.default_rng(data.draw(st.integers(0, 2**31)))
    bn, D = 128, 128
    Nc = R
    x = rng.normal(size=(Nc * bn, D)).astype(np.float32)
    vals = rng.normal(size=(R, K, bn, bn)).astype(np.float32)
    cols = rng.integers(0, Nc, size=(R, K)).astype(np.int32)
    out = ops.spmm(jnp.asarray(x), jnp.asarray(vals), jnp.asarray(cols),
                   backend="interpret")
    ref = bcsr_spmm_ref(jnp.asarray(x), jnp.asarray(vals), jnp.asarray(cols))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4,
                               atol=2e-4)


@settings(max_examples=15, deadline=None)
@given(st.integers(1, 64), st.data())
def test_gather_property(M, data):
    rng = np.random.default_rng(data.draw(st.integers(0, 2**31)))
    N, D = 64, 128
    table = jnp.asarray(rng.normal(size=(N, D)).astype(np.float32))
    idx = jnp.asarray(rng.integers(0, N, size=M).astype(np.int32))
    np.testing.assert_array_equal(
        np.asarray(ops.pull_rows(table, idx, backend="interpret")),
        np.asarray(table)[np.asarray(idx)])


@settings(max_examples=15, deadline=None)
@given(st.integers(1, 64), st.data())
def test_scatter_property(M, data):
    """Random masks, duplicate indices, padded rows: push_rows kernel ==
    scatter_rows_ref oracle (masked rows dropped, last duplicate wins)."""
    rng = np.random.default_rng(data.draw(st.integers(0, 2**31)))
    N, D = 64, 128
    table = jnp.asarray(rng.normal(size=(N, D)).astype(np.float32))
    # duplicates on purpose: small index range relative to M
    idx = jnp.asarray(rng.integers(0, max(N // 2, 1), size=M
                                   ).astype(np.int32))
    values = jnp.asarray(rng.normal(size=(M, D)).astype(np.float32))
    mask = jnp.asarray(rng.random(M) < 0.7)
    out = ops.push_rows(table, idx, values, mask, backend="interpret")
    ref = scatter_rows_ref(table, idx, values, mask)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


# ---------------------------------------------------------------------------
# flash-decode kernel (kernels/decode_attn.py)
# ---------------------------------------------------------------------------

def _decode_ref(q, k, v, pos):
    B, Kh, G, Dh = q.shape
    S = k.shape[1]
    s = jnp.einsum("bhgd,bshd->bhgs", q, k).astype(jnp.float32) / np.sqrt(Dh)
    idx = jnp.arange(S)
    valid = jnp.where(pos >= S, jnp.ones(S, bool), idx <= pos)
    s = jnp.where(valid[None, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, -1)
    return jnp.einsum("bhgs,bshd->bhgd", p.astype(v.dtype), v)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,Kh,G,Dh,S,pos", [
    (2, 2, 4, 64, 512, 511), (1, 4, 2, 128, 1024, 300),
    (2, 1, 8, 64, 512, 600),   # pos >= S: rolling buffer fully valid
])
def test_flash_decode_vs_ref(dtype, B, Kh, G, Dh, S, pos):
    from repro.kernels.decode_attn import flash_decode
    ks = jax.random.split(jax.random.key(B + S + pos), 3)
    q = jax.random.normal(ks[0], (B, Kh, G, Dh), dtype)
    k = jax.random.normal(ks[1], (B, S, Kh, Dh), dtype)
    v = jax.random.normal(ks[2], (B, S, Kh, Dh), dtype)
    out = flash_decode(q, k, v, jnp.array(pos, jnp.int32), block_s=256,
                       interpret=True)
    ref = _decode_ref(q.astype(jnp.float32), k.astype(jnp.float32),
                      v.astype(jnp.float32), pos)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref),
                               rtol=tol, atol=tol)


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 1023), st.data())
def test_flash_decode_position_property(pos, data):
    """Entries beyond `pos` never influence the output."""
    from repro.kernels.decode_attn import flash_decode
    seed = data.draw(st.integers(0, 2**31))
    ks = jax.random.split(jax.random.key(seed), 4)
    B, Kh, G, Dh, S = 1, 2, 2, 64, 1024
    q = jax.random.normal(ks[0], (B, Kh, G, Dh))
    k = jax.random.normal(ks[1], (B, S, Kh, Dh))
    v = jax.random.normal(ks[2], (B, S, Kh, Dh))
    out1 = flash_decode(q, k, v, jnp.array(pos, jnp.int32), block_s=256,
                       interpret=True)
    # perturb only the masked tail
    if pos < S - 1:
        k2 = k.at[:, pos + 1:].set(jax.random.normal(ks[3],
                                                     k[:, pos + 1:].shape))
        out2 = flash_decode(q, k2, v, jnp.array(pos, jnp.int32), block_s=256,
                       interpret=True)
        np.testing.assert_allclose(np.asarray(out1), np.asarray(out2),
                                   rtol=1e-6, atol=1e-6)
