"""Backend-dispatch layer (kernels/ops.py) correctness.

Deterministic (hypothesis-free) coverage: the "interpret" backend — the
exact Pallas kernels that run compiled on TPU — must match the "jnp"
reference backend through every dispatched op AND end-to-end through
`gas_batch_forward` on a real citation graph, in float32 and bfloat16.
`scatter_rows` is additionally unit-tested against its oracle (random
masks, duplicate indices, padded rows); the hypothesis property sweeps
live in test_kernels.py.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import gas as G
from repro.core import history as H
from repro.data.graphs import citation_graph
from repro.gnn.model import GNNSpec, gas_batch_forward, init_gnn
from repro.kernels import ops
from repro.kernels.ref import scatter_rows_ref


# ---------------------------------------------------------------------------
# resolve_backend contract
# ---------------------------------------------------------------------------

def test_resolve_backend_auto_and_overrides(monkeypatch):
    monkeypatch.delenv("REPRO_KERNEL_BACKEND", raising=False)
    # auto: never "interpret", and "jnp" on CPU
    assert ops.resolve_backend() in ("pallas", "jnp")
    if jax.default_backend() != "tpu":
        assert ops.resolve_backend() == "jnp"
    # explicit arg wins
    assert ops.resolve_backend("interpret") == "interpret"
    # env override
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "interpret")
    assert ops.resolve_backend() == "interpret"
    # process-wide default beats env
    ops.set_default_backend("jnp")
    try:
        assert ops.resolve_backend() == "jnp"
        assert ops.resolve_backend("interpret") == "interpret"
    finally:
        ops.set_default_backend(None)
    with pytest.raises(ValueError):
        ops.resolve_backend("cuda")
    with pytest.raises(ValueError):
        ops.set_default_backend("tpu")


# ---------------------------------------------------------------------------
# scatter_rows / push_rows vs oracle (unit tests)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("N,D,M,bd", [(64, 128, 17, 128), (256, 512, 64, 128),
                                      (32, 256, 1, 256)])
def test_scatter_rows_shapes(dtype, N, D, M, bd):
    rng = np.random.default_rng(N + D + M)
    table = jnp.asarray(rng.normal(size=(N, D)).astype(np.float32), dtype)
    idx = jnp.asarray(rng.integers(0, N, size=M).astype(np.int32))
    values = jnp.asarray(rng.normal(size=(M, D)).astype(np.float32), dtype)
    mask = jnp.ones((M,), bool)
    out = ops.push_rows(table, idx, values, mask, backend="interpret", bd=bd)
    ref = scatter_rows_ref(table, idx, values, mask)
    np.testing.assert_array_equal(np.asarray(out, np.float32),
                                  np.asarray(ref, np.float32))


def test_scatter_rows_masked_rows_dropped():
    rng = np.random.default_rng(0)
    table = jnp.asarray(rng.normal(size=(32, 128)).astype(np.float32))
    idx = jnp.asarray([3, 7, 11, 7], dtype=jnp.int32)
    values = jnp.asarray(rng.normal(size=(4, 128)).astype(np.float32))
    mask = jnp.asarray([True, False, True, False])
    out = ops.push_rows(table, idx, values, mask, backend="interpret")
    expect = np.asarray(table).copy()
    expect[3] = np.asarray(values)[0]
    expect[11] = np.asarray(values)[2]   # rows 7 are masked out -> untouched
    np.testing.assert_array_equal(np.asarray(out), expect)


def test_scatter_rows_duplicates_last_wins():
    table = jnp.zeros((16, 128), jnp.float32)
    idx = jnp.asarray([5, 5, 5], dtype=jnp.int32)
    values = jnp.stack([jnp.full((128,), v) for v in (1.0, 2.0, 3.0)])
    mask = jnp.ones((3,), bool)
    out = ops.push_rows(table, idx, values, mask, backend="interpret")
    np.testing.assert_array_equal(np.asarray(out)[5], np.full(128, 3.0))
    ref = scatter_rows_ref(table, idx, values, mask)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_scatter_rows_padded_rows_out_of_range():
    """GAS padding: idx rows carrying the sentinel N with mask=False must
    never clobber real rows (matches core.history.push drop semantics)."""
    rng = np.random.default_rng(1)
    N = 24
    table = jnp.asarray(rng.normal(size=(N, 128)).astype(np.float32))
    idx = jnp.asarray([2, N, N], dtype=jnp.int32)   # N = pad sentinel
    values = jnp.asarray(rng.normal(size=(3, 128)).astype(np.float32))
    mask = jnp.asarray([True, False, False])
    for backend in ("interpret", "jnp"):
        out = ops.push_rows(table, idx, values, mask, backend=backend)
        expect = np.asarray(table).copy()
        expect[2] = np.asarray(values)[0]
        np.testing.assert_array_equal(np.asarray(out), expect)


def _bits(a):
    a = np.asarray(a)
    return a.view(f"u{a.dtype.itemsize}")


def _chunk_case(case, rng):
    """(N, D, idx, mask, scratch_last_row) of a `scatter_rows` chunk case."""
    if case == "chunks":         # 3 chunks, a tile's rows split by each cut
        N, M = 4096, 700
        idx = 3 + rng.permutation(M)
    elif case == "few":          # M < GROUP: one chunk of one GROUP
        N, M = 64, 5
        idx = rng.choice(N, M, replace=False)
    elif case == "dense":        # most tiles take several rows
        N, M = 200, 700
        idx = rng.integers(0, N, M)
    elif case == "dups":         # runs of one index across the chunk cuts
        N, M = 1024, 700
        idx = rng.integers(0, 64, M)
    elif case == "tail":         # real rows in the partial last tile
        N, M = 1003, 300
        idx = rng.integers(960, N, M)
    else:                        # "masked": padding rows to the sentinel
        N, M = 1001, 600
        idx = rng.integers(0, N - 1, M)
    mask = rng.random(M) < 0.7 if case == "masked" else np.ones(M, bool)
    return N, 128, idx.astype(np.int32), mask, case == "masked"


CHUNK_CASES = ["chunks", "few", "dense", "dups", "tail", "masked"]


@pytest.mark.parametrize("case", CHUNK_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_scatter_rows_chunked(dtype, case):
    """The chunked push is bitwise `scatter_rows_ref`: rows in several
    chunks, tiles split by a chunk cut, duplicates (last wins), the
    table's partial last tile, masked rows, and -0.0 / inf / NaN
    payloads, which the merge's select keeps."""
    from repro.kernels import scatter
    rng = np.random.default_rng(CHUNK_CASES.index(case))
    N, D, idx, mask, scratch = _chunk_case(case, rng)
    M = idx.shape[0]
    c = scatter.chunk_rows(M, D)
    order = np.sort(idx[mask])
    if case in ("chunks", "dups"):
        assert M > 2 * c
        assert any(order[k * c - 1] // 8 == order[k * c] // 8
                   for k in range(1, -(-M // c)))
    table = jnp.asarray(rng.normal(size=(N, D)).astype(np.float32), dtype)
    vals = rng.normal(size=(M, D)).astype(np.float32)
    vals[0, :4] = [-0.0, np.inf, -np.inf, np.nan]
    values = jnp.asarray(vals, dtype)
    out = ops.push_rows(table, jnp.asarray(idx), values, jnp.asarray(mask),
                        backend="interpret", scratch_last_row=scratch)
    ref = scatter_rows_ref(table, jnp.asarray(idx), values,
                           jnp.asarray(mask))
    keep = N - 1 if scratch else N        # the sentinel is unspecified
    np.testing.assert_array_equal(_bits(out)[:keep], _bits(ref)[:keep])


@pytest.mark.parametrize("case", CHUNK_CASES)
def test_scatter_rows_q_chunked(case):
    """Quantizing mode: each pushed row lands as
    `core.history.quantize_rows` of its last occurrence, bitwise, in the
    same cases; the scales land beside it."""
    rng = np.random.default_rng(10 + CHUNK_CASES.index(case))
    N, D, idx, mask, scratch = _chunk_case(case, rng)
    M = idx.shape[0]
    table = jnp.asarray(rng.integers(-127, 128, (N, D)), jnp.int8)
    scales = jnp.asarray(rng.random(N).astype(np.float32))
    values = jnp.asarray(rng.normal(size=(M, D)).astype(np.float32) * 3)
    new_t, new_s = ops.push_rows_q(table, scales, jnp.asarray(idx), values,
                                   jnp.asarray(mask), backend="interpret",
                                   scratch_last_row=scratch)
    q, s = H.quantize_rows(values)
    ref_t = scatter_rows_ref(table, jnp.asarray(idx), q, jnp.asarray(mask))
    ref_s = scatter_rows_ref(scales[:, None], jnp.asarray(idx), s[:, None],
                             jnp.asarray(mask))[:, 0]
    keep = N - 1 if scratch else N
    np.testing.assert_array_equal(np.asarray(new_t)[:keep],
                                  np.asarray(ref_t)[:keep])
    np.testing.assert_array_equal(_bits(new_s)[:keep], _bits(ref_s)[:keep])


def test_push_pull_roundtrip_matches_history_module():
    """ops.push_rows/pull_rows on the kernel path == core.history push/pull."""
    rng = np.random.default_rng(2)
    N, D, M = 50, 96, 12   # D deliberately not a multiple of bd (padding)
    table = jnp.asarray(rng.normal(size=(N, D)).astype(np.float32))
    idx = jnp.asarray(rng.permutation(N)[:M].astype(np.int32))
    values = jnp.asarray(rng.normal(size=(M, D)).astype(np.float32))
    mask = jnp.asarray(rng.random(M) < 0.8)
    t_kernel = ops.push_rows(table, idx, values, mask, backend="interpret")
    t_hist = H.push(table, idx, values, mask)
    np.testing.assert_array_equal(np.asarray(t_kernel), np.asarray(t_hist))
    pulled = ops.pull_rows(t_kernel, idx, backend="interpret")
    np.testing.assert_array_equal(np.asarray(pulled),
                                  np.asarray(H.pull(t_hist, idx)))


# ---------------------------------------------------------------------------
# GCN aggregation: BCSR kernel path vs segment-sum path
# ---------------------------------------------------------------------------

def _citation_batches(n=300, parts=4, seed=3):
    g = citation_graph(num_nodes=n, num_features=16, num_classes=4, seed=seed)
    part = np.random.default_rng(seed).integers(0, parts, n).astype(np.int32)
    part = np.unique(part, return_inverse=True)[1].astype(np.int32)
    return g, G.build_batches(g, part, build_blocks=True)


def test_gcn_aggregate_blocks_match_segment_sum():
    g, b = _citation_batches()
    rng = np.random.default_rng(0)
    for bb in range(b.num_batches):
        batch = b.device_batch(bb)
        M = b.max_b + b.max_h + 1
        x_all = jnp.asarray(rng.normal(size=(M, 16)).astype(np.float32))
        ref = ops.gcn_aggregate(
            x_all, (batch.edge_dst, batch.edge_src), batch.edge_w,
            b.max_b, None, backend="jnp")
        out = ops.gcn_aggregate(
            x_all, (batch.edge_dst, batch.edge_src), batch.edge_w,
            b.max_b, (batch.forward.vals, batch.forward.cols),
            backend="interpret")
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)


def test_spmm_gradient_matches_reference():
    """The custom VJP of the kernel spmm path == autodiff of the jnp path."""
    g, b = _citation_batches(n=200, parts=2)
    batch = b.device_batch(0)
    M = b.max_b + b.max_h + 1
    x_all = jnp.asarray(np.random.default_rng(4).normal(
        size=(M, 16)).astype(np.float32))

    def loss(x, backend, blocks):
        out = ops.gcn_aggregate(
            x, (batch.edge_dst, batch.edge_src), batch.edge_w,
            b.max_b, blocks, backend=backend)
        return jnp.sum(out ** 2)

    g_jnp = jax.grad(lambda x: loss(x, "jnp", None))(x_all)
    g_ker = jax.grad(lambda x: loss(
        x, "interpret", (batch.forward.vals, batch.forward.cols)))(x_all)
    np.testing.assert_allclose(np.asarray(g_ker), np.asarray(g_jnp),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# End-to-end: gas_batch_forward backend equivalence on the citation graph
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("d_hidden", [16, 128])
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5),
                                       (jnp.bfloat16, 5e-2)])
def test_gas_forward_backend_equivalence(dtype, tol, d_hidden):
    """d_hidden=16 exercises the padded push path; d_hidden=128 (a bd
    multiple) exercises the in-place scratch-row push. The sentinel row
    (last) is excluded from table comparison — its contents are
    unspecified under scratch_last_row."""
    g, b = _citation_batches()
    spec = GNNSpec(op="gcn", d_in=16, d_hidden=d_hidden, num_classes=4,
                   num_layers=3)
    params = init_gnn(jax.random.key(0), spec)
    params = jax.tree_util.tree_map(lambda a: a.astype(dtype), params)
    x = jnp.asarray(g.x).astype(dtype)

    outs = {}
    tables = {}
    for backend in ("jnp", "interpret"):
        # history_dtype pinned: this test measures jnp-vs-interpret kernel
        # equivalence with the store in the COMPUTE dtype; under an env
        # int8 override the round() bucket flips from bf16 compute noise
        # would dominate the comparison (quantized-store equivalence is
        # covered by tests/test_quantized_history.py)
        hist = H.HistoryStore.create(g.num_nodes + 1, spec.hist_dims(),
                                     dtype=dtype, backend=backend,
                                     history_dtype="f32")
        logits = []
        for bb in range(b.num_batches):
            batch = b.device_batch(bb)
            lg, hist, _, _ = gas_batch_forward(params, spec, x, batch, hist,
                                               backend=backend)
            logits.append(np.asarray(lg, np.float32))
        outs[backend] = np.stack(logits)
        tables[backend] = [np.asarray(t, np.float32)[:-1]
                           for t in hist.tables]

    np.testing.assert_allclose(outs["interpret"], outs["jnp"],
                               rtol=tol, atol=tol)
    for ti, tj in zip(tables["interpret"], tables["jnp"]):
        np.testing.assert_allclose(ti, tj, rtol=tol, atol=tol)


def test_gas_training_backend_equivalence():
    """Full jitted train steps agree between backends (fwd+bwd+AdamW)."""
    from repro.core import runtime as R
    g, _ = _citation_batches(n=200, parts=2)
    spec = GNNSpec(op="gcn", d_in=16, d_hidden=16, num_classes=4,
                   num_layers=2)
    losses = {}
    for backend in ("jnp", "interpret"):
        plan = R.build_plan(g, spec, R.GASConfig(
            num_parts=2, backend=backend, epochs=2, seed=0))
        _, out = R.fit(plan, R.init_state(plan))
        losses[backend] = [m["loss"] for m in out]
    np.testing.assert_allclose(losses["interpret"], losses["jnp"],
                               rtol=1e-4, atol=1e-4)
