"""Async history pipeline (PR 7) correctness.

The whole pipeline — epoch-level halo prefetch (`prefetch_depth`),
host-spilled history tables (`storage="host"`), and the double-buffered
kernel gathers underneath — is only admissible because it is BIT-
IDENTICAL to the synchronous schedule. These tests pin that contract:

 - prefetched `train_epoch` (depth 1) == synchronous (depth 0) for all
   6 ops x {f32, int8}: params, opt state, history tables/scales/age,
   and per-epoch metrics all exactly equal;
 - deeper pipelines + the interpret kernel path stay bit-identical;
 - `storage="host"` training and checkpoints are bit-identical to
   device-resident stores (on CPU the host memory kind degenerates to a
   no-op move but drives the same placement/streaming code path);
 - the pipelined step really does dispatch batch i+depth's halo pull
   BEFORE batch i's push (jaxpr order assertion — the overlap claim);
 - the row-blocked dequantizing `gather_rows` (8, bd) tiles match the dequant
   oracle bitwise for ragged row counts.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import history as H
from repro.core import runtime as R
from repro.data.graphs import citation_graph
from repro.gnn.model import GNNSpec
from repro.train.checkpoint import load_gas_state, save_gas_state

OPS = ("gcn", "gin", "gcnii", "appnp", "gat", "pna")


def _train(op, history_dtype, prefetch_depth, storage="device",
           backend="jnp", epochs=2, n=140, parts=3, seed=7):
    g = citation_graph(num_nodes=n, num_features=16, num_classes=4,
                       seed=seed)
    spec = GNNSpec(op=op, d_in=16, d_hidden=16, num_classes=4,
                   num_layers=3)
    cfg = R.GASConfig(num_parts=parts, backend=backend,
                      history_dtype=history_dtype,
                      history_storage=storage,
                      prefetch_depth=prefetch_depth, epochs=epochs,
                      seed=3)
    plan = R.build_plan(g, spec, cfg)
    state = R.init_state(plan)
    metrics = None
    for e in range(epochs):
        state, metrics = R.train_epoch(plan, state, e)
    return plan, state, metrics


def _assert_bit_identical(sa, sb, ma=None, mb=None):
    ha, hb = sa.histories, sb.histories
    for name, ta, tb in (("params", sa.params, sb.params),
                        ("opt_state", sa.opt_state, sb.opt_state),
                        ("tables", ha.tables, hb.tables),
                        ("scales", ha.scales, hb.scales),
                        ("age", ha.age, hb.age)):
        la = jax.tree_util.tree_leaves(ta)
        lb = jax.tree_util.tree_leaves(tb)
        assert len(la) == len(lb), name
        for i, (a, b) in enumerate(zip(la, lb)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          err_msg=f"{name}[{i}]")
    if ma is not None:
        assert set(ma) == set(mb)
        for k in ma:
            np.testing.assert_array_equal(np.asarray(ma[k]),
                                          np.asarray(mb[k]),
                                          err_msg=f"metrics[{k}]")


# ---------------------------------------------------------------------------
# prefetch_depth bit-identity: all ops x history dtypes
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("op", OPS)
@pytest.mark.parametrize("hd", ["f32", "int8"])
def test_prefetch_epoch_bit_identical(op, hd):
    _, s_sync, m_sync = _train(op, hd, prefetch_depth=0)
    _, s_pipe, m_pipe = _train(op, hd, prefetch_depth=1)
    _assert_bit_identical(s_sync, s_pipe, m_sync, m_pipe)


@pytest.mark.parametrize("hd", ["f32", "int8"])
def test_prefetch_depth2_interpret_bit_identical(hd):
    """Deeper pipeline through the kernel (interpret) path: two pulls in
    flight, every queued entry patched by intervening pushes."""
    _, s_sync, m_sync = _train("gcn", hd, prefetch_depth=0,
                               backend="interpret", epochs=1, n=90)
    _, s_pipe, m_pipe = _train("gcn", hd, prefetch_depth=2,
                               backend="interpret", epochs=1, n=90)
    _assert_bit_identical(s_sync, s_pipe, m_sync, m_pipe)


def test_prefetch_depth_clamped_to_num_batches():
    """depth > num_batches - 1 cannot outrun the epoch; the schedule
    clamps instead of reading stale queue slots."""
    _, s_sync, m_sync = _train("gcn", "f32", prefetch_depth=0)
    _, s_pipe, m_pipe = _train("gcn", "f32", prefetch_depth=99)
    _assert_bit_identical(s_sync, s_pipe, m_sync, m_pipe)


# ---------------------------------------------------------------------------
# host-spilled stores
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("hd", ["f32", "int8"])
def test_host_storage_training_bit_identical(hd):
    """storage="host" is a placement decision, not a numeric one: the
    pipelined host-store run matches the device-store run exactly."""
    _, s_dev, m_dev = _train("gcn", hd, prefetch_depth=1,
                             storage="device")
    _, s_host, m_host = _train("gcn", hd, prefetch_depth=1,
                               storage="host")
    assert s_host.histories.storage == "host"
    _assert_bit_identical(s_dev, s_host, m_dev, m_host)


@pytest.mark.parametrize("hd", ["f32", "int8"])
def test_host_storage_checkpoint_roundtrip_bit_identical(tmp_path, hd):
    """save -> restore -> `place()` -> one more epoch == uninterrupted
    training, bitwise, for host-pinned tables."""
    plan, state, _ = _train("gcn", hd, prefetch_depth=1, storage="host",
                            epochs=1)
    path = str(tmp_path / "host_ckpt.npz")
    save_gas_state(path, state, step=1)
    restored, step = load_gas_state(path, R.init_state(plan))
    assert step == 1
    # the template carries the storage meta; re-place pins the restored
    # tables back to the host memory kind
    assert restored.histories.storage == "host"
    restored = restored.replace(histories=restored.histories.place())
    _assert_bit_identical(state, restored)

    s_cont, m_cont = R.train_epoch(plan, state, 1)
    s_rest, m_rest = R.train_epoch(plan, restored, 1)
    _assert_bit_identical(s_cont, s_rest, m_cont, m_rest)


def test_resolve_history_storage():
    import os
    assert H.resolve_history_storage(None) in H.HISTORY_STORAGES
    assert H.resolve_history_storage("host") == "host"
    with pytest.raises(ValueError):
        H.resolve_history_storage("vmem")
    old = os.environ.get("REPRO_HISTORY_STORAGE")
    try:
        os.environ["REPRO_HISTORY_STORAGE"] = "host"
        assert H.resolve_history_storage(None) == "host"
    finally:
        if old is None:
            os.environ.pop("REPRO_HISTORY_STORAGE", None)
        else:
            os.environ["REPRO_HISTORY_STORAGE"] = old


# ---------------------------------------------------------------------------
# the overlap claim itself: pull dispatched before push (jaxpr order)
# ---------------------------------------------------------------------------

def test_prefetch_step_pull_dispatched_before_push():
    """In the pipelined step's jaxpr, the FIRST gather touching a full
    [N+1, d_hidden] history table (the future batch's halo pull) must
    precede the FIRST scatter into one (this batch's push): the pull is
    in flight before the push lands, which is what lets XLA overlap the
    table I/O with this batch's compute."""
    g = citation_graph(num_nodes=140, num_features=16, num_classes=4,
                       seed=7)
    spec = GNNSpec(op="gcn", d_in=16, d_hidden=16, num_classes=4,
                   num_layers=3)
    plan = R.build_plan(g, spec, R.GASConfig(
        num_parts=3, backend="jnp", prefetch_depth=1, epochs=1, seed=3))
    state = R.init_state(plan)
    batch = plan.batch_stack[0]
    fbatch = plan.batch_stack[1]
    queue = (R._prefetch_entry(state.histories, batch),)
    pf_step = R.make_prefetch_step_fn(plan, 1)
    jaxpr = jax.make_jaxpr(pf_step)(state, batch, fbatch, queue, plan.x,
                                    plan.y, plan.train_mask)

    n1 = g.num_nodes + 1
    table_shape = (n1, spec.d_hidden)

    hits = []          # (flat order index, primitive name)

    def walk(jxp):
        for eqn in jxp.eqns:
            if eqn.primitive.name in ("gather", "scatter") and any(
                    getattr(v.aval, "shape", None) == table_shape
                    for v in eqn.invars):
                hits.append(eqn.primitive.name)
            for v in eqn.params.values():
                for sub in (v if isinstance(v, (list, tuple)) else [v]):
                    if hasattr(sub, "jaxpr"):
                        walk(sub.jaxpr)

    walk(jaxpr.jaxpr)
    assert "gather" in hits and "scatter" in hits, hits
    first_gather = hits.index("gather")
    first_scatter = hits.index("scatter")
    assert first_gather < first_scatter, (
        f"halo pull (gather @ {first_gather}) must be dispatched before "
        f"the push (scatter @ {first_scatter}): {hits[:10]}")


# ---------------------------------------------------------------------------
# row-blocked dequant gather: ragged row counts vs the oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m", [1, 5, 8, 13, 32])
def test_gather_rows_dq_row_blocks_bitwise(m):
    """(8, bd)-tiled dequantizing `gather_rows` pads M up to the tile height and
    slices back; every ragged M must match `table[idx] * scales[idx]`
    bitwise."""
    from repro.kernels.gather import gather_rows

    rng = np.random.default_rng(m)
    n, d = 57, 128
    table = jnp.asarray(rng.integers(-127, 128, (n, d)).astype(np.int8))
    scales = jnp.asarray(rng.uniform(0.01, 2.0, n).astype(np.float32))
    idx = jnp.asarray(rng.integers(0, n, m).astype(np.int32))
    got = gather_rows(table, idx, scales, interpret=True)
    want = (jnp.take(table, idx, axis=0).astype(jnp.float32)
            * jnp.take(scales, idx)[:, None])
    assert got.shape == (m, d)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_history_prefetch_patch_matches_pull():
    """`prefetch` + intervening-push `patch_pulled` + `with_pulled` read
    == a fresh post-push `pull`, bitwise (the queue-patch induction the
    epoch pipeline rests on), f32 and int8."""
    rng = np.random.default_rng(2)
    n1, d, max_h, max_b = 41, 128, 7, 9
    for hd in ("f32", "int8"):
        store = H.HistoryStore.create(n1, [d], backend="jnp",
                                      history_dtype=hd)
        vals = jnp.asarray(rng.normal(size=(n1 - 1, d)).astype(np.float32))
        store = store.push(0, jnp.arange(n1 - 1, dtype=jnp.int32), vals,
                           jnp.ones((n1 - 1,), bool))
        halo = jnp.asarray(rng.choice(n1 - 1, max_h, replace=False)
                           .astype(np.int32))
        hmask = jnp.asarray(np.arange(max_h) < max_h - 2)
        pulled = store.prefetch(halo)
        # an intervening batch pushes rows, two of which are halo rows
        bnodes = jnp.concatenate([halo[:2], jnp.asarray(
            rng.choice(np.setdiff1d(np.arange(n1 - 1), np.asarray(halo)),
                       max_b - 2, replace=False).astype(np.int32))])
        bmask = jnp.ones((max_b,), bool)
        pvals = jnp.asarray(rng.normal(size=(max_b, d)).astype(np.float32))
        store2 = store.push(0, bnodes, pvals, bmask)
        patched = store2.patch_pulled(pulled, halo, hmask, bnodes, bmask,
                                      (pvals,))
        view = store2.with_pulled(patched)
        got = view.pull(0, jnp.arange(max_h, dtype=jnp.int32))
        want = store2.pull(0, halo)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want),
                                      err_msg=hd)
