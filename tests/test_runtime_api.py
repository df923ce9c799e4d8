"""Typed GAS runtime API (core/batch.py, core/history.py HistoryStore,
core/runtime.py plan/state/step):

 - GASBatch pytree stability: flatten/unflatten idempotent, aux data
   hashable, NO re-trace across same-shaped batches, re-trace when a
   block family appears;
 - the executors reject non-GASBatch inputs (the one-release legacy
   dict shim `core.gas.coerce_batch` is removed, as scheduled);
 - HistoryStore: bound backend, pull/push/tick/bytes semantics match the
   reference free functions;
 - GASState checkpoint round-trip: save -> restore -> one more train_step
   bit-identical to uninterrupted training;
 - plan/state/step surface: GASConfig consolidates the toggles and
   refuses the removed per-step epoch loop (`fused_epoch=False`).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import gas as G
from repro.core import history as H
from repro.core import runtime as R
from repro.core.batch import GASBatch, ensure_batch
from repro.data.graphs import citation_graph
from repro.gnn.model import GNNSpec
from repro.train.checkpoint import load_gas_state, save_gas_state


def _graph_and_batches(n=200, parts=3, seed=5, build_blocks=False):
    g = citation_graph(num_nodes=n, num_features=16, num_classes=4,
                       seed=seed)
    part = np.random.default_rng(seed).integers(0, parts, n)
    part = np.unique(part, return_inverse=True)[1].astype(np.int32)
    return g, G.build_batches(g, part, build_blocks=build_blocks)


# ---------------------------------------------------------------------------
# GASBatch pytree contract
# ---------------------------------------------------------------------------

def test_gasbatch_flatten_unflatten_idempotent():
    _, b = _graph_and_batches(build_blocks=True)
    leaves, treedef = jax.tree_util.tree_flatten(b)
    b2 = jax.tree_util.tree_unflatten(treedef, leaves)
    assert isinstance(b2, GASBatch)
    assert (b2.num_batches, b2.max_b, b2.max_h, b2.max_e, b2.bn) == \
        (b.num_batches, b.max_b, b.max_h, b.max_e, b.bn)
    leaves2, treedef2 = jax.tree_util.tree_flatten(b2)
    assert treedef2 == treedef
    for a, c in zip(leaves, leaves2):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(c))


def test_gasbatch_aux_data_hashable_and_treedef_typed():
    _, b_plain = _graph_and_batches(build_blocks=False)
    _, b_blocks = _graph_and_batches(build_blocks=True)
    td_plain = jax.tree_util.tree_structure(b_plain)
    td_blocks = jax.tree_util.tree_structure(b_blocks)
    hash(td_plain), hash(td_blocks)          # aux must be hashable
    # presence of a block family is a *structural* (re-trace) difference
    assert td_plain != td_blocks
    # typed gates replace `"blk_vals_t" in batch`
    assert b_plain.transposed is None and b_blocks.transposed is not None
    assert b_blocks.blocks is not None and len(b_blocks.blocks) == 4


def test_gasbatch_no_retrace_across_same_shaped_batches():
    _, b = _graph_and_batches(build_blocks=True)
    stack = b.device()
    traces = []

    @jax.jit
    def f(batch):
        traces.append(1)
        return jnp.sum(batch.edge_w) + jnp.sum(batch.batch_mask)

    outs = [f(stack[i]) for i in range(b.num_batches)]
    assert len(traces) == 1, "same-shaped batches must share one trace"
    assert len(outs) == b.num_batches


def test_gasbatch_scan_and_getitem_slice():
    _, b = _graph_and_batches(build_blocks=True)
    stack = b.device()
    one = stack[1]
    assert one.batch_nodes.shape == (b.max_b,)
    assert one.forward.vals.shape == stack.forward.vals.shape[1:]

    def body(carry, batch):
        return carry + jnp.sum(batch.edge_w), jnp.sum(batch.batch_mask)

    total, per = jax.lax.scan(body, jnp.zeros(()), stack)
    np.testing.assert_allclose(float(total), float(np.sum(b.edge_w)),
                               rtol=1e-5)
    assert per.shape == (b.num_batches,)


def test_gasbatch_structural_bytes():
    _, b = _graph_and_batches(build_blocks=True)
    sb = b.structural_bytes()
    assert sb["blocks_forward"] == b.forward.bytes() > 0
    assert sb["blocks_unit"] == 0
    assert sb["total"] == sum(v for k, v in sb.items() if k != "total")
    _, bp = _graph_and_batches(build_blocks=False)
    assert bp.structural_bytes()["blocks_forward"] == 0


# ---------------------------------------------------------------------------
# Typed-batch guard (legacy dict shim removed)
# ---------------------------------------------------------------------------

def test_executors_reject_non_gasbatch():
    """The one-release `coerce_batch` dict shim is gone: dicts and other
    garbage raise TypeError instead of being silently converted."""
    assert not hasattr(G, "coerce_batch")
    assert not hasattr(GASBatch, "from_legacy")
    with pytest.raises(TypeError):
        ensure_batch([1, 2, 3])
    with pytest.raises(TypeError):
        ensure_batch({"batch_nodes": np.zeros(3)})
    _, b = _graph_and_batches()
    assert ensure_batch(b) is b


# ---------------------------------------------------------------------------
# HistoryStore
# ---------------------------------------------------------------------------

def test_history_store_matches_reference_semantics():
    # f32 pinned: this compares against the exact-storage reference free
    # functions (quantized semantics: tests/test_quantized_history.py)
    store = H.HistoryStore.create(11, [4, 4], backend="jnp",
                                  history_dtype="f32")
    assert store.backend == "jnp" and store.num_layers == 2
    idx = jnp.array([2, 5, 7, 11], jnp.int32)
    mask = jnp.array([True, True, True, False])
    vals = jnp.arange(16.0).reshape(4, 4)
    store = store.push(0, idx, vals, mask)
    ref = H.push(jnp.zeros((11, 4)), idx, vals, mask)
    np.testing.assert_array_equal(np.asarray(store.tables[0])[:-1],
                                  np.asarray(ref)[:-1])
    np.testing.assert_array_equal(np.asarray(store.pull(0, idx[:3])),
                                  np.asarray(vals[:3]))
    store = store.tick(idx, mask)
    age = np.asarray(store.age)
    assert age[2] == 0 and age[3] == 1       # pushed reset, others aged
    assert store.bytes() == 2 * 11 * 4 * 4
    assert store.bytes_per_table() == [11 * 4 * 4] * 2
    # the store is a pytree: backend survives a tree_map, tables are leaves
    doubled = jax.tree_util.tree_map(lambda a: a * 2, store)
    assert doubled.backend == "jnp"
    np.testing.assert_array_equal(np.asarray(doubled.tables[0]),
                                  np.asarray(store.tables[0]) * 2)


def test_history_store_binds_backend_once():
    store = H.HistoryStore.create(8, [4], backend="interpret")
    assert store.backend == "interpret"
    # structural difference: stores bound to different backends do not
    # share a treedef (so a jitted step cannot silently switch paths)
    other = H.HistoryStore.create(8, [4], backend="jnp")
    assert jax.tree_util.tree_structure(store) != \
        jax.tree_util.tree_structure(other)


# ---------------------------------------------------------------------------
# Plan / state / step + checkpoint round-trip
# ---------------------------------------------------------------------------

def _small_plan(backend="jnp", **kw):
    g = citation_graph(num_nodes=150, num_features=16, num_classes=4,
                       seed=11)
    spec = GNNSpec(op="gcn", d_in=16, d_hidden=16, num_classes=4,
                   num_layers=3)
    cfg = R.GASConfig(num_parts=3, backend=backend, epochs=2, seed=0, **kw)
    plan = R.build_plan(g, spec, cfg)
    return plan, R.init_state(plan)


def test_gas_state_checkpoint_roundtrip_bit_identical(tmp_path):
    """save -> restore -> one more train_step must be bit-identical to
    uninterrupted training (params, opt moments, histories, age, rng)."""
    plan, state = _small_plan()
    state, _ = R.train_epoch(plan, state, 0)

    path = str(tmp_path / "gas_state.npz")
    save_gas_state(path, state, step=1)
    restored, step = load_gas_state(path, R.init_state(plan))
    assert step == 1

    def leaf_np(a):   # typed PRNG keys need key_data before comparison
        if jax.dtypes.issubdtype(a.dtype, jax.dtypes.prng_key):
            a = jax.random.key_data(a)
        return np.asarray(a)

    batch = plan.batch_stack[0]
    cont, m_cont = R.train_step(plan, state, batch)
    resumed, m_res = R.train_step(plan, restored, batch)
    for a, c in zip(jax.tree_util.tree_leaves(cont),
                    jax.tree_util.tree_leaves(resumed)):
        np.testing.assert_array_equal(leaf_np(a), leaf_np(c))
    np.testing.assert_array_equal(np.asarray(m_cont["loss"]),
                                  np.asarray(m_res["loss"]))


def test_build_plan_reuses_a_given_partition():
    """A partition passed to `build_plan` replaces partitioning: a plan
    built from another plan's `part` trains bit-identically to it, and a
    partition of the wrong length is refused."""
    plan, state = _small_plan()
    again = R.build_plan(plan.graph, plan.spec, plan.config, part=plan.part)
    np.testing.assert_array_equal(again.part, plan.part)
    _, m = R.train_step(plan, state, plan.batch(0))
    _, m_again = R.train_step(again, R.init_state(again), again.batch(0))
    np.testing.assert_array_equal(np.asarray(m["loss"]),
                                  np.asarray(m_again["loss"]))
    with pytest.raises(ValueError, match="part must have shape"):
        R.build_plan(plan.graph, plan.spec, plan.config,
                     part=plan.part[:-1])


def test_gasconfig_consolidates_toggles():
    plan, state = _small_plan(fuse_halo=False, use_history=False)
    assert plan.config.fused_epoch and not plan.config.fuse_halo
    state, m = R.train_epoch(plan, state, 0)   # single scan dispatch
    assert np.isfinite(m["loss"])
    # the per-step epoch loop is gone: asking for it is refused
    with pytest.raises(ValueError, match="train_step"):
        R.GASConfig(num_parts=2, fused_epoch=False)
    # the base class's checks still run
    with pytest.raises(ValueError):
        R.GASConfig(num_parts=2, backend="cuda")


def test_trainer_tcfg_not_shared_between_instances():
    """The old `tcfg: TrainConfig = TrainConfig()` default was one shared
    module-import-time instance; mutations leaked across trainers."""
    import inspect

    from repro.train.baselines import FullBatchTrainer
    default = inspect.signature(
        FullBatchTrainer.__init__).parameters["tcfg"].default
    assert default is None
    g = citation_graph(num_nodes=120, num_features=8, num_classes=3, seed=1)
    spec = GNNSpec(op="gcn", d_in=8, d_hidden=8, num_classes=3,
                   num_layers=2)
    a = FullBatchTrainer(g, spec)
    b = FullBatchTrainer(g, spec)
    assert a.tcfg is not b.tcfg
    a.tcfg.lr = 123.0
    assert b.tcfg.lr != 123.0
