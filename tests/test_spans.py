"""The program's spans and counters (`repro.utils.spans`) and where the
program puts them: nesting and parents, nothing kept while recording is
off, the `gas/gc` hook, the spans in a `jax.profiler` trace, the plan
build's spans and block counters, the epoch's spans with and without
prefetch and regrouping, and `dynamic.advance` reading its timings off
its spans.
"""
import gc
import time

import jax
import numpy as np
import pytest

from repro.core import delta as D
from repro.core import dynamic as DY
from repro.core import gas as G
from repro.core import runtime as R
from repro.data.graphs import citation_graph
from repro.gnn.model import GNNSpec
from repro.utils import spans

PLAN_SPANS = {"gas/plan", "gas/plan/partition", "gas/plan/coo",
              "gas/plan/emit", "gas/plan/stack", "gas/plan/device_put"}


@pytest.fixture
def recording():
    """Recording on for one test, and off again whatever happens."""
    spans.start()
    try:
        yield
    finally:
        spans.stop()


def _graph(n=300, seed=3):
    return citation_graph(num_nodes=n, num_features=16, num_classes=4,
                          seed=seed)


def _spec(layers=3):
    return GNNSpec("gcn", 16, 16, 4, layers)


def _children(rec, parent_name):
    """Names of the spans whose parent is the first `parent_name`."""
    idx = [r[0] for r in rec.spans].index(parent_name)
    return [r[0] for r in rec.spans if r[1] == idx]


def test_nesting_and_parents():
    spans.start()
    with spans.span("gas/a") as a:
        with spans.span("gas/a/b") as b:
            time.sleep(0.002)
        with spans.span("gas/a/c"):
            spans.count("gas/n", 2)
            spans.count("gas/n", 3)
    with spans.span("gas/d"):
        pass
    rec = spans.stop()
    assert [(n, p) for n, p, _, _ in rec.spans] == [
        ("gas/a", -1), ("gas/a/b", 0), ("gas/a/c", 0), ("gas/d", -1)]
    assert rec.counters == {"gas/n": 5}
    for _, _, s, e in rec.spans:
        assert 0 < s <= e
    sa, sb = rec.spans[0], rec.spans[1]
    assert sa[2] <= sb[2] and sb[3] <= sa[3]
    assert b.seconds >= 0.002 and a.seconds >= b.seconds
    assert rec.seconds("gas/a/b") == pytest.approx(b.seconds, abs=1e-4)


def test_recording_off_keeps_nothing_and_installs_no_hook():
    spans.stop()
    assert spans._on_gc not in gc.callbacks
    with spans.span("gas/off") as sp:
        spans.count("gas/off", 1)
        gc.collect()
    assert sp.seconds > 0
    assert spans._records is None and spans._counters == {}
    assert spans._on_gc not in gc.callbacks
    spans.start()
    assert gc.callbacks.count(spans._on_gc) == 1
    spans.start()                      # a second start installs no second
    assert gc.callbacks.count(spans._on_gc) == 1
    rec = spans.stop()
    assert rec.spans == [] and rec.counters == {}
    assert spans._on_gc not in gc.callbacks


def test_gc_of_the_oldest_generation_is_a_span(recording):
    with spans.span("gas/outer"):
        gc.collect(0)
        gc.collect(1)
        gc.collect()
    rec = spans.stop()
    gcs = [r for r in rec.spans if r[0] == "gas/gc"]
    assert len(gcs) == 1
    assert gcs[0][1] == 0 and gcs[0][3] >= gcs[0][2]


def test_a_span_lands_in_the_profilers_host_plane(tmp_path):
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(tmp_path))
    try:
        with spans.span("gas/probe") as sp:
            time.sleep(0.02)
    finally:
        jax.profiler.stop_trace()
    path = sorted(tmp_path.glob("plugins/profile/*/*.xplane.pb"))[-1]
    found = [e for p in ProfileData.from_file(str(path)).planes
             if p.name == "/host:CPU" for ln in p.lines for e in ln.events
             if e.name == "gas/probe"]
    assert len(found) == 1
    assert abs(found[0].duration_ns / 1e9 - sp.seconds) < 1e-3


def test_build_plan_records_every_plan_span(recording):
    g = _graph()
    cfg = R.GASConfig(num_parts=3, backend="interpret", partitioner="random")
    plan = R.build_plan(g, _spec(), cfg)
    rec = spans.stop()
    assert {r[0] for r in rec.spans} == PLAN_SPANS
    assert set(_children(rec, "gas/plan")) == PLAN_SPANS - {"gas/plan"}
    assert rec.seconds("gas/plan") >= sum(
        rec.seconds(n) for n in PLAN_SPANS - {"gas/plan"})
    leaves = jax.tree_util.tree_leaves(
        (plan.batch_stack, plan.x, plan.y, plan.train_mask,
         plan.eval_edges, plan.eval_w))
    assert rec.counters["gas/plan/upload_bytes"] == sum(
        a.nbytes for a in leaves)
    assert plan.batch_stack.forward.vals.nbytes < \
        rec.counters["gas/plan/upload_bytes"]


def test_build_plan_with_a_part_skips_the_partition_span(recording):
    g = _graph()
    part = np.arange(g.num_nodes) % 3
    R.build_plan(g, _spec(), R.GASConfig(num_parts=3, backend="jnp"),
                 part=part)
    rec = spans.stop()
    names = {r[0] for r in rec.spans}
    # the jnp backend builds no blocks: no emit, no stack, no fill counts
    assert names == {"gas/plan", "gas/plan/coo", "gas/plan/device_put"}
    assert "gas/plan/block_entries" not in rec.counters


@pytest.mark.parametrize("unit", [False, True])
def test_block_counters_equal_a_dense_scan(recording, unit):
    g = _graph()
    part = (np.arange(g.num_nodes) * 7 % 4).astype(np.int32)
    b = G.build_batches(g, part, build_blocks=True, unit_weights=unit)
    rec = spans.stop()
    fwd, tr = (b.unit, b.unit_transposed) if unit else (b.forward,
                                                      b.transposed)
    assert rec.counters["gas/plan/block_entries"] == \
        fwd.vals.size + tr.vals.size
    assert rec.counters["gas/plan/edge_entries"] == \
        np.count_nonzero(fwd.vals) + np.count_nonzero(tr.vals)
    assert 0 < rec.counters["gas/plan/edge_entries"] < \
        rec.counters["gas/plan/block_entries"]


@pytest.mark.parametrize("depth,clusters", [(0, 1), (1, 1), (0, 2), (1, 2)])
def test_train_epoch_spans(recording, depth, clusters):
    g = _graph(200)
    cfg = R.GASConfig(num_parts=4, backend="jnp", partitioner="random",
                      prefetch_depth=depth, clusters_per_batch=clusters)
    plan = R.build_plan(g, _spec(2), cfg)
    state = R.init_state(plan)
    spans.start()
    for e in range(2):
        state, metrics = R.train_epoch(plan, state, e)
    rec = spans.stop()
    tops = [r for r in rec.spans if r[1] == -1]
    assert [r[0] for r in tops] == ["gas/epoch"] * 2
    want = ["gas/epoch/dispatch", "gas/epoch/wait", "gas/epoch/readback"]
    idx = [i for i, r in enumerate(rec.spans) if r[0] == "gas/epoch"]
    first = [r[0] for r in rec.spans if r[1] == idx[0]]
    second = [r[0] for r in rec.spans if r[1] == idx[1]]
    assert first == want
    # the second epoch regroups the clusters before it dispatches
    assert second == (["gas/epoch/regroup"] * (clusters > 1)) + want
    assert np.isfinite(metrics["loss"])


def test_advance_reads_its_timings_off_its_spans(recording):
    g = citation_graph(num_nodes=160, num_features=8, num_classes=3,
                       seed=0)
    spec = GNNSpec(op="gcn", d_in=8, d_hidden=8, num_classes=3,
                   num_layers=3)
    dcfg = DY.DynamicGASConfig(
        base=R.GASConfig(num_parts=4, backend="jnp", seed=0),
        cold_rebuild_frac=1.01)
    plan = DY.build_dynamic_plan(g, spec, dcfg)
    state = R.init_state(plan)
    d = D.random_delta(g, edge_churn=0.02, nodes_add=3, new_degree=3,
                       feat_frac=0.02, seed=7)
    # collect the earlier tests' garbage first, so that no collection of
    # the oldest generation (a `gas/gc` span) falls inside `advance`
    gc.collect()
    spans.start()
    _, _, info = DY.advance(plan, state, d, dcfg)
    rec = spans.stop()
    assert not info.cold, info.reason
    assert [r[:2] for r in rec.spans] == [
        ("gas/advance/partition", -1), ("gas/advance/batches", -1),
        ("gas/advance/repush", -1)]
    for field, name in (("partition_s", "gas/advance/partition"),
                        ("batches_s", "gas/advance/batches"),
                        ("repush_s", "gas/advance/repush")):
        assert getattr(info, field) == pytest.approx(rec.seconds(name),
                                                     abs=1e-6)


@pytest.mark.parametrize("budget,counted,absent", [
    (None, "gas/agg/panel_calls", "gas/agg/per_block_calls"),
    (1, "gas/agg/per_block_calls", "gas/agg/panel_calls")])
def test_fused_epoch_counts_its_staging_path(recording, monkeypatch, budget,
                                             counted, absent):
    """Each traced `ops.gas_aggregate` call counts the staging path its
    `gather_spmm` compiles: the VMEM panel at these shapes, the per-block
    path once the budget is too small for any panel."""
    from repro.kernels import fused
    if budget is not None:
        monkeypatch.setattr(fused, "VMEM_BUDGET", budget)
    jax.clear_caches()              # trace again: counters count traces
    g = _graph(200)
    cfg = R.GASConfig(num_parts=4, backend="interpret", partitioner="random",
                      fuse_halo=True)
    plan = R.build_plan(g, _spec(3), cfg)
    state = R.init_state(plan)
    spans.start()
    try:
        _, metrics = R.train_epoch(plan, state, 0)
    finally:
        rec = spans.stop()
        jax.clear_caches()
    # one fused aggregation per layer after the first, per trace
    assert rec.counters[counted] >= 2
    assert absent not in rec.counters
    assert np.isfinite(metrics["loss"])


@pytest.mark.parametrize("quantized", [False, True])
@pytest.mark.parametrize("M", [5, 700, 2176])
def test_push_counts_its_chunks(recording, quantized, M):
    """Tracing a kernel-path push counts the grid steps its chunked
    `scatter_rows` compiles, ceil(M / C)."""
    import jax.numpy as jnp
    from repro.kernels import ops, scatter
    N, D = 3001, 256
    idx = jax.ShapeDtypeStruct((M,), jnp.int32)
    vals = jax.ShapeDtypeStruct((M, D), jnp.float32)
    mask = jax.ShapeDtypeStruct((M,), jnp.bool_)
    if quantized:
        jax.eval_shape(
            lambda t, s, i, v, m: ops.push_rows_q(
                t, s, i, v, m, backend="interpret", scratch_last_row=True),
            jax.ShapeDtypeStruct((N, D), jnp.int8),
            jax.ShapeDtypeStruct((N,), jnp.float32), idx, vals, mask)
    else:
        jax.eval_shape(
            lambda t, i, v, m: ops.push_rows(
                t, i, v, m, backend="interpret", scratch_last_row=True),
            jax.ShapeDtypeStruct((N, D), jnp.float32), idx, vals, mask)
    rec = spans.stop()
    c = scatter.chunk_rows(M, D)
    assert c % 8 == 0 and c <= scatter.CHUNK
    assert rec.counters == {"gas/push/chunks": -(-M // c)}
    assert -(-M // c) == -(-M // scatter.CHUNK)
