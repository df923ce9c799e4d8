"""Sharded GAS (`GASConfig(ranks=R)`, `core/dist_gas.py`): the runtime's
epoch under `shard_map` on R devices, against a plain `jax.numpy`
reference of the same supersteps (`bench/ops/gcn_dist.py`).

The multi-device cases run in a child process on
`--xla_force_host_platform_device_count=4` (the test process has one CPU
device) with the interpreted Pallas kernels; the routing, refusal and
`ranks=1` cases run here."""
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import dist_gas as DG
from repro.core import gas as G
from repro.core import runtime as R
from repro.core.partition import metis_like_partition
from repro.data.graphs import citation_graph
from repro.gnn.model import GNNSpec

ROOT = os.path.join(os.path.dirname(__file__), "..")
SRC = os.path.join(ROOT, "src")

# Program against reference after two epochs of 4 supersteps: both sides
# are float32; the program sums each aggregation over 128 x 128 blocks,
# the reference over edges, so they part at the last bit (measured
# 1e-7 relative) and Adam carries that on. 1e-5 leaves 100x room; the
# planted faults below read 1e-2 (fresh reads) and 1e-1 (no exchange).
TOL = 1e-5
# int8: a last-bit difference before quantizing may move one code by a
# step, 1/127 of its row's largest entry (measured 7e-8 all the same)
TOL_INT8_TABLES = 1e-4


def run_child(code: str) -> dict:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join([SRC, ROOT])
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       env=env, capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-4000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


SETUP = """
    import json
    import jax, jax.numpy as jnp, numpy as np
    from repro.core import dist_gas as DG, runtime as R
    from repro.data.graphs import citation_graph
    from repro.gnn.model import GNNSpec, init_gnn

    def sharded(hd, num_parts=16, nodes=2000, lr=0.01, backend="interpret"):
        g = citation_graph(num_nodes=nodes, num_features=16, num_classes=4,
                           seed=5)
        spec = GNNSpec(op="gcn", d_in=16, d_hidden=32, num_classes=4,
                       num_layers=3)
        cfg = R.GASConfig(num_parts=num_parts, ranks=4, backend=backend,
                          history_dtype=hd, lr=lr, weight_decay=0.0,
                          grad_clip=float("inf"), seed=7)
        plan = R.build_plan(g, spec, cfg)
        params = jax.tree_util.tree_map(
            np.asarray, init_gnn(jax.random.key(3), spec))
        state = R.init_state(plan).replace(
            params=jax.device_put(params, DG.replicated(plan.mesh)))
        return g, spec, cfg, plan, params, state

    def node_tables(plan, store):
        out = []
        for i, t in enumerate(store.tables):
            t = np.asarray(t)[plan.node_rows].astype(np.float32)
            if store.scales is not None:
                t = t * np.asarray(store.scales[i])[plan.node_rows][:, None]
            out.append(t)
        return out
"""


@pytest.mark.slow
@pytest.mark.parametrize("hd,fault", [("f32", None), ("int8", None),
                                      ("f32", "fresh_reads"),
                                      ("f32", "drop_exchange")])
def test_sharded_epochs_match_reference(hd, fault):
    """Two epochs of `train_epoch` with `GASConfig(ranks=4)` (16 parts, 4
    supersteps an epoch, interpreted kernels) against the plain reference
    of the same supersteps: per-superstep losses, Adam's first moment
    after epoch 1, parameters and history tables. The fault cases are a
    reference whose parts read the pushes of the parts before them in the
    same superstep, and one that reads every row another rank owns as
    zeros (the exchange left out): each must fail the same comparison."""
    out = run_child(SETUP + f"""
    from bench.ops import gcn, gcn_dist
    g, spec, cfg, plan, params, state = sharded({hd!r})
    losses, first_m = [], None
    for e in range(2):
        state, m = R.train_epoch(plan, state, e)
        losses.append(float(m["loss"]))
        if first_m is None:
            first_m = jax.tree_util.tree_map(np.asarray, state.opt_state.m)
    orders = [np.random.default_rng(cfg.seed * 1000 + e).permutation(16)
              for e in range(2)]
    ref = gcn_dist.train(
        params, g.x, g.y.astype(np.int32), g.train_mask,
        gcn.part_edges(g.indptr, g.indices, plan.part), orders, [32, 32],
        {{"lr": 0.01, "weight_decay": 0.0, "grad_clip": None}}, 4,
        qmax=127 if {hd!r} == "int8" else 0,
        fresh_reads={fault == "fresh_reads"},
        drop_exchange={fault == "drop_exchange"})

    def rel(a, b):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))

    leaves = jax.tree_util.tree_leaves
    print(json.dumps({{
        "loss": max(abs(a - float(np.mean(b))) / abs(float(np.mean(b)))
                    for a, b in zip(losses, ref["batch_loss"])),
        "m": max(rel(a, b) for a, b in zip(leaves(first_m),
                                           leaves(ref["m"]))),
        "params": max(rel(a, b) for a, b in zip(leaves(state.params),
                                                leaves(ref["params"]))),
        "tables": max(rel(a, b) for a, b in zip(
            node_tables(plan, state.histories), ref["tables"])),
        "supersteps": len(ref["batch_loss"][0])}}))
    """)
    assert out["supersteps"] == 4
    tol = {"loss": TOL, "m": TOL, "params": TOL,
           "tables": TOL_INT8_TABLES if hd == "int8" else TOL}
    over = {k: out[k] for k in tol if out[k] > tol[k]}
    if fault:
        assert over, out
    else:
        assert not over, out


@pytest.mark.slow
def test_supersteps_converge_to_exact():
    """With one part a rank (S = 1, every superstep trains every node)
    and fixed parameters (lr 0), the history tables reach the exact
    full-batch embeddings by superstep num_layers - 1, through the kernel
    path: table l holds layer l+1's output once layer l's table is
    exact (paper guarantee #4, sharded)."""
    out = run_child(SETUP + """
    import dataclasses
    from repro.gnn.model import full_forward
    from repro.core.gas import gcn_edge_weights
    g, spec, cfg, plan, params, state = sharded("f32", num_parts=4,
                                                nodes=600, lr=0.0)
    dst, src, w = gcn_edge_weights(g)
    exact = []
    for k in (1, 2):
        sk = dataclasses.replace(spec, num_layers=k)
        pk = {"layers": params["layers"][:k]}
        exact.append(np.maximum(np.asarray(full_forward(
            pk, sk, jnp.asarray(g.x), (jnp.asarray(dst), jnp.asarray(src)),
            jnp.asarray(w), g.num_nodes)), 0.0))
    errs = []
    for e in range(2):
        state, _ = R.train_epoch(plan, state, e)
        tabs = node_tables(plan, state.histories)
        errs.append([float(np.abs(t - x).max()) for t, x in zip(tabs, exact)])
    same = all(np.array_equal(np.asarray(a), b) for a, b in zip(
        jax.tree_util.tree_leaves(state.params),
        jax.tree_util.tree_leaves(params)))
    print(json.dumps({"errs": errs, "params_fixed": same,
                      "supersteps": plan.batch_stack.slots.shape[1]}))
    """)
    errs = out["errs"]
    assert out["supersteps"] == 1 and out["params_fixed"]
    assert errs[0][0] < 1e-4 and errs[0][1] > 1e-2, errs
    assert max(errs[1]) < 1e-4, errs


@pytest.mark.slow
def test_sharded_store_layout_and_kernel_path():
    """`init_state` row-shards f32 and int8 stores over the plan's mesh
    (one block of rows and its sentinel a device; int8 scales alongside),
    and the sharded epoch's jaxpr runs the one-chip kernels inside
    `shard_map`: the fused `gather_spmm`, `bcsr_spmm` forward and
    transposed backward, `gather_rows` and the `scatter_rows` push, with
    the halo exchange by `ppermute` and no edge-indexed gather or scatter
    (the jnp backend's segment sums trip the same detector). An int8
    store's exchange carries its rows as stored: every permuted operand
    is int8 rows [C, d] or their f32 scales [C], never dequantized f32
    rows."""
    out = run_child(SETUP + """
    from collections import Counter

    def eqns(j):
        for e in j.eqns:
            yield e
            for v in e.params.values():
                for sub in (v if isinstance(v, (list, tuple)) else [v]):
                    if hasattr(sub, "eqns"):
                        yield from eqns(sub)
                    elif hasattr(getattr(sub, "jaxpr", None), "eqns"):
                        yield from eqns(sub.jaxpr)

    res = {}
    for hd in ("f32", "int8"):
        g, spec, cfg, plan, params, state = sharded(hd, nodes=600,
                                                    num_parts=8)
        st = state.histories
        rows = plan.batch_stack.rows
        res[hd] = {
            "dtype": str(st.tables[0].dtype),
            "shape": list(st.tables[0].shape),
            "shards": sorted({tuple(s.data.shape)
                              for s in st.tables[0].addressable_shards}),
            "scales": None if st.scales is None else
            [list(s.shape) for s in st.scales],
            "rows": rows}
    for backend, hd in (("interpret", "f32"), ("jnp", "f32"),
                        ("interpret", "int8")):
        g, spec, cfg, plan, params, state = sharded(hd, nodes=600,
                                                    num_parts=8,
                                                    backend=backend)
        fn = DG.make_epoch_fn(plan.spec, plan.config, plan.backend,
                              plan.mesh)
        jx = jax.make_jaxpr(fn)(state, plan.batch_stack, jnp.arange(8),
                                plan.x, plan.y, plan.train_mask).jaxpr
        jits, edge = Counter(), []
        max_e = plan.batches.max_e
        for e in eqns(jx):
            name = e.primitive.name
            if name in ("jit", "pjit"):
                jits[e.params["name"]] += 1
            jits[name] += 0
            if any(t in name for t in ("gather", "scatter", "segment")):
                for v in list(e.invars) + list(e.outvars):
                    shape = getattr(getattr(v, "aval", None), "shape", ())
                    if len(shape) >= 1 and shape[0] == max_e:
                        edge.append(name)
        res[f"{backend}-{hd}"] = {
            "jits": dict(jits), "edge": edge,
            "ppermute": [[str(v.aval.dtype), list(v.aval.shape)]
                         for e in eqns(jx) if e.primitive.name == "ppermute"
                         for v in e.invars],
            "C": plan.batch_stack.send_idx.shape[-1],
            "shard_map": sum(1 for e in eqns(jx)
                             if e.primitive.name == "shard_map")}
    print(json.dumps(res))
    """)
    for hd in ("f32", "int8"):
        r = out[hd]
        n = 4 * (r["rows"] + 1)
        assert r["dtype"] == {"f32": "float32", "int8": "int8"}[hd]
        assert r["shape"] == [n, 32]
        assert r["shards"] == [[r["rows"] + 1, 32]]
        assert r["scales"] == (None if hd == "f32" else [[n], [n]])
    ref = out["jnp-f32"]
    for hd in ("f32", "int8"):
        ker = out[f"interpret-{hd}"]
        assert ker["shard_map"] == 1
        jits = ker["jits"]
        assert jits.get("gather_spmm", 0) == 2   # layers 2 and 3 forward
        assert jits.get("bcsr_spmm", 0) == 3     # layer 1, and transposed
        #                                          backward of layers 2, 3
        assert jits.get("scatter_rows", 0) == 2  # one push a hidden layer
        assert not ker["edge"], ker["edge"]
    assert ref["edge"], "detector found no segment sum on the jnp path"
    assert out["interpret-f32"]["jits"].get("gather_rows", 0) >= 2
    # two hidden layers, three peers: 6 row permutes, and with int8 as
    # many of scales
    C = out["interpret-f32"]["C"]
    assert out["interpret-f32"]["ppermute"] == [["float32", [C, 32]]] * 6
    C = out["interpret-int8"]["C"]
    wire = sorted((dt, tuple(shape))
                  for dt, shape in out["interpret-int8"]["ppermute"])
    assert wire == [("float32", (C,))] * 6 + [("int8", (C, 32))] * 6, wire


@pytest.mark.slow
def test_int8_halo_view_is_the_owners_rows_bitwise():
    """Inside `shard_map`, each rank's int8 halo view of every part (own
    rows gathered, the others' permuted) holds the owners' stored rows
    and scales bit for bit, so dequantizing at the receiver gives
    exactly `dequantize_rows` of the owners' rows; padded slots read the
    zero row with scale 1."""
    out = run_child(SETUP + """
    from jax.sharding import PartitionSpec as P
    from repro.core import history as H
    g, spec, cfg, plan, params, state = sharded("int8", nodes=600,
                                                num_parts=8)
    n, d = state.histories.tables[0].shape
    vals = np.random.default_rng(0).normal(size=(n, d)).astype(np.float32)
    q, s = H.quantize_rows(jnp.asarray(vals))
    q_dev = jax.device_put(q, DG.sharded(plan.mesh))
    s_dev = jax.device_put(s, DG.sharded(plan.mesh))
    per_rank = DG.rank_orders(np.random.default_rng(1).permutation(8), 4)
    S = per_rank.shape[1]

    def body(q, s, dist, per_rank):
        dist = jax.tree_util.tree_map(lambda a: a[0], dist)
        me = jax.lax.axis_index(DG.AXIS)
        views = [DG.halo_view(q, s, dist, me, per_rank, t, per_rank[me, t],
                              plan.backend) for t in range(S)]
        rows = jnp.stack([v[0] for v in views])[None]
        scl = jnp.stack([v[1] for v in views])[None]
        deq = jnp.stack([H.dequantize_rows(*v) for v in views])[None]
        return rows, scl, deq

    rows, scl, deq = jax.shard_map(
        body, mesh=plan.mesh, in_specs=(P(DG.AXIS), P(DG.AXIS), P(DG.AXIS),
                                        P()),
        out_specs=P(DG.AXIS), check_vma=False)(
            q_dev, s_dev, plan.batch_stack, jnp.asarray(per_rank))
    rows, scl, deq = map(np.asarray, (rows, scl, deq))
    qn, sn = np.asarray(q), np.asarray(s)
    want_deq = np.asarray(H.dequantize_rows(q, s))
    hn = np.asarray(plan.batch_stack.batch.halo_nodes)
    hm = np.asarray(plan.batch_stack.batch.halo_mask)
    ok, slots = True, 0
    for r in range(4):
        for t in range(S):
            j = per_rank[r, t]
            m = hm[r, j]
            src = plan.node_rows[hn[r, j][m]]
            ok &= bool(np.array_equal(rows[r, t][m], qn[src]))
            ok &= bool(np.array_equal(scl[r, t][m], sn[src]))
            ok &= bool(np.array_equal(deq[r, t][m], want_deq[src]))
            ok &= bool((rows[r, t][~m] == 0).all()
                       and (scl[r, t][~m] == 1).all())
            slots += int(m.sum())
    print(json.dumps({"ok": ok, "dtype": str(rows.dtype), "slots": slots}))
    """)
    assert out["dtype"] == "int8" and out["slots"] > 0
    assert out["ok"]


def _routing_case(ranks, parts, nodes=400, seed=3):
    g = citation_graph(num_nodes=nodes, num_features=8, num_classes=3,
                       seed=seed)
    part = metis_like_partition(g.indptr, g.indices, parts, seed=0)
    batches = G.build_batches(g, part, build_blocks=False)
    dist, node_rows, counts = DG.build_dist(part, batches, ranks)
    return g, part, batches, dist, node_rows, counts


@pytest.mark.parametrize("ranks,parts", [(4, 8), (2, 6), (4, 4)])
def test_routing_delivers_every_halo_row_from_its_owner_once(ranks, parts):
    """Replays each superstep's exchange on the host over a table whose
    rows hold their node id: for any pairing of the ranks' parts, every
    valid halo slot reads its own node's row, sent by the rank that owns
    it, and each sent row lands in exactly one slot."""
    g, part, batches, dist, node_rows, counts = _routing_case(ranks, parts)
    S = parts // ranks
    rows = dist.rows
    owner = part // S
    table = np.full(ranks * (rows + 1), -1, np.int64)
    table[node_rows] = np.arange(g.num_nodes)
    shards = table.reshape(ranks, rows + 1)
    L, C = dist.loc_idx.shape[-1], dist.send_idx.shape[-1]
    rng = np.random.default_rng(0)
    remote = local = 0
    for _ in range(4):
        per_rank = DG.rank_orders(rng.permutation(parts), ranks)
        for s in range(S):
            for r in range(ranks):
                j = per_rank[r, s]
                buf = [shards[r][dist.loc_idx[r, j]]]
                for k in range(1, ranks):
                    q = (r - k) % ranks
                    buf.append(shards[q][dist.send_idx[q, r, j]])
                buf = np.concatenate(buf + [[-2]])
                assert len(buf) == L + (ranks - 1) * C + 1
                pos = dist.src_pos[r, j]
                hm = dist.batch.halo_mask[r, j]
                hn = dist.batch.halo_nodes[r, j]
                np.testing.assert_array_equal(buf[pos[hm]], hn[hm])
                assert (buf[pos[~hm]] == -2).all()
                valid = pos[hm]
                assert len(np.unique(valid)) == len(valid)
                from_peer = valid >= L
                k_of = (valid[from_peer] - L) // C + 1
                np.testing.assert_array_equal(
                    (r - k_of) % ranks, owner[hn[hm][from_peer]])
                assert (owner[hn[hm][~from_peer]] == r).all()
                remote += int(from_peer.sum())
                local += int((~from_peer).sum())
    # each part is taken once an epoch: four epochs' rows, four times the
    # plan's per-epoch counts
    assert (remote, local) == (4 * counts["remote"], 4 * counts["local"])
    np.testing.assert_array_equal(
        np.sort(node_rows // (rows + 1)), np.sort(owner))
    assert len(np.unique(node_rows)) == g.num_nodes


def test_rank_orders_follow_the_epoch_permutation():
    """Each rank takes its own parts in the order the epoch's permutation
    of all parts lists them, on the host and inside jit alike."""
    order = np.array([5, 0, 7, 2, 1, 6, 3, 4])
    want = np.array([[0, 1], [0, 1], [1, 0], [1, 0]])
    np.testing.assert_array_equal(DG.rank_orders(order, 4), want)
    got = jax.jit(lambda o: DG.rank_orders(o, 4))(jnp.asarray(order))
    np.testing.assert_array_equal(np.asarray(got), want)


@pytest.mark.parametrize("kw,err", [
    ({"num_parts": 6}, ValueError),
    ({"history_dtype": "vq"}, NotImplementedError),
    ({"prefetch_depth": 1}, NotImplementedError),
    ({"clusters_per_batch": 2}, NotImplementedError),
    ({"halo_age_decay": 0.5}, NotImplementedError)])
def test_sharded_plan_refuses_what_it_has_no_form_of(kw, err):
    g = citation_graph(num_nodes=80, num_features=8, num_classes=3, seed=3)
    spec = GNNSpec(op="gcn", d_in=8, d_hidden=8, num_classes=3,
                   num_layers=3)
    cfg = R.GASConfig(**{"num_parts": 8, "ranks": 4, "backend": "jnp",
                         **kw})
    with pytest.raises(err):
        R.build_plan(g, spec, cfg)


def test_rank_one_is_the_one_chip_path():
    """`ranks=1` (the default) builds no mesh and trains bit for bit as a
    config that does not name it."""
    g = citation_graph(num_nodes=150, num_features=8, num_classes=3, seed=3)
    spec = GNNSpec(op="gcn", d_in=8, d_hidden=16, num_classes=3,
                   num_layers=3)
    out = []
    for cfg in (R.GASConfig(num_parts=4, backend="interpret", seed=2),
                R.GASConfig(num_parts=4, backend="interpret", seed=2,
                            ranks=1)):
        plan = R.build_plan(g, spec, cfg)
        assert plan.mesh is None and plan.node_rows is None
        state = R.init_state(plan)
        for e in range(2):
            state, m = R.train_epoch(plan, state, e)
        out.append((jax.tree_util.tree_leaves(state), m))
    for a, b in zip(out[0][0], out[1][0]):
        if jnp.issubdtype(a.dtype, jax.dtypes.prng_key):
            a, b = jax.random.key_data(a), jax.random.key_data(b)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert out[0][1] == out[1][1]
