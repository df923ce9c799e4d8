"""Distributed GAS (shard_map + ppermute halo exchange) correctness:
with fixed params, supersteps converge to the exact full-batch embeddings
(paper guarantee #4, distributed)."""
import os
import subprocess
import sys
import textwrap

import pytest

pytestmark = pytest.mark.slow

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def test_dist_gas_converges_to_exact():
    code = textwrap.dedent("""
        import os
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.core import dist_gas as DG
        from repro.core.gas import gcn_edge_weights
        from repro.core.partition import metis_like_partition
        from repro.data.graphs import citation_graph
        from repro.gnn.model import GNNSpec, full_forward, init_gnn

        ranks = 4
        mesh = jax.make_mesh((ranks,), ("data",),
                             axis_types=(jax.sharding.AxisType.Auto,))
        g = citation_graph(num_nodes=600, num_features=16, num_classes=4,
                           seed=9)
        part = metis_like_partition(g.indptr, g.indices, ranks, seed=0)
        structs = DG.build_dist_structs(g, part)
        spec = GNNSpec(op="gcn", d_in=16, d_hidden=16, num_classes=4,
                       num_layers=3)
        params = init_gnn(jax.random.key(0), spec)
        store = structs.init_store(spec.hist_dims())
        x_pad = jnp.asarray(DG.permute_node_array(structs, g.x))
        y_pad = jnp.asarray(DG.permute_node_array(structs,
                                                  g.y.astype(np.int32)))
        m_pad = jnp.asarray(DG.permute_node_array(structs, g.train_mask))
        batch = structs.device_batch()
        exchange = structs.exchange_arrays()
        loss_fn = DG.make_dist_loss_fn(spec, structs, mesh)

        dst, src, w = gcn_edge_weights(g)
        exact = np.asarray(full_forward(
            params, spec, jnp.asarray(g.x),
            (jnp.asarray(dst), jnp.asarray(src)), jnp.asarray(w),
            g.num_nodes))

        with mesh:
            errs = []
            for _ in range(spec.num_layers):
                loss, (store, acc, logits) = loss_fn(
                    params, store, x_pad, y_pad, m_pad, batch, exchange)
                out = np.asarray(logits)
                valid = structs.old_of_new >= 0
                got = np.zeros_like(exact)
                got[structs.old_of_new[valid]] = out[valid]
                errs.append(float(np.abs(got - exact).max()))
        print("ERRS", errs)
        assert errs[-1] < 1e-3, errs
        assert errs[0] > errs[-1]
    """)
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = SRC
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-4000:]
    assert "ERRS" in r.stdout


def test_dist_store_supports_quantized_histories():
    """`init_store` honors the history_dtype knob (was the PR-5 debt
    xfail): int8 stores carry per-row scale shards sized to the padded
    row space, and the f32 default is unchanged."""
    import numpy as np

    from repro.core import dist_gas as DG
    from repro.core.partition import metis_like_partition
    from repro.data.graphs import citation_graph

    g = citation_graph(num_nodes=80, num_features=8, num_classes=3,
                       seed=3)
    part = metis_like_partition(g.indptr, g.indices, 2, seed=0)
    structs = DG.build_dist_structs(g, part)
    n = structs.num_ranks * structs.rows
    store = structs.init_store([8, 8], history_dtype="int8")
    assert store.history_dtype == "int8"
    assert all(np.asarray(t).dtype == np.int8 for t in store.tables)
    assert store.scales is not None and len(store.scales) == 2
    assert all(s.shape == (n,) for s in store.scales)
    f32 = structs.init_store([8, 8])
    assert f32.history_dtype == "f32" and f32.scales is None
    assert all(np.asarray(t).dtype == np.float32 for t in f32.tables)


def test_dist_quantized_exchange_bitwise():
    """The quantized halo exchange ppermutes RAW int8 rows + per-row
    scales and dequantizes at the receiver: the exchanged halo must be
    BITWISE equal to gathering the same int8 table rows and scales
    directly (`dequantize_rows` semantics), and a full superstep must
    round-trip int8 tables + scales through `make_dist_loss_fn`."""
    code = textwrap.dedent("""
        import dataclasses
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.core import dist_gas as DG
        from repro.core import history as H
        from repro.core.partition import metis_like_partition
        from repro.data.graphs import citation_graph
        from repro.gnn.model import GNNSpec, init_gnn

        ranks = 2
        mesh = jax.make_mesh((ranks,), ("data",),
                             axis_types=(jax.sharding.AxisType.Auto,))
        g = citation_graph(num_nodes=150, num_features=8, num_classes=3,
                           seed=11)
        part = metis_like_partition(g.indptr, g.indices, ranks, seed=0)
        S = DG.build_dist_structs(g, part)
        n = S.num_ranks * S.rows
        d = 8
        rng = np.random.default_rng(0)
        vals = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
        q, s = H.quantize_rows(vals)
        store = S.init_store([d, d], history_dtype="int8")
        store = dataclasses.replace(store, tables=(q, q), scales=(s, s))

        plan = S.exchange_arrays()
        hmask = jnp.asarray(S.batch.halo_mask)

        def body(tables, scales, hm, pl_):
            pl_ = jax.tree_util.tree_map(lambda a: a[0], pl_)
            raw, scl = DG.halo_exchange(tables[0], pl_, S.max_halo,
                                        "data", scales_loc=scales[0])
            assert raw.dtype == jnp.int8, raw.dtype   # int8 on the wire
            deq = raw.astype(jnp.float32) * scl[:, None]
            return deq * hm[0][:, None]

        sm = jax.shard_map(
            body, mesh=mesh,
            in_specs=([P("data")] * 2, [P("data")] * 2, P("data"),
                      {k: P("data") for k in plan}),
            out_specs=P("data"), check_vma=False)
        with mesh:
            got = np.asarray(sm(list(store.tables), list(store.scales),
                                hmask, plan))
        got = got.reshape(S.num_ranks, S.max_halo, d)

        hn = np.asarray(S.batch.halo_nodes)
        hm_np = np.asarray(S.batch.halo_mask)
        hc = np.clip(hn, 0, n - 1)
        qn, sn = np.asarray(q), np.asarray(s)
        ref = np.where(hm_np[..., None],
                       qn[hc].astype(np.float32) * sn[hc][..., None], 0.0)
        assert np.array_equal(got, ref), float(np.abs(got - ref).max())

        # full superstep round-trip: pushes re-quantize, store stays int8
        spec = GNNSpec(op="gcn", d_in=8, d_hidden=8, num_classes=3,
                       num_layers=3)
        params = init_gnn(jax.random.key(0), spec)
        x_pad = jnp.asarray(DG.permute_node_array(S, g.x))
        y_pad = jnp.asarray(DG.permute_node_array(S,
                                                  g.y.astype(np.int32)))
        m_pad = jnp.asarray(DG.permute_node_array(S, g.train_mask))
        batch = S.device_batch()
        loss_fn = DG.make_dist_loss_fn(spec, S, mesh)
        with mesh:
            loss, (st2, acc, logits) = loss_fn(
                params, store, x_pad, y_pad, m_pad, batch, plan)
            loss2, (st3, _, _) = loss_fn(
                params, st2, x_pad, y_pad, m_pad, batch, plan)
        for st in (st2, st3):
            assert st.history_dtype == "int8"
            assert all(np.asarray(t).dtype == np.int8 for t in st.tables)
            assert st.scales is not None and len(st.scales) == 2
        assert np.isfinite(float(loss)) and np.isfinite(float(loss2))
        print("BITWISE_OK")
    """)
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    env["PYTHONPATH"] = SRC
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-4000:]
    assert "BITWISE_OK" in r.stdout
