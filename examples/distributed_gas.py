import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=4").strip()

"""Distributed GAS (paper §7 future work, implemented): 4 ranks train one
cluster each per superstep; histories are row-sharded; halo rows move via
static ppermute exchange; grads flow through shard_map AD.

    python examples/distributed_gas.py
"""
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import dist_gas as DG
from repro.core.partition import metis_like_partition
from repro.data.graphs import citation_graph
from repro.gnn.model import GNNSpec, full_forward, init_gnn
from repro.core.gas import gcn_edge_weights
from repro.train.optimizer import adamw_init, adamw_update, clip_by_global_norm


def main():
    ranks = 4
    mesh = jax.make_mesh((ranks,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    g = citation_graph(num_nodes=2000, num_features=64, num_classes=6,
                       homophily=0.72, feature_noise=2.2, seed=7)
    part = metis_like_partition(g.indptr, g.indices, ranks, seed=0)
    structs = DG.build_dist_structs(g, part)
    print(f"{g.num_nodes} nodes on {ranks} ranks, {structs.rows} rows/rank, "
          f"max halo {structs.max_halo}")

    spec = GNNSpec(op="gcn", d_in=64, d_hidden=48, num_classes=6,
                   num_layers=3)
    params = init_gnn(jax.random.key(0), spec)
    opt = adamw_init(params)
    # row-sharded HistoryStore — the same typed store the single-host
    # runtime trains with
    store = structs.init_store(spec.hist_dims())

    x_pad = jnp.asarray(DG.permute_node_array(structs, g.x))
    y_pad = jnp.asarray(DG.permute_node_array(structs,
                                              g.y.astype(np.int32)))
    m_pad = jnp.asarray(DG.permute_node_array(structs, g.train_mask))
    batch = structs.device_batch()     # rank-stacked GASBatch
    exchange = structs.exchange_arrays()

    loss_fn = DG.make_dist_loss_fn(spec, structs, mesh)

    @jax.jit
    def superstep(params, opt, store, x_pad, y_pad, m_pad, batch, exchange):
        (loss, (new_store, acc, _)), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, store, x_pad, y_pad, m_pad,
                                   batch, exchange)
        grads, _ = clip_by_global_norm(grads, 2.0)
        params, opt = adamw_update(grads, opt, params, lr=0.01, b1=0.9,
                                   b2=0.999, weight_decay=5e-4)
        return params, opt, new_store, loss, acc

    with mesh:
        t0 = time.time()
        for epoch in range(80):
            params, opt, store, loss, acc = superstep(
                params, opt, store, x_pad, y_pad, m_pad, batch, exchange)
            if (epoch + 1) % 20 == 0:
                print(f"superstep {epoch+1}: loss {float(loss):.4f} "
                      f"train acc {float(acc):.4f}")
        print(f"trained in {time.time()-t0:.1f}s")

    # exact full-propagation evaluation
    dst, src, w = gcn_edge_weights(g)
    logits = full_forward(params, spec, jnp.asarray(g.x),
                          (jnp.asarray(dst), jnp.asarray(src)),
                          jnp.asarray(w), g.num_nodes)
    pred = np.asarray(jnp.argmax(logits, -1))
    print("test acc:", float((pred[g.test_mask] == g.y[g.test_mask]).mean()))


if __name__ == "__main__":
    main()
