import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=4").strip()

"""Sharded GAS (paper §7 future work, implemented) through the runtime:
`GASConfig(ranks=4)` puts 8 parts on 4 ranks, 2 each; histories are
row-sharded over the mesh; every superstep each rank trains one of its
parts, with halo rows from the other ranks by `ppermute`, and the ranks
sum their gradients before one AdamW step.

    python examples/distributed_gas.py
"""
import time

from repro.core import runtime as R
from repro.data.graphs import citation_graph
from repro.gnn.model import GNNSpec


def main():
    g = citation_graph(num_nodes=2000, num_features=64, num_classes=6,
                       homophily=0.72, feature_noise=2.2, seed=7)
    spec = GNNSpec(op="gcn", d_in=64, d_hidden=48, num_classes=6,
                   num_layers=3)
    plan = R.build_plan(g, spec, R.GASConfig(num_parts=8, ranks=4,
                                             epochs=40, seed=0))
    dist = plan.batch_stack
    print(f"{g.num_nodes} nodes in {plan.batches.num_batches} parts on "
          f"{dist.ranks} ranks, {dist.rows} rows a shard, "
          f"{dist.slots.shape[1]} supersteps an epoch, backend "
          f"{plan.backend}")
    state = R.init_state(plan)
    t0 = time.time()
    for epoch in range(plan.config.epochs):
        state, m = R.train_epoch(plan, state, epoch)
        if (epoch + 1) % 10 == 0:
            print(f"epoch {epoch + 1}: loss {m['loss']:.4f} "
                  f"train acc {m['acc']:.4f}")
    print(f"trained in {time.time() - t0:.1f}s")
    print("exact evaluation:", R.evaluate_exact(plan, state))


if __name__ == "__main__":
    main()
