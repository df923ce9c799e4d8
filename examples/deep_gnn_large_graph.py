"""Scale a DEEP GCNII and an EXPRESSIVE GIN to a larger graph with GAS
(the paper's §6.3 scenario): models that are hard to scale because their
receptive field spans the whole graph.

    PYTHONPATH=src python examples/deep_gnn_large_graph.py [--nodes 20000]
"""
import argparse
import time

from repro.core import runtime as R
from repro.core.partition import inter_intra_ratio
from repro.data.graphs import citation_graph, sbm_cluster_graph
from repro.gnn.model import GNNSpec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--nodes", type=int, default=20000)
    ap.add_argument("--epochs", type=int, default=30)
    args = ap.parse_args()

    graph = citation_graph(num_nodes=args.nodes, avg_degree=8,
                           num_features=128, num_classes=10,
                           homophily=0.7, feature_noise=2.0, seed=1)
    parts = max(args.nodes // 800, 8)
    print(f"graph: {graph.num_nodes} nodes {graph.num_edges} edges; "
          f"{parts} METIS-like clusters")

    # deep GCNII — full-batch would hold num_nodes x hidden x 32 activations
    spec = GNNSpec(op="gcnii", d_in=128, d_hidden=64, num_classes=10,
                   num_layers=32, alpha=0.1)
    t0 = time.time()
    plan = R.build_plan(graph, spec, R.GASConfig(
        num_parts=parts, partitioner="metis", clusters_per_batch=2,
        epochs=args.epochs, lr=0.01))
    print("inter/intra after clustering:",
          round(inter_intra_ratio(graph.indptr, graph.indices, plan.part), 3))
    state, _ = R.fit(plan, R.init_state(plan), log_every=10)
    print(f"GCNII-32L: {R.evaluate_exact(plan, state)} "
          f"in {time.time()-t0:.0f}s")
    b = plan.batches
    ws = (b.max_b + b.max_h) * 64 * 4 * 32 / 1e6
    print(f"device working set {ws:.1f}MB for a {graph.num_nodes}-node graph "
          f"(constant in graph size — paper's central claim)")

    # expressive GIN on a CLUSTER-style task
    sbm = sbm_cluster_graph(num_nodes=min(args.nodes, 6000),
                            num_communities=10, seed=2)
    spec2 = GNNSpec(op="gin", d_in=sbm.x.shape[1], d_hidden=64,
                    num_classes=10, num_layers=4, reg_delta=0.05,
                    reg_weight=0.05)
    plan2 = R.build_plan(sbm, spec2, R.GASConfig(
        num_parts=40, partitioner="metis", clusters_per_batch=10,
        epochs=max(args.epochs, 40), lr=0.01))
    state2, _ = R.fit(plan2, R.init_state(plan2), log_every=10)
    print(f"GIN-4L on CLUSTER-SBM: {R.evaluate_exact(plan2, state2)}")


if __name__ == "__main__":
    main()
