"""Paper Table 5: GAS (with deep/expressive models) vs scalable baselines —
GraphSAGE (node-wise sampling), SGC (decoupled propagation), CLUSTER-GCN
(GAS executor with use_history=False), all on the same graph/splits."""
from __future__ import annotations

import time

from repro.core import runtime as R
from repro.data.graphs import citation_graph
from repro.gnn.model import GNNSpec
from repro.train.baselines import GraphSAGETrainer, SGCTrainer, TrainConfig


def run(quick=False):
    epochs = 25 if quick else 60
    g = citation_graph(num_nodes=1500 if quick else 4000, num_features=64,
                       num_classes=6, homophily=0.7, feature_noise=2.5,
                       seed=80)
    parts = 8 if quick else 16
    rows = []

    t0 = time.time()
    sage = GraphSAGETrainer(g, d_hidden=48, num_layers=2, fanout=10,
                            batch_size=256,
                            tcfg=TrainConfig(epochs=max(epochs // 4, 5),
                                             lr=0.01, seed=0))
    sage.fit()
    rows.append(("table5/graphsage", (time.time() - t0) * 1e6,
                 f"test={sage.evaluate()['test_acc']*100:.2f}"))

    t0 = time.time()
    sgc = SGCTrainer(g, k=2, tcfg=TrainConfig(epochs=epochs * 4, lr=0.05,
                                              seed=0))
    sgc.fit()
    rows.append(("table5/sgc", (time.time() - t0) * 1e6,
                 f"test={sgc.evaluate()['test_acc']*100:.2f}"))

    gcn = GNNSpec(op="gcn", d_in=64, d_hidden=48, num_classes=6,
                  num_layers=2)
    for name, spec, use_history in (
            ("cluster-gcn", gcn, False),
            ("gas-gcn", gcn, True),
            ("gas-gcnii16", GNNSpec(op="gcnii", d_in=64, d_hidden=48,
                                    num_classes=6, num_layers=16,
                                    alpha=0.1), True),
            ("gas-pna", GNNSpec(op="pna", d_in=64, d_hidden=48,
                                num_classes=6, num_layers=2,
                                log_deg_mean=1.8), True)):
        t0 = time.time()
        plan = R.build_plan(g, spec, R.GASConfig(
            num_parts=parts, partitioner="metis", use_history=use_history,
            epochs=epochs, lr=0.01, seed=0))
        state, _ = R.fit(plan, R.init_state(plan))
        acc = R.evaluate_exact(plan, state)["test_acc"]
        rows.append((f"table5/{name}", (time.time() - t0) * 1e6,
                     f"test={acc*100:.2f}"))
    return rows


if __name__ == "__main__":
    for name, us, derived in run():
        print(f"{name},{us:.0f},{derived}")
