"""Paper Table 1: GAS matches full-batch across GCN/GAT/APPNP/GCNII.

Synthetic citation graphs (datasets are offline), 3 seeds; reports
full-batch vs GAS test accuracy and the delta.
"""
from __future__ import annotations

import time

from common import mean_std  # noqa: F401

from repro.core import runtime as R
from repro.data.graphs import citation_graph
from repro.gnn.model import GNNSpec
from repro.train.baselines import FullBatchTrainer, TrainConfig

OPS = [("gcn", 2), ("gat", 2), ("appnp", 5), ("gcnii", 8)]


def run(seeds=(0, 1, 2), epochs=60, quick=False):
    if quick:
        seeds = (0,)
        epochs = 30
    rows = []
    for op, L in OPS:
        accs_f, accs_g = [], []
        t0 = time.time()
        for s in seeds:
            g = citation_graph(num_nodes=1200, num_features=64,
                               num_classes=6, homophily=0.72,
                               feature_noise=2.2, seed=10 + s)
            spec = GNNSpec(op=op, d_in=64, d_hidden=64, num_classes=6,
                           num_layers=L, alpha=0.1)
            tcfg = TrainConfig(epochs=epochs, lr=0.01, seed=s)
            fb = FullBatchTrainer(g, spec, tcfg)
            fb.fit()
            accs_f.append(fb.evaluate()["test_acc"])
            plan = R.build_plan(g, spec, R.GASConfig(
                num_parts=8, partitioner="metis", epochs=epochs, lr=0.01,
                seed=s))
            state, _ = R.fit(plan, R.init_state(plan))
            accs_g.append(R.evaluate_exact(plan, state)["test_acc"])
        mf, sf = mean_std(accs_f)
        mg, sg = mean_std(accs_g)
        us = (time.time() - t0) / max(len(seeds), 1) * 1e6
        rows.append((f"table1/{op}-{L}L", us,
                     f"full={mf*100:.2f}+-{sf*100:.2f} "
                     f"gas={mg*100:.2f}+-{sg*100:.2f} "
                     f"delta={(mg-mf)*100:+.2f}pp"))
    return rows


if __name__ == "__main__":
    for name, us, derived in run():
        print(f"{name},{us:.0f},{derived}")
