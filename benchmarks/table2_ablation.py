"""Paper Table 2/7: ablation of the two GAS techniques — METIS-style
inter-connectivity minimization and Eq. 3 Lipschitz regularization — for a
deep GCNII and an expressive GIN, reported as pp deltas vs full-batch."""
from __future__ import annotations

import time

from common import mean_std

from repro.core import runtime as R
from repro.data.graphs import citation_graph, sbm_cluster_graph
from repro.gnn.model import GNNSpec
from repro.train.baselines import FullBatchTrainer, TrainConfig

VARIANTS = [
    ("baseline", dict(partitioner="random", reg=False)),
    ("+reg", dict(partitioner="random", reg=True)),
    ("+metis", dict(partitioner="metis", reg=False)),
    ("gas(full)", dict(partitioner="metis", reg=True)),
]


def _run_case(g, spec_kw, epochs, seeds, parts, k=1):
    full_accs = []
    for s in seeds:
        spec = GNNSpec(**spec_kw)
        fb = FullBatchTrainer(g, spec, TrainConfig(epochs=epochs, lr=0.01,
                                                   seed=s))
        fb.fit()
        full_accs.append(fb.evaluate()["test_acc"])
    full_acc = mean_std(full_accs)[0]

    out = {}
    for name, opt in VARIANTS:
        accs = []
        for s in seeds:
            kw = dict(spec_kw)
            if opt["reg"]:
                kw.update(reg_delta=0.05, reg_weight=0.05)
            spec = GNNSpec(**kw)
            plan = R.build_plan(g, spec, R.GASConfig(
                num_parts=parts, partitioner=opt["partitioner"],
                clusters_per_batch=k, epochs=epochs, lr=0.01, seed=s))
            state, _ = R.fit(plan, R.init_state(plan))
            accs.append(R.evaluate_exact(plan, state)["test_acc"])
        out[name] = mean_std(accs)[0] - full_acc
    return full_acc, out


def run(quick=False):
    seeds = (0,) if quick else (0, 1)
    epochs = 40 if quick else 80
    rows = []

    t0 = time.time()
    g = citation_graph(num_nodes=1000, num_features=64, num_classes=6,
                       homophily=0.7, feature_noise=2.5, seed=21)
    full_acc, deltas = _run_case(
        g, dict(op="gcnii", d_in=64, d_hidden=48, num_classes=6,
                num_layers=16, alpha=0.1), epochs, seeds, parts=8)
    rows.append(("table2/gcnii-16L", (time.time() - t0) * 1e6,
                 f"full={full_acc*100:.2f} " + " ".join(
                     f"{k}={v*100:+.2f}pp" for k, v in deltas.items())))

    t0 = time.time()
    g2 = sbm_cluster_graph(num_nodes=900, num_communities=6, seed=22)
    full_acc2, deltas2 = _run_case(
        g2, dict(op="gin", d_in=7, d_hidden=48, num_classes=6, num_layers=4),
        epochs, seeds, parts=24, k=8)
    rows.append(("table7/gin-4L-cluster", (time.time() - t0) * 1e6,
                 f"full={full_acc2*100:.2f} " + " ".join(
                     f"{k}={v*100:+.2f}pp" for k, v in deltas2.items())))
    return rows


if __name__ == "__main__":
    for name, us, derived in run():
        print(f"{name},{us:.0f},{derived}")
