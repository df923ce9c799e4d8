"""Quickstart: convert a full-batch GCN into its GAS-scaled variant.

Mirrors the paper's Listing 1 -> Listing 2 conversion: same operator, same
hyperparameters — the only changes are (1) METIS-style clustering, (2) the
history-backed mini-batch executor. Uses the typed plan/state/step runtime
(`repro.core.runtime`): one `GASConfig` holds every knob, `build_plan`
does all one-time work (partition, padded `GASBatch` structures, kernel
backend resolution), and training threads an explicit `GASState` through
pure jitted epochs (`R.fit` runs the same loop for `config.epochs`).

    PYTHONPATH=src python examples/quickstart.py [--backend jnp|interpret|pallas]
                                                 [--history-dtype f32|bf16|int8|vq]
                                                 [--history-storage device|host]
                                                 [--prefetch-depth N]

`--backend` selects the kernel path for history I/O and GCN aggregation
(see repro/kernels/ops.py); default auto-selects pallas on TPU, jnp on CPU.
`--history-dtype` compresses the history tables (the dominant memory
term): bf16 halves them, int8 quarters them with symmetric per-row
quantization, and vq product-quantizes rows to one uint8 code per 8
features against a per-layer k-means codebook (>= 10x at realistic
sizes; requires hidden widths divisible by 8) — the added error is
reported as the `hist_quant_err` metric next to the staleness
diagnostics.
`--history-storage host` spills the tables to host RAM (the paper's
large-graph configuration: capacity scales with CPU RAM, pulled rows
stream device-ward) and `--prefetch-depth` software-pipelines the epoch
so batch i+depth's halo pull is dispatched before batch i's
backward/push — both are bit-identical to the synchronous device
schedule.
"""
import argparse
import time

from repro.core import history as H
from repro.core import runtime as R
from repro.data.graphs import citation_graph
from repro.gnn.model import GNNSpec
from repro.kernels import ops
from repro.launch.compile_cache import enable_compile_cache
from repro.train.baselines import FullBatchTrainer, TrainConfig


def main(backend=None, epochs=60, nodes=2500, history_dtype=None,
         history_storage=None, prefetch_depth=0):
    backend = ops.resolve_backend(backend)
    history_dtype = H.resolve_history_dtype(history_dtype)
    history_storage = H.resolve_history_storage(history_storage)
    print(f"kernel backend: {backend}, history dtype: {history_dtype}, "
          f"history storage: {history_storage}, "
          f"prefetch depth: {prefetch_depth}")
    graph = citation_graph(num_nodes=nodes, num_features=128, num_classes=7,
                           homophily=0.75, feature_noise=2.0, seed=0)
    print(f"graph: {graph.num_nodes} nodes, {graph.num_edges} edges, "
          f"{graph.num_classes} classes")

    spec = GNNSpec(op="gcn", d_in=128, d_hidden=64, num_classes=7,
                   num_layers=2)

    t0 = time.time()
    full = FullBatchTrainer(graph, spec, TrainConfig(epochs=epochs, lr=0.01))
    full.fit()
    acc_full = full.evaluate()
    print(f"full-batch GCN : test acc {acc_full['test_acc']:.4f} "
          f"({time.time()-t0:.1f}s)")

    # GAS: one config -> one plan (static) + one state (trainable),
    # then pure functional epochs
    t0 = time.time()
    config = R.GASConfig(num_parts=16, partitioner="metis",
                         backend=backend, history_dtype=history_dtype,
                         history_storage=history_storage,
                         prefetch_depth=prefetch_depth,
                         epochs=epochs, lr=0.01)
    plan = R.build_plan(graph, spec, config)
    state = R.init_state(plan)
    for epoch in range(config.epochs):
        state, metrics = R.train_epoch(plan, state, epoch)
    acc_gas = R.evaluate_exact(plan, state)
    print(f"GAS GCN        : test acc {acc_gas['test_acc']:.4f} "
          f"({time.time()-t0:.1f}s, "
          f"hist_quant_err {metrics['hist_quant_err']:.2e})")
    print(f"delta          : {(acc_gas['test_acc']-acc_full['test_acc'])*100:+.2f}pp "
          f"(paper Table 1: GAS matches full-batch)")

    # constant-memory history-based inference (paper advantage #2):
    # lax.scan over the stacked GASBatch, histories pulled per cluster
    logits = R.predict(plan, state)
    print(f"gas_predict    : logits {tuple(logits.shape)} from "
          f"{plan.batches.num_batches} cluster batches")

    # constant-memory working set + typed per-struct accounting
    b = plan.batches
    peak = (b.max_b + b.max_h) * spec.d_hidden * 4 * spec.num_layers
    full_ws = graph.num_nodes * spec.d_hidden * 4 * spec.num_layers
    print(f"device working set: GAS {peak/1e6:.2f}MB vs full {full_ws/1e6:.2f}MB "
          f"({full_ws/peak:.1f}x smaller)")
    sb = b.structural_bytes()
    print(f"batch structures : total {sb['total']/1e6:.2f}MB "
          f"(coo {sb['coo']/1e6:.2f}MB, blocks "
          f"{(sb['blocks_forward']+sb['blocks_transposed'])/1e6:.2f}MB)")
    f32_bytes = (graph.num_nodes + 1) * spec.d_hidden * 4 * \
        state.histories.num_layers
    print(f"history store    : {state.histories.bytes()/1e6:.2f}MB in "
          f"{state.histories.num_layers} tables "
          f"(dtype {state.histories.history_dtype}, "
          f"{f32_bytes/max(state.histories.bytes(), 1):.2f}x vs f32; "
          f"backend bound: {state.histories.backend})")


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--backend", choices=ops.BACKENDS, default=None)
    ap.add_argument("--history-dtype", choices=H.HISTORY_DTYPES,
                    default=None,
                    help="history-table precision (default: "
                         "$REPRO_HISTORY_DTYPE or f32)")
    ap.add_argument("--history-storage", choices=H.HISTORY_STORAGES,
                    default=None,
                    help="history-table placement (default: "
                         "$REPRO_HISTORY_STORAGE or device); 'host' "
                         "spills tables to host RAM and streams pulled "
                         "rows device-ward")
    ap.add_argument("--prefetch-depth", type=int, default=0,
                    help="software-pipeline depth: dispatch batch "
                         "i+depth's halo pull before batch i's "
                         "backward/push (0 = synchronous)")
    ap.add_argument("--epochs", type=int, default=60,
                    help="training epochs (CI smoke uses a small value)")
    ap.add_argument("--nodes", type=int, default=2500)
    args = ap.parse_args()
    enable_compile_cache()
    main(args.backend, epochs=args.epochs, nodes=args.nodes,
         history_dtype=args.history_dtype,
         history_storage=args.history_storage,
         prefetch_depth=args.prefetch_depth)
