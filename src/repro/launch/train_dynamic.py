"""Evolving-graph GAS launcher: train across a snapshot sequence.

Builds a slack-padded dynamic plan (`core.dynamic.build_dynamic_plan`),
fits the initial snapshot, then per snapshot draws a seeded
`random_delta` (edge churn + node arrivals + feature drift), carries the
plan/state across it with the incremental `advance` — partition repair,
batch patching, selective history re-push — and keeps training. Per
snapshot it prints accuracy and where the advance time went.

    PYTHONPATH=src python -m repro.launch.train_dynamic --nodes 800 \
        --parts 8 --snapshots 4 --epochs 3 --churn 0.01 --nodes-add 5

    # force cold rebuilds every snapshot, for comparison:
    ... train_dynamic --cold-frac 0.0

`--smoke` (used by CI on the interpret matrix leg) runs two snapshots on
a tiny graph and asserts the dynamic contract: the advance stayed
incremental, the repaired partition is valid and balanced, history rows
outside the delta's out-closure kept their exact bits, and the
post-advance metrics are finite.
"""
from __future__ import annotations

import argparse
import time

import numpy as np

from repro.core import delta as D
from repro.core import dynamic as DY
from repro.core import runtime as R
from repro.data.graphs import citation_graph
from repro.launch.compile_cache import enable_compile_cache


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--op", default="gcn")
    ap.add_argument("--nodes", type=int, default=800)
    ap.add_argument("--features", type=int, default=16)
    ap.add_argument("--classes", type=int, default=4)
    ap.add_argument("--hidden", type=int, default=32)
    ap.add_argument("--layers", type=int, default=3)
    ap.add_argument("--heads", type=int, default=4)
    ap.add_argument("--parts", type=int, default=8)
    ap.add_argument("--epochs", type=int, default=3,
                    help="training epochs per snapshot")
    ap.add_argument("--snapshots", type=int, default=4,
                    help="number of deltas applied after the initial fit")
    ap.add_argument("--churn", type=float, default=0.01,
                    help="fraction of undirected edges deleted AND "
                         "inserted per snapshot")
    ap.add_argument("--nodes-add", type=int, default=5,
                    help="new nodes per snapshot")
    ap.add_argument("--feat-frac", type=float, default=0.01,
                    help="fraction of nodes whose features drift")
    ap.add_argument("--cold-frac", type=float, default=0.25,
                    help="closure fraction above which advance "
                         "cold-rebuilds (0 forces cold every snapshot)")
    ap.add_argument("--pad-slack", type=float, default=0.25)
    ap.add_argument("--backend", default=None,
                    help="pallas | interpret | jnp (default: resolve env)")
    ap.add_argument("--history-dtype", default=None,
                    help="f32 | bf16 | int8 | vq (default: resolve env)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny run asserting the dynamic contract (CI)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    if args.smoke:
        args.nodes = min(args.nodes, 180)
        args.snapshots = 2
        args.epochs = min(args.epochs, 2)
        args.parts = min(args.parts, 4)
        args.cold_frac = 1.01          # the contract under test

    from repro.gnn.model import GNNSpec
    g = citation_graph(num_nodes=args.nodes, num_features=args.features,
                       num_classes=args.classes, seed=args.seed)
    spec = GNNSpec(op=args.op, d_in=args.features, d_hidden=args.hidden,
                   num_classes=args.classes, num_layers=args.layers,
                   heads=args.heads)
    dcfg = DY.DynamicGASConfig(
        base=R.GASConfig(num_parts=args.parts, backend=args.backend,
                         history_dtype=args.history_dtype,
                         epochs=args.epochs, seed=args.seed),
        cold_rebuild_frac=args.cold_frac, pad_slack=args.pad_slack)

    plan = DY.build_dynamic_plan(g, spec, dcfg)
    state = R.init_state(plan)
    t0 = time.time()
    state, _ = R.fit(plan, state, epochs=args.epochs)
    ev = R.evaluate_exact(plan, state)
    print(f"snapshot 0: {g.num_nodes} nodes, trained {args.epochs} "
          f"epochs in {time.time() - t0:.1f}s, val {ev['val_acc']:.3f} "
          f"test {ev['test_acc']:.3f} "
          f"(backend={plan.backend}, "
          f"history={state.histories.history_dtype})")

    smoke_rec = None
    for snap in range(1, args.snapshots + 1):
        d = D.random_delta(plan.graph, edge_churn=args.churn,
                           nodes_add=args.nodes_add,
                           feat_frac=args.feat_frac,
                           seed=args.seed + 100 + snap)
        n_old = plan.graph.num_nodes
        grown = (state.histories.grow(d.num_new_nodes) if args.smoke
                 else None)
        plan, state, info = DY.advance(plan, state, d, dcfg)
        if args.smoke:
            # host-side snapshot of the contract data NOW — the next fit
            # donates this state's buffers, so the comparison must not
            # hold device references across it
            smoke_rec = dict(
                d=d, info=info, n_old=n_old,
                grown=[np.asarray(t) for t in grown.tables],
                grown_age=np.asarray(grown.age),
                tables=[np.asarray(t) for t in state.histories.tables],
                age=np.asarray(state.histories.age))
        state, _ = R.fit(plan, state, epochs=args.epochs)
        ev = R.evaluate_exact(plan, state)
        mode = "cold" if info.cold else "incremental"
        print(f"snapshot {snap}: {plan.graph.num_nodes} nodes "
              f"(+{info.num_new_nodes}), advance {info.total_s * 1e3:.1f}ms "
              f"[{mode}: partition {info.partition_s * 1e3:.1f} "
              f"batches {info.batches_s * 1e3:.1f} "
              f"repush {info.repush_s * 1e3:.1f}], "
              f"closure {info.closure_frac:.1%}, "
              f"rebuilt {info.rebuilt_parts} parts, "
              f"moved {info.reassigned} nodes, "
              f"val {ev['val_acc']:.3f} test {ev['test_acc']:.3f}")

    if args.smoke:
        _smoke_asserts(args, plan, state, smoke_rec)
        print("smoke OK")


def _smoke_asserts(args, plan, state, rec):
    info = rec["info"]
    assert not info.cold, info.reason
    part = np.asarray(plan.part)
    N = plan.graph.num_nodes
    assert part.shape == (N,) and part.min() >= 0 \
        and part.max() < args.parts
    sizes = np.bincount(part, minlength=args.parts)
    assert sizes.max() <= int(np.ceil(1.15 * N / args.parts)) + 1, sizes
    # rows outside the delta's out-closure kept their exact bits (ages
    # too), rows inside reset their clock — checked on the host
    # snapshots taken right after the advance
    closure = D.out_closure(plan.graph,
                            rec["d"].invalidation_seeds(rec["n_old"]),
                            plan.spec.num_layers - 1)
    outside = np.setdiff1d(np.arange(N), closure)
    for t_new, t_old in zip(rec["tables"], rec["grown"]):
        np.testing.assert_array_equal(t_new[outside], t_old[outside])
    np.testing.assert_array_equal(rec["age"][closure], 0)
    np.testing.assert_array_equal(rec["age"][outside],
                                  rec["grown_age"][outside])
    ev = R.evaluate_exact(plan, state)
    assert np.isfinite(ev["val_acc"]) and np.isfinite(ev["test_acc"])


if __name__ == "__main__":
    main()
