"""GAS serving launcher: history tables as a warm embedding cache.

Trains a small GAS model (or loads a checkpoint written by
`train.checkpoint.save_gas_state`), binds its per-layer history tables as
the serving cache — f32/bf16/int8/vq stores are served as-is through the
fused dequant/decode-gather pull path — and answers a stream of batched
query-node requests under a configurable staleness SLO, printing per-SLO
p50/p99 latency, accuracy and cache diagnostics.

Roles (`--role`, the process split of core/serve_service.py):

    # single process, in-process serving (default)
    PYTHONPATH=src python -m repro.launch.serve_gas --role both \
        --nodes 600 --parts 4 --epochs 5 --slo 2 --requests 16 --batch 32

    # process 1: the history-owning backend (sole writer), on a socket
    PYTHONPATH=src python -m repro.launch.serve_gas --role backend \
        --port 18321 --nodes 600 --epochs 5

    # process 2..N: stateless frontends — same graph/serve flags, model
    # params arrive over the wire at hello; no checkpoint needed
    PYTHONPATH=src python -m repro.launch.serve_gas --role frontend \
        --port 18321 --nodes 600 --slo 0 --requests 16 --batch 32

    # exactness mode: --slo 0 re-pushes every stale dependency first
    # pure-cache mode: --slo none never refreshes

The backend and each frontend are separate JAX processes, and a TPU chip
belongs to one process at a time. On a chip host, run the store-service
backend with `JAX_PLATFORMS=cpu` (it only stores and moves rows) so the
frontend can hold the chip, or serve in one process with `--role both`.

A checkpoint round-trip carries its model metadata inline:

    ... serve_gas --save-checkpoint /tmp/gas.npz ...
    ... serve_gas --checkpoint /tmp/gas.npz ...

`--smoke` (used by CI on every matrix leg; the interpret leg also runs
the two-process backend+frontend pairing) serves two request batches on
a tiny graph and asserts the SLO contract: `halo_age_max <= slo` after
refresh, repeat requests are served bit-identically from the warm cache,
and — for lossless stores — SLO=0 logits match the jitted full-graph
recompute within `EXACT_ATOL` with the same argmax. Frontend smokes
assert the same contract through the wire.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import runtime as R
from repro.core import serve as S
from repro.core import serve_service as SS
from repro.data.graphs import citation_graph
from repro.gnn.model import GNNSpec, full_forward
from repro.launch.compile_cache import enable_compile_cache
from repro.train.checkpoint import (load_gas_meta, load_gas_state,
                                    save_gas_state)


def _parse_slo(s: str):
    return None if s.lower() in ("none", "inf") else int(s)


def _build(args):
    g = citation_graph(num_nodes=args.nodes, num_features=args.features,
                       num_classes=args.classes, seed=args.seed)
    spec = GNNSpec(op=args.op, d_in=args.features, d_hidden=args.hidden,
                   num_classes=args.classes, num_layers=args.layers,
                   heads=args.heads)
    cfg = R.GASConfig(num_parts=args.parts, backend=args.backend,
                      history_dtype=args.history_dtype,
                      epochs=args.epochs, seed=args.seed)
    return g, spec, cfg


def _serve_config(args):
    buckets = tuple(int(b) for b in args.buckets.split(","))
    return S.ServeConfig(staleness_slo=args.slo, buckets=buckets,
                         backend=args.backend)


def _trained_state(args):
    """Train (or restore) the GAS state the serving cache binds."""
    if args.checkpoint:
        meta = load_gas_meta(args.checkpoint)
        if meta is not None:
            for k, v in meta.get("args", {}).items():
                setattr(args, k, v)
        g, spec, cfg = _build(args)
        plan = R.build_plan(g, spec, cfg)
        state, step = load_gas_state(args.checkpoint, R.init_state(plan))
        print(f"loaded {args.checkpoint} (step {step}, "
              f"history_dtype={state.histories.history_dtype})")
    else:
        g, spec, cfg = _build(args)
        plan = R.build_plan(g, spec, cfg)
        t0 = time.time()
        state, logs = R.fit(plan, R.init_state(plan), epochs=args.epochs)
        loss = logs[-1]["loss"] if logs else float("nan")
        print(f"trained {args.epochs} epochs in {time.time() - t0:.1f}s "
              f"(loss {loss:.4f})")

    if args.save_checkpoint:
        keep = ("op", "nodes", "features", "classes", "hidden", "layers",
                "heads", "parts", "backend", "history_dtype", "seed")
        save_gas_state(args.save_checkpoint, state, step=args.epochs,
                       meta={"args": {k: getattr(args, k) for k in keep}})
        print(f"saved {args.save_checkpoint}")
    return g, spec, state


def _query_stream(args, num_nodes):
    rng = np.random.default_rng(args.seed + 1)
    return [rng.choice(num_nodes, size=args.batch, replace=False)
            for _ in range(args.requests)]


def _report(args, lat, halo_max, refreshed, acc, extra=""):
    p50, p99 = np.percentile(lat, 50), np.percentile(lat, 99)
    print(f"served {args.requests} x {args.batch} queries: "
          f"p50 {p50:.2f} ms, p99 {p99:.2f} ms, acc {acc:.3f}, "
          f"halo_age_max {max(halo_max):.0f}, "
          f"refreshed {refreshed:.0f} rows{extra}")


def _run_both(args):
    """Single-process serving through the typed plan/state/step API."""
    g, spec, state = _trained_state(args)
    splan = S.build_serve_plan(g, spec, _serve_config(args))
    state = S.init_serve_state(splan, state)
    store = state.histories
    print(f"cache: {len(store.tables)} tables x {g.num_nodes} rows, "
          f"{store.bytes():,} bytes ({store.history_dtype}), "
          f"backend={splan.backend}, slo={args.slo}, "
          f"buckets={splan.query_buckets}")

    queries = _query_stream(args, g.num_nodes)
    # warm the jit caches so latency numbers measure serving, not tracing
    _, state, _ = S.serve_request(splan, state, queries[0])

    lat, halo_max, results = [], [], []
    for q in queries:
        t0 = time.perf_counter()
        logits, state, diags = S.serve_request(splan, state, q)
        lat.append((time.perf_counter() - t0) * 1e3)
        halo_max.append(diags["halo_age_max"])
        results.append((q, logits, diags))

    y = np.asarray(g.y)[:g.num_nodes]
    correct = sum(int((np.argmax(lg, -1) == y[q]).sum())
                  for q, lg, _ in results)
    _report(args, lat, halo_max,
            sum(d["refreshed"] for _, _, d in results),
            correct / (args.requests * args.batch))

    if args.smoke:
        _smoke_asserts(args, g, spec, state.params,
                       state.histories.history_dtype, results,
                       replay=lambda q: S.serve_request(splan, state, q)[0])
        print("smoke OK")


def _run_backend(args):
    """The history-owning store service: sole writer, blocking accept
    loop. `--port 0` binds an ephemeral port (written to --port-file for
    the two-process CI smoke)."""
    g, spec, state = _trained_state(args)
    splan = S.build_serve_plan(g, spec, _serve_config(args))
    sstate = S.init_serve_state(splan, state)
    backend = SS.HistoryBackend(splan, sstate)
    store = sstate.histories
    print(f"backend: {len(store.tables)} tables x {g.num_nodes} rows "
          f"({store.history_dtype}), slo={args.slo}, version=0")

    def ready(port):
        print(f"backend listening on {args.host}:{port}", flush=True)
        if args.port_file:
            with open(args.port_file, "w") as f:
                f.write(str(port))

    SS.serve_backend_forever(backend, host=args.host, port=args.port,
                             ready=ready)


def _run_frontend(args):
    """A stateless query frontend: graph/spec/serve flags must match the
    backend's; params and codebooks arrive at hello."""
    g, _, _ = _build(args)
    spec = GNNSpec(op=args.op, d_in=args.features, d_hidden=args.hidden,
                   num_classes=args.classes, num_layers=args.layers,
                   heads=args.heads)
    transport = SS.SocketTransport(args.host, args.port)
    fe = SS.ServeFrontend(g, spec, _serve_config(args), transport)
    print(f"frontend: connected to {args.host}:{args.port}, "
          f"history_dtype={fe.history_dtype}, slo={args.slo}, "
          f"backend={fe.plan.backend}")

    queries = _query_stream(args, g.num_nodes)
    fe.serve_request(queries[0])          # warm the jit caches

    lat, halo_max, results, retries = [], [], [], 0.0
    for q in queries:
        t0 = time.perf_counter()
        logits, diags = fe.serve_request(q)
        lat.append((time.perf_counter() - t0) * 1e3)
        halo_max.append(diags["halo_age_max"])
        retries += diags["num_retries"]
        results.append((q, logits, diags))

    y = np.asarray(g.y)[:g.num_nodes]
    correct = sum(int((np.argmax(lg, -1) == y[q]).sum())
                  for q, lg, _ in results)
    _report(args, lat, halo_max,
            sum(d["refreshed"] for _, _, d in results),
            correct / (args.requests * args.batch),
            extra=f", retries {retries:.0f}")

    if args.smoke:
        _smoke_asserts(args, g, spec, fe.params, fe.history_dtype,
                       results, replay=lambda q: fe.serve_request(q)[0])
        print("smoke OK")
    fe.close()


def _smoke_asserts(args, g, spec, params, history_dtype, results, replay):
    slo = args.slo
    if slo is not None:
        for _, _, d in results:
            assert d["halo_age_max"] <= slo, (d, slo)
    # warm-cache coherence: repeating a request is bit-identical
    q = results[0][0]
    np.testing.assert_array_equal(replay(q), replay(q))
    # exactness: SLO=0 lossless-store serving equals the jitted
    # full-graph forward (compressed stores round through the quantizer
    # and are only accuracy-checked above)
    from repro.core.history import get_codec
    if slo == 0 and get_codec(history_dtype).lossless:
        from repro.core import gas as G
        dst, src, w = G.gcn_edge_weights(g)
        exact = np.asarray(jax.jit(full_forward, static_argnums=(1, 5))(
            params, spec, jnp.asarray(g.x),
            (jnp.asarray(dst), jnp.asarray(src)), jnp.asarray(w),
            g.num_nodes))
        for q, lg, _ in results:
            assert_matches_full_forward(lg, exact[q])


# SLO=0 serving and `full_forward` sum the same f32 terms in different
# orders (cached per-layer rows, block-dense kernels), so they agree to a
# few ulps, not bitwise.
EXACT_ATOL = 1e-5


def assert_matches_full_forward(logits, exact, atol: float = EXACT_ATOL):
    """Served logits vs the full-graph forward: within `atol` (absolute
    and relative) and the same argmax wherever the exact top-2 margin is
    wider than the tolerance allows to flip."""
    logits, exact = np.asarray(logits), np.asarray(exact)
    np.testing.assert_allclose(logits, exact, rtol=atol, atol=atol)
    top2 = np.sort(exact, axis=-1)[:, -2:]
    decided = top2[:, 1] - top2[:, 0] > 4 * atol * (1 + np.abs(top2[:, 1]))
    agree = np.argmax(logits, -1) == np.argmax(exact, -1)
    assert agree[decided].all(), "served argmax differs from full_forward"


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--role", default="both",
                    choices=("both", "backend", "frontend"),
                    help="both = in-process serving; backend = history-"
                         "owning store service; frontend = stateless "
                         "query resolver over the wire")
    ap.add_argument("--op", default="gcn")
    ap.add_argument("--nodes", type=int, default=600)
    ap.add_argument("--features", type=int, default=16)
    ap.add_argument("--classes", type=int, default=4)
    ap.add_argument("--hidden", type=int, default=32)
    ap.add_argument("--layers", type=int, default=3)
    ap.add_argument("--heads", type=int, default=4)
    ap.add_argument("--parts", type=int, default=4)
    ap.add_argument("--epochs", type=int, default=5)
    ap.add_argument("--backend", default=None,
                    help="pallas | interpret | jnp (default: resolve env)")
    ap.add_argument("--history-dtype", default=None,
                    help="f32 | bf16 | int8 | vq (default: resolve env)")
    ap.add_argument("--slo", type=_parse_slo, default=0,
                    help="staleness bound; 0 = exact, 'none' = pure cache")
    ap.add_argument("--buckets", default="8,32,128",
                    help="comma-separated query padding buckets")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--checkpoint", default=None,
                    help="load a trained GASState instead of training")
    ap.add_argument("--save-checkpoint", default=None)
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=18321,
                    help="store-service port (0 = ephemeral)")
    ap.add_argument("--port-file", default=None,
                    help="backend: write the bound port here once ready")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny run asserting the SLO contract (CI)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    if args.smoke:
        args.nodes = min(args.nodes, 200)
        args.requests = 2
        args.epochs = min(args.epochs, 2)

    if args.role == "backend":
        _run_backend(args)
    elif args.role == "frontend":
        _run_frontend(args)
    else:
        _run_both(args)


if __name__ == "__main__":
    main()
