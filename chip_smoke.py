#!/usr/bin/env python3
"""One-process smoke of GAS training and serving on a TPU.

    python3 chip_smoke.py              # one chip
    python3 chip_smoke.py --chips 4    # sharded GAS on a 4-chip mesh
    python3 chip_smoke.py --rehearse [--chips 4]
                                       # tiny CPU rehearsal (not a chip run)

One chip: GCN 128 -> 256 -> 256 -> 40 (ogbn-arxiv's feature and class
counts, the 3 x 256 GCN of the PyGAS large-graph configs) on a seeded
synthetic citation graph, through the public runtime on the compiled
`pallas` backend:

  * kernels: the matmul precision probe behind `COMPARE_PRECISION`
    (`bcsr_spmm` and an XLA dot against an f64 reference), and the vq
    history pull and push kernels against `core.history`'s jnp codec;
  * training, once with f32 and once with int8 histories: a few
    `train_step`s, then fused `train_epoch`s (one `lax.scan` each). The
    first two steps' losses and pushed history rows are compared with the
    same steps on `backend="jnp"`, on the same chip and at float32 matmul
    precision (see `F32_TOL`): the second step runs on the parameters
    the first step's backward and optimizer update made. A third step on
    both backends at the default precision (the precision of the timed
    steps) is held to `BF16_TOL`. The loss must be finite and fall;
  * serving: `staleness_slo=0` requests from the trained f32 state,
    compared with `full_forward` on the same chip.

`--chips 4` runs only sharded GAS (`GASConfig(ranks=4)`, `core/dist_gas.py`)
through the runtime on a mesh of the four chips: the first epoch on the
kernels must match the same epoch on `backend="jnp"` (loss, parameters,
history rows, at float32 precision), then further epochs must lower the
loss.

Earlier lines report the device, the resolved backend, set-up, compile
and step seconds and peak device bytes. The last line is one JSON object,
`{"ok": true, "device": {...}}`, printed only when every phase passed on
a TPU; any failure exits non-zero. `--rehearse` runs the same phases on
the CPU with interpreted kernels on a small graph, for checking the
script without a chip; it never reports a device result.

The compile cache follows `repro.launch.compile_cache`. Everything runs
in this one process: a TPU chip belongs to one process at a time.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

# Agreement between two implementations on one chip, as the largest
# error relative to the largest magnitude of the reference. On a TPU an
# f32 dot at the default precision rounds its operands to bf16, so two
# sides whose dot inputs differ in the last f32 bits can land a bf16
# step (2^-8) apart (measured 2.6e-3 on pushed rows). The comparisons
# therefore run both sides at `jax.default_matmul_precision("float32")`
# and hold them to f32 rounding over a 3-layer forward; timed steps and
# epochs run at the default precision.
F32_TOL = 1e-4
COMPARE_PRECISION = "float32"
# parameters after two AdamW steps. The first steps move a weight by
# about lr * g / (|g| + eps), so where |g| is near eps a last-bit
# difference in g moves it by a visible fraction of lr (1.4e-4 of the
# leaf's scale on a v5e chip, 2.4e-5 on the CPU rehearsal); a wrong
# gradient moves weights by about lr, 1e-1 of a leaf's scale or more
PARAM_TOL = 1e-3
# the same comparison at the default precision: a few bf16 rounding
# steps (2^-8 = 3.9e-3) over the 3-layer forward
BF16_TOL = 1e-2


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny CPU run with interpreted kernels")
    ap.add_argument("--seed", type=int, default=0)
    return ap.parse_args(argv)


def log(msg: str) -> None:
    print(msg, flush=True)


def timed(fn, *args):
    """(result, seconds) with the result ready on the device."""
    import jax
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return out, time.perf_counter() - t0


def rel_err(got, want) -> float:
    import numpy as np
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.abs(got - want).max() / max(np.abs(want).max(), 1e-30))


def check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)


class Misses:
    """Tolerance checks of one phase: each is logged as it is made, and
    the phase fails at its end naming every miss, so one run shows all
    the numbers."""

    def __init__(self, tag: str):
        self.tag, self.missed = tag, []

    def within(self, what: str, err: float, tol: float) -> None:
        log(f"{self.tag}: {what} {err:.2e} (tolerance {tol:g})")
        if not err <= tol:
            self.missed.append(f"{what} {err:.2e} > {tol:g}")

    def raise_any(self) -> None:
        check(not self.missed, f"{self.tag}: " + "; ".join(self.missed))


def graph_size(rehearse: bool):
    """(nodes, GAS parts). 10k nodes keeps the pure-Python METIS-like
    partition near 15 s of host time; the rehearsal graph is tiny."""
    return (600, 3) if rehearse else (10_000, 10)


def peak_bytes(dev):
    stats = dev.memory_stats()
    return None if not stats else stats.get("peak_bytes_in_use")


# ---------------------------------------------------------------------------
# one chip: kernels
# ---------------------------------------------------------------------------

def precision_probe(rng, interpret):
    """`bcsr_spmm` and an XLA f32 dot against an f64 reference, at the
    default and at `COMPARE_PRECISION` matmul precision: why the
    comparisons below run at float32 precision."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels.bcsr_spmm import bcsr_spmm

    r, k, bn, d = 4, 4, 128, 256
    x = rng.standard_normal((k * bn, d)).astype(np.float32)
    vals = rng.standard_normal((r, k, bn, bn)).astype(np.float32)
    cols = np.stack([rng.permutation(k) for _ in range(r)]).astype(np.int32)
    want = np.einsum("rkab,rkbd->rad", vals.astype(np.float64),
                     x.reshape(k, bn, d)[cols].astype(np.float64))
    want = want.reshape(r * bn, d)
    a = vals[0].transpose(1, 0, 2).reshape(bn, k * bn)
    want_dot = a.astype(np.float64) @ x.astype(np.float64)
    spmm = jax.jit(lambda x, v, c: bcsr_spmm(x, v, c, interpret=interpret))
    errs = {}
    for prec in ("default", COMPARE_PRECISION):
        with jax.default_matmul_precision(
                None if prec == "default" else prec):
            errs[prec] = (
                rel_err(spmm(jnp.asarray(x), jnp.asarray(vals),
                             jnp.asarray(cols)), want),
                rel_err(jax.jit(jnp.dot)(jnp.asarray(a), jnp.asarray(x)),
                        want_dot))
    log("kernels: f32 matmul rel err vs f64 (bcsr_spmm, XLA dot): "
        + ", ".join(f"{p} precision {e[0]:.2e}, {e[1]:.2e}"
                    for p, e in errs.items()))
    misses = Misses("kernels")
    for name, err in zip(("bcsr_spmm", "XLA dot"), errs[COMPARE_PRECISION]):
        misses.within(f"{name} at {COMPARE_PRECISION} precision rel err "
                      "vs f64", err, F32_TOL)
    misses.raise_any()


def vq_kernels(rng, interpret):
    """The vq pull (`gather_rows_vq`) must equal `history.vq_decode_rows`
    bit for bit; the vq push (`scatter_rows_vq`) must pick, for every
    subvector, an entry as near (in f64) as `history.vq_encode_rows`'s."""
    import jax.numpy as jnp
    import numpy as np
    from repro.core import history as H
    from repro.kernels.gather import gather_rows_vq
    from repro.kernels.scatter import scatter_rows_vq

    n, m, s, c, ds = (601, 64, 32, 256, 8) if interpret else \
        (10_001, 1_536, 32, 256, 8)
    cb = rng.uniform(-1, 1, (s, c, ds)).astype(np.float32)
    cb[:, 0] = 0.0                              # entry 0 is pinned to zero
    table = rng.integers(0, c, (n, s), dtype=np.uint8)
    scales = rng.uniform(0.5, 2.0, n).astype(np.float32)
    idx = rng.integers(0, n, m).astype(np.int32)
    got = gather_rows_vq(jnp.asarray(table), jnp.asarray(cb),
                         jnp.asarray(scales), jnp.asarray(idx),
                         interpret=interpret)[:, :s * ds]
    want = H.vq_decode_rows(jnp.asarray(table[idx]), jnp.asarray(cb),
                            jnp.asarray(scales[idx]))
    got, want = np.asarray(got), np.asarray(want)
    log(f"kernels: vq pull [{m}, {s * ds}] rows differing from "
        f"vq_decode_rows: {int((got != want).any(axis=1).sum())}")
    check(np.array_equal(got, want), "kernels: vq pull is not bitwise "
          f"vq_decode_rows (max abs err {np.abs(got - want).max():.3e})")

    rows = rng.permutation(n)[:m].astype(np.int32)
    vals = rng.standard_normal((m, s * ds)).astype(np.float32)
    pushed = scatter_rows_vq(jnp.asarray(table), jnp.asarray(rows),
                             jnp.asarray(vals),
                             H.vq_row_scales(jnp.asarray(vals)),
                             jnp.asarray(cb), interpret=interpret)
    codes = np.asarray(pushed)[rows].astype(np.int64)
    ref_codes, ref_scl = H.vq_encode_rows(jnp.asarray(vals),
                                          jnp.asarray(cb))
    ref_codes = np.asarray(ref_codes).astype(np.int64)
    u = (vals / np.asarray(ref_scl)[:, None]).reshape(m, s, ds)
    sub = np.arange(s)

    def dist(cd):
        return np.square(u.astype(np.float64) - cb[sub, cd]).sum(-1)
    worse = dist(codes) - dist(ref_codes)
    log(f"kernels: vq push [{m}, {s}] codes differing from vq_encode_rows: "
        f"{int((codes != ref_codes).sum())}, max distance excess "
        f"{worse.max():.2e}")
    check(worse.max() <= 1e-5,
          "kernels: vq push picked a farther codebook entry than "
          f"vq_encode_rows (excess {worse.max():.2e})")
    untouched = np.ones(n, bool)
    untouched[rows] = False
    check(np.array_equal(np.asarray(pushed)[untouched], table[untouched]),
          "kernels: vq push changed rows it was not given")


# ---------------------------------------------------------------------------
# one chip: training
# ---------------------------------------------------------------------------

def pushed_rows(state, rows):
    """Layer-wise history rows at `rows`, dequantized to f32, plus the
    per-row int8 quantization step (zeros for f32 stores)."""
    import numpy as np
    h = state.histories
    out = []
    for ell, table in enumerate(h.tables):
        vals = np.asarray(table)[rows].astype(np.float32)
        step = np.zeros(len(rows), np.float32)
        if h.scales is not None:
            step = np.asarray(h.scales[ell])[rows]
            vals = vals * step[:, None]
        out.append((vals, step))
    return out


def train_phase(g, spec, backend, history_dtype, part, num_parts, seed,
                steps, epochs):
    """Train on `backend`, check its first three steps against the same
    steps on jnp. Returns (plan, state) after training."""
    import jax
    import numpy as np
    from repro.core import runtime as R

    def config(be):
        return R.GASConfig(num_parts=num_parts, backend=be,
                           history_dtype=history_dtype, seed=seed)

    tag = f"train[{history_dtype}]"
    plan = R.build_plan(g, spec, config(backend), part=part)
    check(plan.backend == ("interpret" if backend == "interpret"
                           else "pallas"),
          f"{tag}: backend resolved to {plan.backend}, not pallas")
    ref = R.build_plan(g, spec, config("jnp"), part=plan.part)
    state, ref_state = R.init_state(plan), R.init_state(ref)
    log(f"{tag}: backend {plan.backend}, history store "
        f"{state.histories.bytes():,} bytes "
        f"({len(state.histories.tables)} tables)")

    batch_rows = []
    for b in range(3):
        nodes = np.asarray(plan.batches.batch_nodes[b])
        batch_rows.append(nodes[np.asarray(plan.batches.batch_mask[b])])

    compiled = set()

    def both(b, precision, tol):
        """Batch b on both backends; compare loss and pushed rows."""
        nonlocal state, ref_state
        first = "(compile + run) " if precision not in compiled else ""
        compiled.add(precision)
        with jax.default_matmul_precision(precision):
            (state, m), secs = timed(R.train_step, plan, state,
                                     plan.batch(b))
            (ref_state, ref_m), ref_secs = timed(R.train_step, ref,
                                                 ref_state, ref.batch(b))
        loss, ref_loss = float(m["loss"]), float(ref_m["loss"])
        at = f"step {b} at {precision or 'default'} precision"
        log(f"{tag}: {at} {first}{secs:.3f} s on {plan.backend}, "
            f"{ref_secs:.3f} s on jnp; loss {loss:.6f} vs jnp "
            f"{ref_loss:.6f}")
        check(np.isfinite(loss), f"{tag}: {at} loss {loss} is not finite")
        misses.within(f"{at} loss rel err vs jnp",
                      abs(loss - ref_loss) / abs(ref_loss), tol)
        rows = batch_rows[b]
        for ell, ((got, step), (want, _)) in enumerate(
                zip(pushed_rows(state, rows), pushed_rows(ref_state, rows))):
            # int8: the two sides may round a value to adjacent codes, so
            # only the error beyond one quantization step counts
            slack = np.maximum(np.abs(got - want) - step[:, None] * 1.0001,
                               0)
            misses.within(
                f"{at} pushed layer-{ell} rows [{len(rows)}, "
                f"{got.shape[1]}] rel err vs jnp (all "
                f"{rel_err(got, want):.2e})"
                + (", beyond one int8 step" if history_dtype == "int8"
                   else ""),
                float(slack.max() / np.abs(want).max()), tol)
        return loss

    # steps 0 and 1 at float32 precision: step 0 checks the forward, and
    # step 1 runs on the parameters step 0's backward and update made;
    # step 2 runs at the precision of the timed steps below
    misses = Misses(tag)
    losses = [both(0, COMPARE_PRECISION, F32_TOL),
              both(1, COMPARE_PRECISION, F32_TOL)]
    param_err, leaf = max(
        (rel_err(got, want), jax.tree_util.keystr(path))
        for (path, got), want in zip(
            jax.tree_util.tree_leaves_with_path(state.params),
            jax.tree.leaves(ref_state.params)))
    misses.within(f"params after step 1, worst leaf ({leaf}) rel err vs "
                  "jnp", param_err, PARAM_TOL)
    losses.append(both(2, None, BF16_TOL))
    misses.raise_any()

    # steady steps at the default precision
    nb = plan.batches.num_batches
    step_s = []
    for i in range(3, steps + 3):
        (state, m), s = timed(R.train_step, plan, state, plan.batch(i % nb))
        step_s.append(s)
        losses.append(float(m["loss"]))
    log(f"{tag}: steady train_step s {['%.5f' % s for s in step_s]} "
        f"(median {np.median(step_s):.5f})")

    # fused epochs: one jitted lax.scan over every batch each
    ep_loss, ep_s = [], []
    for e in range(epochs):
        t0 = time.perf_counter()
        state, em = R.train_epoch(plan, state, e)
        state = jax.block_until_ready(state)
        ep_s.append(time.perf_counter() - t0)
        ep_loss.append(em["loss"])
    log(f"{tag}: train_epoch ({nb} batches) s {['%.4f' % s for s in ep_s]} "
        f"(first includes compile); mean loss per epoch "
        f"{['%.5f' % v for v in ep_loss]}")
    check(all(np.isfinite(losses + ep_loss)), f"{tag}: non-finite loss")
    check(ep_loss[-1] < ep_loss[0] and ep_loss[-1] < losses[0],
          f"{tag}: loss did not fall ({losses[0]} -> {ep_loss})")
    return plan, state


# ---------------------------------------------------------------------------
# one chip: serving
# ---------------------------------------------------------------------------

def serve_phase(g, spec, plan, state, backend, seed, requests, batch):
    """SLO=0 requests from the trained state vs `full_forward`."""
    import jax
    import numpy as np
    from repro.core import serve as S
    from repro.gnn.model import full_forward
    from repro.launch.serve_gas import assert_matches_full_forward

    splan = S.build_serve_plan(g, spec, S.ServeConfig(staleness_slo=0,
                                                      backend=backend))
    check(splan.backend == plan.backend, f"serve: backend resolved to "
          f"{splan.backend}, not {plan.backend}")
    sstate = S.init_serve_state(splan, state)
    misses = Misses("serve")
    rng = np.random.default_rng(seed + 1)
    lat, served = [], []
    with jax.default_matmul_precision(COMPARE_PRECISION):
        exact, ff_s = timed(jax.jit(full_forward, static_argnums=(1, 5)),
                            sstate.params, spec, plan.x, plan.eval_edges,
                            plan.eval_w, g.num_nodes)
        exact = np.asarray(exact)
        scale = max(1.0, float(np.abs(exact).max()))
        for i in range(requests):
            q = rng.choice(g.num_nodes, size=batch, replace=False)
            t0 = time.perf_counter()
            logits, sstate, diags = S.serve_request(splan, sstate, q)
            lat.append(time.perf_counter() - t0)
            agree = float((np.argmax(logits, -1)
                           == np.argmax(exact[q], -1)).mean())
            log(f"serve: request {i} [{batch}] backend {splan.backend}, "
                f"refreshed {diags['refreshed']:.0f} rows, halo_age_max "
                f"{diags['halo_age_max']:.0f}, {lat[-1]:.4f} s, argmax "
                f"agreement {agree:.4f}")
            check(diags["halo_age_max"] <= 0, "serve: SLO 0 violated")
            misses.within(f"request {i} logits rel err vs full_forward",
                          rel_err(logits, exact[q]), F32_TOL)
            served.append((logits, exact[q]))
    misses.raise_any()
    for logits, want in served:     # elementwise, and the same argmax
        assert_matches_full_forward(logits, want, atol=F32_TOL * scale)
    log(f"serve: at {COMPARE_PRECISION} matmul precision, full_forward "
        f"(compile + run) {ff_s:.3f} s; request s "
        f"{['%.4f' % s for s in lat]} (first includes compile)")


def one_chip(args, rehearse):
    from repro.core.partition import metis_like_partition
    from repro.data.graphs import citation_graph
    from repro.gnn.model import GNNSpec

    import numpy as np

    nodes, parts = graph_size(rehearse)
    backend = "interpret" if rehearse else None
    rng = np.random.default_rng(args.seed)
    t0 = time.perf_counter()
    precision_probe(rng, rehearse)
    vq_kernels(rng, rehearse)
    log(f"kernels: phase s {time.perf_counter() - t0:.2f}")
    spec = GNNSpec(op="gcn", d_in=128, d_hidden=256, num_classes=40,
                   num_layers=3)
    t0 = time.perf_counter()
    g = citation_graph(num_nodes=nodes, avg_degree=10, num_features=128,
                       num_classes=40, seed=args.seed)
    graph_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    part = metis_like_partition(g.indptr, g.indices, parts, seed=args.seed)
    part_s = time.perf_counter() - t0
    log(f"graph: {g.num_nodes} nodes, {len(g.indices)} edges, "
        f"{parts} parts; set-up s: graph {graph_s:.2f}, metis "
        f"{part_s:.2f}")

    steps, epochs = (2, 2) if rehearse else (5, 3)
    for hd in ("f32", "int8"):
        t0 = time.perf_counter()
        plan, state = train_phase(g, spec, backend, hd, part, parts,
                                  args.seed, steps, epochs)
        if hd == "f32":
            fwd = plan.batches.forward
            log(f"plan: {plan.batches.num_batches} batches, max "
                f"{plan.batches.max_b} nodes / {plan.batches.max_h} halo "
                f"rows per batch, forward blocks "
                f"{list(fwd.vals.shape)} ({fwd.vals.nbytes:,} bytes)")
            served = plan, state      # serving binds the f32 store
        log(f"train[{hd}]: phase s {time.perf_counter() - t0:.2f}")
    serve_phase(g, spec, *served, backend, args.seed,
                requests=3, batch=64 if rehearse else 128)


# ---------------------------------------------------------------------------
# four chips: distributed GAS
# ---------------------------------------------------------------------------

def four_chips(args, rehearse):
    """Sharded GAS through the runtime (`GASConfig(ranks=4)`) on a mesh
    of the four chips: the first epoch on the kernel backend against the
    same epoch on jnp (same partition, weights and superstep order), at
    float32 precision, then further epochs must lower the loss."""
    import jax
    import numpy as np
    from repro.core import runtime as R
    from repro.core.partition import metis_like_partition
    from repro.data.graphs import citation_graph
    from repro.gnn.model import GNNSpec

    ranks = 4
    devices = jax.devices()
    check(len(devices) >= ranks, f"--chips 4 needs 4 devices, "
          f"found {len(devices)}")
    backend = "interpret" if rehearse else None
    nodes, _ = graph_size(rehearse)
    parts = 8 if rehearse else 20             # 2 or 5 supersteps an epoch
    spec = GNNSpec(op="gcn", d_in=128, d_hidden=256, num_classes=40,
                   num_layers=3)
    t0 = time.perf_counter()
    g = citation_graph(num_nodes=nodes, avg_degree=10, num_features=128,
                       num_classes=40, seed=args.seed)
    part = metis_like_partition(g.indptr, g.indices, parts, seed=args.seed)

    def plan_on(be):
        return R.build_plan(g, spec, R.GASConfig(
            num_parts=parts, ranks=ranks, backend=be, seed=args.seed),
            part=part, devices=devices[:ranks])

    plan, ref = plan_on(backend), plan_on("jnp")
    check(plan.backend == ("interpret" if rehearse else "pallas"),
          f"dist: backend resolved to {plan.backend}, not pallas")
    dist = plan.batch_stack
    log(f"dist: {g.num_nodes} nodes, {len(g.indices)} edges, {parts} parts "
        f"on {ranks} chips, {dist.slots.shape[1]} supersteps an epoch, "
        f"{dist.rows} rows a shard, {dist.send_idx.shape[-1]} rows a peer; "
        f"set-up {time.perf_counter() - t0:.2f} s")
    state, ref_state = R.init_state(plan), R.init_state(ref)

    misses = Misses("dist")
    with jax.default_matmul_precision(COMPARE_PRECISION):
        (state, m), secs = timed(R.train_epoch, plan, state, 0)
        (ref_state, ref_m), ref_secs = timed(R.train_epoch, ref, ref_state,
                                             0)
    log(f"dist: epoch 0 at {COMPARE_PRECISION} precision (compile + run) "
        f"{secs:.3f} s on {plan.backend}, {ref_secs:.3f} s on jnp; loss "
        f"{m['loss']:.6f} vs jnp {ref_m['loss']:.6f}")
    misses.within("epoch 0 loss rel err vs jnp",
                  abs(m["loss"] - ref_m["loss"]) / abs(ref_m["loss"]),
                  F32_TOL)
    misses.within("epoch 0 parameters rel err vs jnp", max(
        rel_err(a, b) for a, b in zip(
            jax.tree_util.tree_leaves(state.params),
            jax.tree_util.tree_leaves(ref_state.params))), PARAM_TOL)
    for ell, (a, b) in enumerate(zip(state.histories.tables,
                                     ref_state.histories.tables)):
        misses.within(f"epoch 0 layer-{ell} history rows rel err vs jnp",
                      rel_err(np.asarray(a)[plan.node_rows],
                              np.asarray(b)[ref.node_rows]), F32_TOL)
    misses.raise_any()

    losses, epoch_s = [m["loss"]], []
    for e in range(1, 3 if rehearse else 6):
        (state, m), s = timed(R.train_epoch, plan, state, e)
        losses.append(m["loss"])
        epoch_s.append(s)
    log(f"dist: epoch loss {['%.5f' % v for v in losses]}; s "
        f"{['%.4f' % s for s in epoch_s]} (the first compiles at the "
        "default precision)")
    check(all(np.isfinite(losses)) and losses[-1] < losses[0],
          f"dist: training loss did not fall: {losses}")


def main(argv=None) -> int:
    args = parse_args(argv)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        if args.chips == 4:
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + " --xla_force_host_platform_device_count=4").strip()
    sys.path.insert(0, str(ROOT / "src"))

    import jax
    from repro.launch.compile_cache import enable_compile_cache

    dev = jax.devices()[0]
    if not args.rehearse and dev.platform != "tpu":
        print(f"chip_smoke: no TPU (platform {dev.platform}); "
              "use --rehearse for a CPU run", file=sys.stderr)
        return 1
    log(f"device: {dev.platform} {dev.device_kind} x {len(jax.devices())}, "
        f"jax {jax.__version__}, compile cache "
        f"{enable_compile_cache()}")

    t0 = time.perf_counter()
    if args.chips == 4:
        four_chips(args, args.rehearse)
    else:
        one_chip(args, args.rehearse)
    log(f"total s {time.perf_counter() - t0:.1f}, peak bytes in use "
        f"{peak_bytes(dev)}")
    if args.rehearse:
        log("rehearsal passed (CPU, interpreted kernels: not a chip run)")
        return 0
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
