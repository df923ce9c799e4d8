"""The four-chip cell `arxiv-gcn-dist4.train` on the CPU: its driver
through the harness on four virtual devices with interpreted kernels,
its per-layer readers on a synthetic four-chip trace, and the control
and a planted fault of its reference at a size a test run can hold.
`bench/run.py` itself refuses the CPU."""
import dataclasses
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from bench import harness, trace
from repro.utils.spans import Recording

CELL = "arxiv-gcn-dist4.train"
# the cell's own settings but a graph the interpreter runs in seconds:
# 8 parts on 4 ranks, 2 supersteps an epoch
SMALL = {"nodes": 1200, "parts": 8, "backend": "interpret"}


def small_config():
    found = harness.load_cell(CELL)
    return {**found["config"], **SMALL}, found


@pytest.mark.slow
def test_result_line_on_four_virtual_devices(tmp_path):
    """Both modes of a run: the end-to-end metrics, then a traced run
    whose program recording holds the plan's exchange counters (a CPU
    trace has no TPU ops, so the device readers find nothing)."""
    code = textwrap.dedent(f"""
        import json, time
        from pathlib import Path
        import jax
        from bench import harness
        bench = harness.load_json(harness.ROOT / "BENCHMARK.json")
        for e in bench["configs"]:
            if e["name"] == "arxiv-gcn-dist4":
                cfg = harness.load_json(harness.ROOT / e["file"])
                cfg.update({SMALL!r})
                p = Path({str(tmp_path)!r}) / "cfg.json"
                p.write_text(json.dumps(cfg))
                e["file"] = str(p)
        seen = {{}}
        result = harness.result

        def spy(found, ctx, *a, **kw):
            prog = ctx["program"]
            seen["counters"] = None if prog is None else prog.counters
            return result(found, ctx, *a, **kw)

        harness.result = spy
        outs = []
        for tr in (False, True):
            with jax.default_matmul_precision("highest"):
                out = harness.run({CELL!r}, 2**31 + 11, 0.3, tr,
                                  time.perf_counter(), bench=bench,
                                  chips_required=False,
                                  trace_dir=Path({str(tmp_path)!r}) / "t")
            outs.append({{"out": out, "counters": seen["counters"]}})
        print(json.dumps(outs))
    """)
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"
    root = str(harness.ROOT)
    env["PYTHONPATH"] = os.pathsep.join([root, os.path.join(root, "src")])
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-4000:]
    plain, traced = json.loads(r.stdout.strip().splitlines()[-1])
    for run in (plain, traced):
        out = run["out"]
        assert out["correct"] is True and out["failed"] == 0
        assert out["device"]["count"] == 4
        assert set(out["checks"]) == {"loss", "grad", "change", "rows"}
    assert {"setup_s", "epoch_s"} <= set(plain["out"]["metrics"])
    assert plain["counters"] is None
    # only the host clock's reader: a CPU trace has no TPU ops
    assert set(traced["out"]["metrics"]) == {"plan_build_s.dist"}
    c = traced["counters"]
    assert c["gas/dist/supersteps"] == 2
    assert c["gas/dist/exchange_rows"] > 0 and c["gas/dist/local_halo_rows"]
    # f32 rows of 256 floats, over the two hidden layers
    assert c["gas/dist/exchange_bytes"] == c["gas/dist/exchange_rows"] * 1024


def four_chip_trace():
    """Per chip one epoch: a 3 ms permute pair, a 1 ms all-reduce, 40 ms
    of aggregation kernels, 10 ms of other work, 46 ms idle."""
    chip = [("collective-permute-start", 0, 1_000_000),
            ("collective-permute-done", 1_000_000, 2_000_000),
            ("all-reduce", 3_000_000, 1_000_000),
            ("jvp_jit_gather_spmm__", 4_000_000, 30_000_000),
            ("transpose_jvp_jit_bcsr_spmm___", 34_000_000, 10_000_000),
            ("jvp_jit_scatter_rows__", 44_000_000, 10_000_000)]
    return trace.Summary(ops=[list(chip) for _ in range(4)])


def ctx(summary, program=None):
    work = {"model_flops": 1e12, "aggregation": [
        {"forward": (1e9, 1e8), "backward": (1e9, 1e8)},
        {"forward": (4e9, 4e8), "backward": (4e9, 4e8)}]}
    return {"trace": summary, "epochs": 1, "window_s": 0.1, "chips": 4,
            "program": program, "work": work,
            "spans": {"plan_build": 33.7},
            "peaks": harness.device_peaks("TPU v5 lite")}


@pytest.mark.parametrize("metric,want", [
    ("collective_ms.dist", 4.0), ("agg_kernel_ms.dist", 40.0),
    ("device_idle_share.dist", 46.0), ("history_push_ms.dist", 10.0),
    # each chip has a permute in flight from 0 to 3 ms
    ("exchange_gb_s.dist", 1.5e9 / (4 * 3e-3) / 1e9),
    ("train_mfu.dist", 100.0 * 1e13 / (4 * 197e12)),
    ("plan_build_s.dist", 33.7)])
def test_dist_readers(metric, want):
    prog = Recording(spans=[], counters={"gas/dist/exchange_bytes": 1.5e9})
    read = harness.load_reader(metric)
    assert read(ctx(four_chip_trace(), prog)) == pytest.approx(want)
    if metric not in ("train_mfu.dist", "plan_build_s.dist"):
        assert read(ctx(None, prog)) is None
    if metric == "exchange_gb_s.dist":
        assert read(ctx(four_chip_trace())) is None


@pytest.mark.parametrize("kernel", ["gather_spmm", "bcsr_spmm"])
def test_dist_rooflines_split_the_whole_graphs_work_over_chips(kernel):
    """A chip does a quarter of the whole graph's aggregation work in the
    kernel time averaged over chips: the one-chip share over 4."""
    c = ctx(four_chip_trace())
    whole = harness.load_reader(f"{kernel}_roofline")(c)
    got = harness.load_reader(f"{kernel}_roofline.dist")(c)
    assert whole > 0 and got == pytest.approx(whole / 4)
    assert harness.load_reader(f"{kernel}_roofline.dist")(ctx(None)) is None


def test_exchange_in_flight_runs_from_start_to_done():
    """Two overlapping permutes (started at 0 and 1 ms, done by 6 and 9
    ms) under a kernel, then a synchronous one of 2 ms: 11 ms in flight,
    whichever start a done belongs to; a chip's lone done at the trace's
    edge counts nothing."""
    mod = harness.load_module("metrics", "exchange_gb_s.dist")
    ms = 1_000_000
    chip = [("collective-permute-start", 0, ms // 10),
            ("collective-permute-start", 1 * ms, ms // 10),
            ("jvp_jit_gather_spmm__", 2 * ms, 3 * ms),
            ("collective-permute-done", 5 * ms, ms),
            ("collective-permute-done", 8 * ms, ms),
            ("collective-permute", 20 * ms, 2 * ms)]
    assert mod.in_flight_ns(chip) == 11 * ms
    assert mod.in_flight_ns([("collective-permute-done", 0, ms)]) == 0
    prog = Recording(spans=[], counters={"gas/dist/exchange_bytes": 2.2e9})
    got = mod.read(ctx(trace.Summary(ops=[chip] * 4), prog))
    assert got == pytest.approx(2.2e9 / (4 * 11e-3) / 1e9)


def test_control_and_half_batch_are_not_correct():
    """At this size the reference one precision below (`passes=3`) reads
    far above what rounding alone gives (the reference from weights one
    float apart), and a reference with half of each part's training nodes
    left out of the loss fails the cell's limits."""
    config, found = small_config()
    op = harness.load_op(config)
    inp = harness.make_inputs(config, 2**31 + 21)
    n = found["traffic"]["check_epochs"]
    want = op.outputs(config, inp, n)
    ctl = harness.compare(op.outputs(config, inp, n, control=True), want,
                          inp.params)
    w0 = inp.params["layers"][0]["w"]
    nudged = {"layers": [dict(lp) for lp in inp.params["layers"]]}
    nudged["layers"][0]["w"] = np.nextafter(w0, np.inf).astype(w0.dtype)
    ulp = harness.compare(
        op.outputs(config, dataclasses.replace(inp, params=nudged), n),
        want, inp.params)
    assert ctl["rows"] > 10 * max(ulp["rows"], 1e-9)
    assert ctl["grad"] > 10 * max(ulp["grad"], 1e-9)
    half = dataclasses.replace(inp, train=inp.train & (
        np.arange(len(inp.train)) % 2 == 0))
    fault = harness.compare(op.outputs(config, half, n), want, inp.params)
    assert not harness.is_correct(harness.checks(fault, config["limits"]), 0)
    assert len(want["batch_loss"][0]) == SMALL["parts"] // config["ranks"]


def test_dropped_exchange_is_not_correct():
    """The fault the exchange between chips is most likely to hide: a
    reference that reads every history row another rank owns as zeros
    (`drop_exchange`) fails the cell's limits against the reference,
    though the check is one epoch from zeroed tables, so only rows pushed
    by the epoch's earlier supersteps differ."""
    config, found = small_config()
    op = harness.load_op(config)
    inp = harness.make_inputs(config, 2**31 + 23)
    n = found["traffic"]["check_epochs"]
    want = op.outputs(config, inp, n)
    same = harness.compare(op.outputs(config, inp, n), want, inp.params)
    fault = harness.compare(op.outputs(config, inp, n, drop_exchange=True),
                            want, inp.params)
    assert harness.is_correct(harness.checks(same, config["limits"]), 0)
    assert not harness.is_correct(harness.checks(fault, config["limits"]),
                                  0), fault
    # the first superstep reads zeroed tables either way
    np.testing.assert_array_equal(
        op.outputs(config, inp, n, drop_exchange=True)["batch_loss"][0][0],
        want["batch_loss"][0][0])
