"""The trace reduction on a recorded TPU trace of one fused GAS epoch."""
import json
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

from bench import trace

DATA = Path(__file__).parent / "data" / "epoch_trace.json"


def planes():
    raw = json.loads(DATA.read_text())["planes"]
    return [NS(name=p["name"], lines=[
        NS(name=ln["name"], events=[NS(name=n, start_ns=s, duration_ns=d)
                                    for n, s, d in ln["events"]])
        for ln in p["lines"]]) for p in raw]


def test_op_names_drop_number_and_text():
    assert trace.op_name("%jvp_jit_gather_spmm__.18 = f32[2048,256] "
                         "custom-call(s32[16,45] %a)") == \
        "jvp_jit_gather_spmm__"
    assert trace.op_name("%while.5 = (s32[]) while(...)") == "while"


def test_recorded_epoch():
    s = trace.summarize(planes())
    assert s.chips == 1
    # the scan's `while` (159.815 ms) spans the rest and is left out
    assert all(n != "while" for n, _, _ in s.ops[0])
    assert len(s.ops[0]) == 1114
    # two fused gather-SpMM layers, five batches: 71.567 + 71.565 ms
    assert s.op_seconds("gather_spmm") == pytest.approx(0.143133, abs=2e-6)
    assert s.op_seconds("bcsr_spmm") == pytest.approx(0.005155, abs=2e-6)
    assert s.op_seconds("scatter_rows") == pytest.approx(0.002404, abs=2e-6)
    busy = s.busy_s()
    assert 0.155 < busy < 0.159815
    assert s.top_ops(1)[0][0] == "jvp_jit_gather_spmm__"
    gaps = s.idle_gaps(3)
    assert [g[0] for g in gaps] == ["host"] * 3
    assert gaps[0][1] >= gaps[1][1] >= gaps[2][1] > 0


def test_busy_is_a_union_and_gaps_are_named_by_spans():
    ops = [("a", 0, 10), ("b", 5, 10), ("c", 30, 10)]
    s = trace.Summary(ops=[ops], spans=[("bench/epoch", 14, 20)])
    assert s.busy_s() == pytest.approx(25e-9)
    assert s.idle_gaps() == [["bench/epoch", pytest.approx(15e-9)]]
    two = trace.Summary(ops=[ops, [("a", 0, 5)]])
    assert two.busy_s() == pytest.approx(15e-9)
    assert two.op_seconds("a") == pytest.approx(7.5e-9)


def test_only_device_ops_and_bench_spans_are_read():
    ev = NS(name="%x.1 = f32[] add()", start_ns=0, duration_ns=4)
    host = NS(name="bench/epoch", start_ns=0, duration_ns=9)
    other = NS(name="PjitFunction", start_ns=0, duration_ns=9)
    s = trace.summarize([
        NS(name="/device:TPU:0", lines=[NS(name="XLA Ops", events=[ev]),
                                        NS(name="Steps", events=[ev])]),
        NS(name="/device:TPU:0 SparseCore", lines=[
            NS(name="XLA Ops", events=[ev])]),
        NS(name="/host:CPU", lines=[NS(name="python",
                                       events=[host, other])])])
    assert s.ops == [[("x", 0, 4)]]
    assert s.spans == [("bench/epoch", 0, 9)]
