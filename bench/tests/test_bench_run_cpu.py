"""A tiny run of the harness's internals on the CPU with interpreted
kernels: the shape of the result line. `bench/run.py` itself refuses the
CPU."""
import json
import time

import pytest

from bench import harness


@pytest.mark.parametrize("cell,trace", [("arxiv-gcn-f32.train", False),
                                        ("arxiv-gcn-int8.train", True)])
def test_result_line(tiny_bench, tmp_path, cell, trace):
    found = harness.load_cell(cell, tiny_bench)
    out = harness.run(cell, 2**31 + 11, 0.5, trace,
                            time.perf_counter(), bench=tiny_bench,
                            chips_required=False,
                            trace_dir=tmp_path / "trace")
    assert list(out)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(out)[-1] == "checks"
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] >= 1
    assert set(out["checks"]) == set(found["config"]["limits"])
    for c in out["checks"].values():
        assert c["value"] <= c["limit"]
    wanted = found["per_layer"] if trace else found["end_to_end"]
    assert set(out["metrics"]) <= {m["name"] for m in wanted}
    if trace:
        # a CPU trace has no TPU ops: device readers find nothing
        assert "plan_build_s" in out["metrics"]
        assert "agg_kernel_ms.train" not in out["metrics"]
        assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
        assert out["device"]["window_s"] > 0
    else:
        assert {"setup_s", "epoch_s"} <= set(out["metrics"])
        assert "breakdown" not in out
    for m in out["metrics"].values():
        assert m["value"] > 0 and m["unit"]
    assert out["device"]["count"] == 1
    json.dumps(out)
    assert not (tmp_path / "trace").exists()


def test_refuses_the_cpu(tiny_bench):
    with pytest.raises(harness.NoChip):
        harness.run("arxiv-gcn-f32.train", 1, 0.1, False, 0.0,
                          bench=tiny_bench)
