"""The harness finds cells, configurations, traffic, drivers, ops and
metrics by name, and BENCHMARK.json keeps the benchmark's format."""
import json
import math
import re

import numpy as np
import pytest

from bench import harness

BENCH = harness.load_json(harness.ROOT / "BENCHMARK.json")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_resolves(cell):
    found = harness.load_cell(cell)
    assert found["cell"]["name"] == cell
    cfg = found["config"]
    for key in ("source", "reduced", "assumed", "limits", "control",
                "reference", "matmul_precision"):
        assert key in cfg, key
    assert cfg["limits"] and set(cfg["limits"]) <= set(harness.CHECK_NAMES)
    assert callable(harness.load_module("drivers",
                                        found["traffic"]["kind"]).run)
    op = harness.load_op(cfg)
    for entry in ("init_params", "model_flops", "outputs"):
        assert callable(getattr(op, entry)), entry
    names = {m["name"] for m in found["end_to_end"]}
    assert "setup_s" in names and len(names) >= 2
    assert found["per_layer"]
    for m in found["end_to_end"] + found["per_layer"]:
        assert callable(harness.load_reader(m["name"]))


def test_per_layer_metrics_follow_their_cells():
    bench = json.loads(json.dumps(BENCH))
    bench["workloads"].append({"name": "x.y", "config": "arxiv-gcn-f32",
                               "traffic": "train", "chips": 1, "why": "t"})
    found = harness.load_cell("x.y", bench)
    # a metric listing its cells skips others; one without follows the
    # end-to-end metric it moves
    got = {m["name"] for m in found["per_layer"]}
    assert got == {m["name"] for m in BENCH["per_layer"]
                   if "workloads" not in m}
    bench["per_layer"].append({"name": "setup_s", "moves": "setup_s"})
    found = harness.load_cell("x.y", bench)
    assert [m["name"] for m in found["per_layer"]] == ["setup_s"]
    with pytest.raises(KeyError):
        harness.load_cell("no.such-cell")


def test_format():
    assert BENCH["command"] == ["python3", "bench/run.py"]
    assert BENCH["paths"] == ["bench"]
    seen = set()
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in BENCH[group]:
            assert NAME.match(e["name"]), e["name"]
            assert e["name"] not in seen
            seen.add(e["name"])
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert m["moves"] in {e["name"] for e in BENCH["end_to_end"]}
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    for c in BENCH["configs"]:
        assert (harness.ROOT / c["file"]).is_file()
        cfg = harness.load_json(harness.ROOT / c["file"])
        assert set(c["reduced"]) <= set(cfg) and c["reduced"] == \
            cfg["reduced"]


def test_peaks_are_by_device_kind():
    assert harness.device_peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        harness.device_peaks("TPU v9 imaginary")


def test_new_pieces_are_new_files(tmp_path):
    """A driver, op or metric that a later cell brings is a file of its
    own, found by its name alone."""
    for group, name, body in (("drivers", "serve", "def run(): return 1"),
                              ("ops", "gat", "def outputs(): return 2"),
                              ("metrics", "x_ms.serve", "def read(c): 3")):
        (tmp_path / group).mkdir()
        (tmp_path / group / f"{name}.py").write_text(body)
        assert harness.load_module(group, name, root=tmp_path)
    assert harness.load_module("drivers", "serve", root=tmp_path).run() == 1
    with pytest.raises(KeyError):
        harness.load_module("drivers", "no_such_kind")


def test_reduced_lists_every_departure_from_the_source():
    for c in BENCH["configs"]:
        cfg = harness.load_json(harness.ROOT / c["file"])
        assert set(cfg["reduced"]) == set(cfg["why_reduced"])
        run = {**cfg["optimizer"], **cfg["model"], **cfg}
        for key, published in cfg["published"].items():
            if key in run:
                differs = run[key] != published
                assert differs == (key in cfg["reduced"]), key


@pytest.mark.parametrize("where", ["loss", "m", "params", "table"])
def test_a_nan_anywhere_fails_every_check(where):
    def outputs():
        return {"loss": [1.0], "m": {"w": np.ones(3)},
                "params": {"w": np.ones(3)},
                "tables": [np.ones((4, 2)), np.ones((4, 2))]}

    got, want = outputs(), outputs()
    assert harness.compare(got, want, {"w": np.zeros(3)})["rows"] == 0
    if where == "loss":
        got["loss"] = [math.nan]
    elif where == "table":
        # not the first table: a plain max() would drop it
        got["tables"][1][2, 1] = np.nan
    else:
        got[where]["w"][1] = np.nan
    vals = harness.compare(got, want, {"w": np.zeros(3)})
    assert all(v == math.inf for v in vals.values())
    assert not harness.is_correct(harness.checks(vals, {"rows": 1.0}), 0)
