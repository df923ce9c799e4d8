"""Internals of `bench/run.py`: one cell, one run.

Everything a cell needs is found by name, so a new cell, configuration,
traffic mix, op or metric is a new file and no file here changes:
  * the cell in `BENCHMARK.json` (`workloads`), its configuration file
    (`configs[].file`) and its traffic mix, `bench/traffic/<traffic>.json`,
    a data file of parameters;
  * the driver of the mix's `kind`, `bench/drivers/<kind>.py`, whose
    `run(...)` drives the program through a window and returns the
    result line;
  * the configuration's op, `bench/ops/<model.op>.py`: the run's initial
    weights, the operations of a full-graph pass, and the plain reference
    the check compares the program with;
  * each metric the cell reports, `bench/metrics/<metric>.py`, whose
    `read(ctx)` returns the number or None where it finds nothing to read;
  * the chip's peaks, `bench/peaks.json`, by `device_kind`.
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import math
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Dict, List, Optional

import numpy as np

from bench import graphgen

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
TRACE_DIR = ROOT / ".bench_trace"
CHECK_NAMES = ("loss", "grad", "change", "rows")
# a leaf whose reference gradient is under this share of the median
# leaf's moves by round-off alone under Adam: it is left out of `change`
IDLE_LEAF = 1e-3


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# finding things by name
# ---------------------------------------------------------------------------

def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, bench: Optional[dict] = None) -> dict:
    """The cell `name` with its configuration, traffic and metric entries:
    {"cell", "config", "traffic", "end_to_end", "per_layer"}."""
    bench = bench if bench is not None else load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = load_json(ROOT / entry["file"])
    traffic = load_json(BENCH / "traffic" / f"{cell['traffic']}.json")

    def mine(m):
        return name in m.get("workloads", [name])

    e2e = [m for m in bench["end_to_end"] if mine(m)]
    moved = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if mine(m) and ("workloads" in m or m["moves"] in moved)]
    return {"cell": cell, "config": config, "traffic": traffic,
            "end_to_end": e2e, "per_layer": per_layer}


def load_module(group: str, name: str, root: Path = BENCH):
    """The module `<root>/<group>/<name>.py`, loaded from its file."""
    path = root / group / f"{name}.py"
    if not path.is_file():
        raise KeyError(f"no {group} {name!r}: {path} is missing")
    spec = importlib.util.spec_from_file_location(
        f"bench_{group}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(metric: str):
    return load_module("metrics", metric).read


def load_op(config: dict):
    return load_module("ops", config["model"]["op"])


def device_peaks(kind: str) -> dict:
    table = load_json(BENCH / "peaks.json")["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


def require_chips(chips: int):
    """The chips to run on; raises NoChip off a TPU or short of chips."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devs[0].platform!r})")
    if len(devs) < chips:
        raise NoChip(f"cell needs {chips} chips, JAX found {len(devs)}")
    return devs[:chips]


# ---------------------------------------------------------------------------
# inputs
# ---------------------------------------------------------------------------

@dataclass
class Inputs:
    """The graph, the run's node data and initial weights, all numpy."""
    indptr: np.ndarray
    indices: np.ndarray
    part: np.ndarray
    x: np.ndarray
    y: np.ndarray
    train: np.ndarray
    params: Any
    order_seed: int
    spans: Dict[str, float] = field(default_factory=dict)

    def orders(self, epochs: int) -> List[np.ndarray]:
        """Batch order of epochs 0.. as `runtime.train_epoch` draws it."""
        p = int(self.part.max()) + 1
        return [np.random.default_rng(self.order_seed * 1000 + e)
                .permutation(p) for e in range(epochs)]


@contextlib.contextmanager
def span(spans: Dict[str, float], name: str):
    import jax
    t = time.perf_counter()
    with jax.profiler.TraceAnnotation(f"bench/{name}"):
        yield
    spans[name] = spans.get(name, 0.0) + time.perf_counter() - t


def make_inputs(config: dict, seed: int) -> Inputs:
    spans: Dict[str, float] = {}
    m = config["model"]
    with span(spans, "graph"):
        indptr, indices, part = graphgen.structure(config)
        x, y, train = graphgen.node_data(part, config,
                                         m["features"], m["classes"], seed)
    with span(spans, "weights"):
        params = load_op(config).init_params(seed, config)
    return Inputs(indptr, indices, part, x, y, train, params,
                  order_seed=seed % 2**31, spans=spans)


# ---------------------------------------------------------------------------
# the comparison
# ---------------------------------------------------------------------------

def leaves(tree) -> List[np.ndarray]:
    import jax
    return [np.asarray(a, np.float64) for a in jax.tree_util.tree_leaves(tree)]


def _worst(values) -> float:
    """The largest of `values`; inf where one is not a number."""
    values = [float(v) for v in values]
    return math.inf if any(math.isnan(v) for v in values) else max(values)


def _norm_gap(got: List[np.ndarray], want: List[np.ndarray],
              keep: List[bool]) -> float:
    """Worst leaf of |‖got‖ - ‖want‖| over max(‖want leaf‖, ‖median
    leaf‖)."""
    gn = [np.linalg.norm(a) for a in got]
    wn = [np.linalg.norm(a) for a in want]
    med = float(np.median(wn))
    return _worst(abs(g - w) / max(w, med, 1e-30)
                  for g, w, k in zip(gn, wn, keep) if k)


def compare(got: dict, want: dict, params0) -> Dict[str, float]:
    """The numbers `correct` rests on, program (`got`) against reference
    (`want`):
      loss   largest relative gap of a check epoch's mean loss;
      grad   worst leaf's gap of norms of AdamW's first moment after the
             first epoch (the gradients as the optimizer took them);
      change worst leaf's gap of norms of the parameters' change over the
             check epochs, leaves the reference does not move left out;
      rows   worst history table's relative Frobenius error after them.
    Gaps of norms by leaf are measured against the larger of that leaf's
    and the median leaf's reference norm. Every number is inf where the
    program's losses, moments, parameters or tables hold a value that is
    not finite."""
    lg = _worst(abs(a - b) / max(abs(b), 1e-30)
                for a, b in zip(got["loss"], want["loss"]))
    gm, wm = leaves(got["m"]), leaves(want["m"])
    wnorm = [np.linalg.norm(a) for a in wm]
    keep = [w >= IDLE_LEAF * float(np.median(wnorm)) for w in wnorm]
    grad = _norm_gap(gm, wm, [True] * len(wm))
    p0, gp = leaves(params0), leaves(got["params"])
    change = _norm_gap([a - b for a, b in zip(gp, p0)],
                       [a - b for a, b in zip(leaves(want["params"]), p0)],
                       keep)
    tables = [np.asarray(g, np.float64) for g in got["tables"]]
    rows = _worst(np.linalg.norm(g - w) / max(np.linalg.norm(w), 1e-30)
                  for g, w in zip(tables, want["tables"]))
    finite = (all(math.isfinite(v) for v in got["loss"])
              and all(np.isfinite(a).all() for a in gm + gp + tables))
    vals = {"loss": lg, "grad": grad, "change": change, "rows": rows}
    return {k: (v if finite else math.inf) for k, v in vals.items()}


def checks(values: Dict[str, float], limits: Dict[str, float]) -> dict:
    """The numbers the configuration holds to a limit, each beside it."""
    return {k: {"value": values[k], "limit": lim} for k, lim in limits.items()}


def is_correct(chk: dict, failed: int) -> bool:
    return failed == 0 and all(c["value"] <= c["limit"]
                               for c in chk.values())


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def run(name: str, seed: int, seconds: float, trace: bool, t_start: float,
        *, bench: Optional[dict] = None, chips_required: bool = True,
        trace_dir: Path = TRACE_DIR) -> dict:
    """One run of the cell `name` by its traffic's driver; returns the
    result line as a dict. `chips_required=False` skips the look for a
    TPU (CPU tests)."""
    import jax

    found = load_cell(name, bench)
    driver = load_module("drivers", found["traffic"]["kind"])
    chips = found["cell"]["chips"]
    devs = (require_chips(chips) if chips_required
            else jax.devices()[:chips])
    with jax.default_matmul_precision(found["config"]["matmul_precision"]):
        return driver.run(name, found, devs, seed, seconds, trace, t_start,
                          chips_required=chips_required,
                          trace_dir=Path(trace_dir))


def result(found: dict, ctx: dict, chk: dict, attempted: int, failed: int,
           devs, summary=None) -> dict:
    """The result line: the cell's end-to-end metrics, or with a trace
    summary its per-layer ones, each read by its own reader from `ctx`;
    then the device, the breakdown, and last the checks."""
    ctx = {**ctx, "trace": summary, "chips": len(devs),
           "config": found["config"]}
    wanted = found["per_layer"] if summary is not None else \
        found["end_to_end"]
    metrics = {}
    for m in wanted:
        v = load_reader(m["name"])(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": ctx["peak_bytes"]}
    out = {"correct": is_correct(chk, failed), "attempted": attempted,
           "failed": failed, "metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = summary.busy_s()
        device["window_s"] = ctx["window_s"]
        out["breakdown"] = {"device_ops": summary.top_ops(),
                            "idle_gaps": summary.idle_gaps()}
    out["checks"] = chk
    return out


def peak_bytes(devs) -> int:
    """`peak_bytes_in_use` of the fullest chip."""
    return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs)


def rmtree(path: Path) -> None:
    import shutil
    shutil.rmtree(path, ignore_errors=True)
