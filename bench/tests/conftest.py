import os
import sys
from pathlib import Path

# CPU only: the benchmark's internals run here with interpreted kernels
os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

import json  # noqa: E402

import pytest  # noqa: E402

# a graph small enough for interpreted kernels; every other setting is
# the cell's own
TINY = {"nodes": 600, "parts": 3, "backend": "interpret"}


@pytest.fixture(scope="session")
def tiny_bench(tmp_path_factory):
    """BENCHMARK.json with each configuration cut to TINY, in files of a
    temporary directory."""
    from bench import harness
    bench = harness.load_json(ROOT / "BENCHMARK.json")
    d = tmp_path_factory.mktemp("configs")
    for entry in bench["configs"]:
        cfg = harness.load_json(ROOT / entry["file"])
        cfg.update(TINY)
        path = d / f"{entry['name']}.json"
        path.write_text(json.dumps(cfg))
        entry["file"] = str(path)
    return bench
