"""The benchmark's own tests: CPU only, seconds long. Run them with
    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
They are not part of the repo's tier-1 run (tests/)."""

import json
import os
import shutil
import sys

import pytest

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
for p in (ROOT, BENCH_DIR):
    if p not in sys.path:
        sys.path.insert(0, p)
TINY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "tiny")


@pytest.fixture(scope="session")
def benchmark_json():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.fixture()
def tiny_tree(tmp_path):
    """A benchmark of three dummy cells made of files and entries alone:
    tiny configurations and mixes, every per-layer metric file as
    committed, and the harness's code untouched."""
    base = tmp_path / "bench"
    shutil.copytree(os.path.join(TINY, "configs"), base / "configs")
    shutil.copytree(os.path.join(TINY, "traffic"), base / "traffic")
    shutil.copytree(os.path.join(BENCH_DIR, "layer_metrics"),
                    base / "layer_metrics")
    path = tmp_path / "BENCHMARK.json"
    shutil.copy(os.path.join(TINY, "BENCHMARK.tiny.json"), path)
    return str(path)
