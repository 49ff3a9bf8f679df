"""One cell of each traffic kind, end to end at a tiny test-only size on
the CPU: the run is correct, counts what it served, and refuses to print a
device metric; without the rehearsal flag it refuses to run at all."""

import json
import os
import subprocess
import sys

import pytest

from conftest import BENCH_DIR, ROOT

ENV = dict(os.environ, JAX_PLATFORMS="cpu")


def _run(args, **kw):
    return subprocess.run([sys.executable, os.path.join(BENCH_DIR, "run.py")]
                          + args, cwd=ROOT, env=ENV, capture_output=True,
                          text=True, timeout=600, **kw)


@pytest.mark.parametrize("cell,trace,counts", [
    ("tiny-gpt.tiny-train", 0, "steps"),
    ("tiny-llama.tiny-chat", 1, "sample"),
    ("tiny-llama.tiny-batch", 0, "completed"),
])
def test_cell_runs_and_withholds_device_metrics(tiny_tree, cell, trace, counts):
    p = _run(["--workload", cell, "--seed", "2147483659", "--seconds", "2",
              "--trace", str(trace), "--benchmark-json", tiny_tree,
              "--allow-cpu-rehearsal"])
    assert p.returncode == 0, p.stderr[-2000:]
    lines = p.stdout.strip().splitlines()
    line = json.loads(lines[-1])
    assert lines[-2].startswith("info ")
    info = json.loads(lines[-2][5:])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and info[counts] > 0
    assert info["compiles_in_window"] == 0
    assert line["rehearsal"] is True and line["metrics"] == {}
    assert line["device"]["platform"] == "cpu"
    assert info["problems"] == []
    if "schedule_digest" in info:
        assert len(info["schedule_digest"]) == 16


def test_no_chip_is_an_error_not_a_cpu_run(tiny_tree):
    p = _run(["--workload", "tiny-gpt.tiny-train", "--seed", "1", "--seconds",
              "1", "--trace", "0", "--benchmark-json", tiny_tree])
    assert p.returncode != 0
    assert p.stdout.strip() == "" and "no accelerator" in p.stderr
