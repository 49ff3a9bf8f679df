"""The yardstick's own tests ride tier-1.

`benchmark/` is the repo's only benchmark, and `benchmark/tests` holds its
contract (schedule, statistics, trace reduction, both cells end to end at
tiny sizes). They run here in a process of their own, as their builder
runs them: from the root of the checkout, on the CPU, without this
suite's virtual devices and flags.
"""

import os
import re
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_benchmark_tests_pass():
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "FLAGS_pir_verify")
           and not k.startswith("PYTEST_")}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", "benchmark/tests", "-q",
         "-p", "no:cacheprovider"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=450)
    tail = proc.stdout[-3000:] + proc.stderr[-1000:]
    assert proc.returncode == 0, tail
    counts = dict((word, int(n)) for n, word in re.findall(
        r"(\d+) (passed|failed|error|errors|skipped)", proc.stdout))
    # 38 when this test was written (PR 30), 46 with the Brumby cell's
    # (PR 32, 105 s alone), 56 with the Mellum cell's (PR 34, 75 s alone),
    # 88 with the pass and part readers' (PR 36, 4 s alone; the whole
    # 165 s); a benchmark PR adds, never loses
    assert counts.get("passed", 0) >= 88, tail
    assert set(counts) <= {"passed"}, tail
