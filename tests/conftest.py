"""Test env: force CPU backend with 8 virtual devices so sharding/mesh tests
run without TPU hardware (chip runs go through the chip tool and
chip_smoke.py). Tier-1 sets JAX_PLATFORMS=cpu, which jax honours; the
jax.config override below keeps a bare `pytest` on a machine with a chip
on the CPU too, and must land before any backend is used.
"""

import os

# tests run the PIR structural verifier after capture AND after every
# enabled pass (prod default is "boundary"): any pass producing
# malformed IR fails loudly here instead of degrading silently
os.environ.setdefault("FLAGS_pir_verify", "on")

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: interpreter-heavy cases excluded from tier-1's "
        "-m 'not slow' run (full production shapes; run on demand)")
    config.addinivalue_line(
        "markers", "chaos: fault-injection drills (resilience subsystem). "
        "Deterministic and fast, so they ride tier-1; select just them "
        "with -m chaos, or exclude with -m 'not chaos' if a platform's "
        "signal/timing semantics misbehave")


def measured_leaks(body, module_file, attempts=3):
    """tracemalloc disabled-noop guard, flake-hardened for in-suite runs.

    In a warm many-hundred-test process, GC cycles and leftover daemon
    threads can allocate inside the watched module during the trace
    window, so a single measurement can report a phantom leak. Only a
    leak that reproduces on every attempt is the fast path actually
    allocating. `body` is the hot loop; `module_file` the filename
    fragment allocations are attributed to (e.g. "metrics.py").
    """
    import gc
    import tracemalloc
    last = None
    for _ in range(attempts):
        gc.collect()
        tracemalloc.start()
        snap1 = tracemalloc.take_snapshot()
        body()
        snap2 = tracemalloc.take_snapshot()
        tracemalloc.stop()
        last = [s for s in snap2.compare_to(snap1, "filename")
                if module_file in (s.traceback[0].filename or "")
                and s.size_diff > 0]
        if not last:
            return []
    return last
