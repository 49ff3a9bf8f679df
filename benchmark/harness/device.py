"""The device as jax reports it, the table of peaks, the compile cache."""

from __future__ import annotations

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))


def require_chips(n, rehearsal=False):
    """{"platform", "kind", "count"} as jax reports the devices, or
    None (after a message on stderr) when jax found no accelerator or
    fewer chips than the cell asks for. `rehearsal` lets a CPU through for
    the benchmark's own tests; the result then withholds device metrics."""
    import jax
    devs = jax.devices()
    d = devs[0]
    if d.platform == "cpu" and not rehearsal:
        print(f"benchmark: jax found no accelerator (platform "
              f"{d.platform!r}); nothing was run", file=sys.stderr)
        return None
    if len(devs) < n:
        print(f"benchmark: the cell needs {n} chip(s), jax found "
              f"{len(devs)}; nothing was run", file=sys.stderr)
        return None
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs), "rehearsal": d.platform == "cpu"}


def peaks(kind):
    """Published peaks of one chip. An unknown device is an error."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)["by_device_kind"]
    if kind not in table:
        raise KeyError(f"no peaks recorded for device_kind {kind!r}; add it "
                       f"to benchmark/harness/peaks.json with its source")
    return table[kind]


def setup_compile_cache():
    """The program's helper places the cache (JAX_COMPILATION_CACHE_DIR or
    <checkout>/.jax_cache); the benchmark also caches the programs that
    compile in under a second (decode, small prefill buckets, eager glue),
    which jax's default threshold would compile again in every process."""
    import jax
    from paddle_tpu.framework.compile_cache import setup_compile_cache as s
    path = s()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def memory_peak_bytes(n):
    """Allocator peak on the fullest of the first `n` devices (live arrays;
    a running program's temporaries are not in it), None on a backend that
    reports none."""
    import jax
    peaks_ = [(d.memory_stats() or {}).get("peak_bytes_in_use")
              for d in jax.devices()[:n]]
    peaks_ = [p for p in peaks_ if p is not None]
    return max(peaks_) if peaks_ else None
