"""Runtime kernel autotuning: measure candidate Pallas configs, cache winners.

reference capability: paddle/phi/kernels/autotune/ — AutoTuneBase
(auto_tune_base.h) times candidate kernels on first use, KernelCallback
cache (cache.h) memoizes the winner per input signature, and
switch_autotune.cc exposes the global toggle; layout autotuning hooks in
eager (fluid/eager/eager_layout_auto_tune.h). The python knob is
paddle.incubate.autotune.set_config.

TPU-native design: the tunables are Pallas grid/block shapes (block_q,
block_k for flash attention — the VMEM-tiling equivalent of the
reference's algorithm choice). Candidates are compiled and timed ONCE per
(kernel, shape-signature, device) on synthetic inputs, so tuning can run
even while the caller is being jit-traced; the winner is cached
process-wide. Off by default (FLAGS_use_autotune, like the reference's
switch) because timing compiles every candidate.
"""

from __future__ import annotations

import time
from typing import Any, Callable, Sequence

import jax

from ...framework import flags as _flags

__all__ = ["AlgorithmCache", "autotune", "enable_autotune",
           "disable_autotune", "autotune_enabled", "autotune_status"]

_flags.define_flag(
    "use_autotune", False,
    "time candidate Pallas block configs on first use and cache the winner "
    "(reference: FLAGS_use_autotune, phi/kernels/autotune/switch_autotune.cc)")


# per-key candidate->ms spreads from the most recent tuning runs
timing_log: dict = {}


def _backend_failed(site: str, err: Exception):
    """A candidate failed to compile or run on the TPU: never silent.
    Logged with the compiler's message and counted by call site."""
    import warnings
    warnings.warn(
        f"attention backend failure at {site}: {type(err).__name__}: "
        f"{str(err)[:2000]}", RuntimeWarning, stacklevel=3)
    from ...observability.catalog import metric as _obs_metric
    _obs_metric("attention_backend_failures_total", site=site).inc()


class AlgorithmCache:
    """Winner cache + hit/miss stats (reference: autotune/cache.h)."""

    def __init__(self):
        self._cache: dict[Any, Any] = {}
        self.hits = 0
        self.misses = 0

    def get(self, key):
        if key in self._cache:
            self.hits += 1
            return self._cache[key]
        self.misses += 1
        return None

    def put(self, key, value):
        self._cache[key] = value

    def clear(self):
        self._cache.clear()
        self.hits = 0
        self.misses = 0

    def __len__(self):
        return len(self._cache)


_global_cache = AlgorithmCache()


def enable_autotune():
    _flags.set_flags({"use_autotune": True})


def disable_autotune():
    _flags.set_flags({"use_autotune": False})


def autotune_enabled() -> bool:
    return bool(_flags.flag_value("use_autotune"))


def autotune_status():
    """reference: switch_autotune.cc AutoTuneStatus."""
    return {"enabled": autotune_enabled(), "size": len(_global_cache),
            "cache_hits": _global_cache.hits,
            "cache_misses": _global_cache.misses}


def _time_once(fn: Callable[[], Any], repeats: int = 2) -> float:
    """Best-of-N wall time of fn() (fn must block until ready)."""
    fn()  # compile + warm up
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def autotune(key, candidates: Sequence[Any], make_runner, default=None,
             repeats: int = 2):
    """Pick the fastest candidate for `key`, caching the winner.

    make_runner(candidate) -> zero-arg callable that executes the kernel
    with that config on synthetic inputs and blocks until ready, or raises
    ValueError for a candidate that does not apply to the shape. A
    candidate the compiler or the device refuses (e.g. VMEM overflow) is
    disqualified with the message logged and counted
    (attention_backend_failures_total{site="autotune"}). Returns
    `default` (or the first candidate) if tuning is disabled; raises if
    every applicable candidate was refused.
    """
    if default is None:
        default = candidates[0]
    if not autotune_enabled():
        return default
    cached = _global_cache.get(key)
    if cached is not None:
        return cached
    best, best_t = default, float("inf")
    timings = {}
    refused = 0
    for cand in candidates:
        try:
            runner = make_runner(cand)
        except ValueError:
            continue  # candidate does not apply to this shape
        try:
            t = _time_once(runner, repeats)
        except Exception as e:  # noqa: BLE001 — any compiler/runtime class
            _backend_failed("autotune", e)
            refused += 1
            continue
        timings[str(cand)] = round(t * 1e3, 3)
        if t < best_t:
            best, best_t = cand, t
    if refused and not timings:
        raise RuntimeError(
            f"autotune {key}: every applicable candidate was refused "
            "(see the attention backend failure warnings above)")
    _global_cache.put(key, best)
    # full spread kept separately (not in the winner cache — it would
    # skew hit/size stats), for offline analysis when baking shipped
    # defaults: close seconds-place timings mean a noise-sensitive winner
    timing_log[key] = timings
    return best


def clear_cache():
    _global_cache.clear()
