"""Compile seconds by program and persistent-cache hits and misses, from
jax.monitoring (what the compiler itself reports). Copied from
chip_smoke.py CompileMeter."""

from __future__ import annotations

_BACKEND = "/jax/core/compile/backend_compile_duration"


class CompileMeter:
    def __init__(self):
        import jax
        self.by_program = {}
        self.events = 0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **kw):
        if event == _BACKEND:
            name = kw.get("fun_name", "?")
            self.by_program[name] = self.by_program.get(name, 0.0) + secs
            self.events += 1

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def report(self, top=12):
        """{"seconds_by_program" (largest first), "cache_hits",
        "cache_misses", "backend_compile_events"}."""
        rows = sorted(self.by_program.items(), key=lambda kv: -kv[1])[:top]
        return {"seconds_by_program": {k: round(v, 3) for k, v in rows},
                "backend_compile_s": round(sum(self.by_program.values()), 3),
                "backend_compile_events": self.events,
                "cache_hits": self.hits, "cache_misses": self.misses}
