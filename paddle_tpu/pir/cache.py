"""Persistent compile cache: on-disk serialized programs.

reference capability: the reference's PIR serialize/deserialize
(paddle/fluid/pir/serialize_deserialize/) + inference program caching.
TPU-native design: the artifact is a serialized ``jax.export.Exported``
(StableHLO) of the post-pass program — warm starts skip the pass
pipeline's output re-lowering and XLA compilation entirely (round 5
showed ≥700M configs historically dying at exactly that step).

Contract (RESILIENCE.md discipline):

* artifacts are sha256-verified on read; any mismatch / truncation /
  bad magic raises the TYPED ``CompileCacheCorruptionError`` and the
  pipeline falls back to a fresh compile, counting
  ``compile_cache_corrupt_total`` — corruption can never produce a
  wrong program, only a slower start;
* writes are atomic (tmp + os.replace) and size-cap LRU-evicted
  (``FLAGS_compile_cache_max_bytes``, oldest-read first);
* ``compile.cache_read`` / ``compile.cache_write`` are registered
  fault sites, drilled by tools/chaos_drill.py with the zero-escape
  guarantee.

Layout: ``<dir>/<key>.pirc`` =
``b"PIRC" + u32 header_len + header_json + payload`` where the header
records the payload sha256 and provenance metadata.
"""

from __future__ import annotations

import hashlib
import json
import os
import struct
import threading

__all__ = ["CompileCache", "CompileCacheCorruptionError", "default_cache",
           "cache_key", "stats_snapshot"]

_MAGIC = b"PIRC"
_SUFFIX = ".pirc"

# process-local counters, independent of the observability layer so
# hit/miss can be reported even with metrics disabled
_STATS = {"hit": 0, "miss": 0, "write": 0, "corrupt": 0, "evict": 0,
          "read_error": 0, "write_error": 0}
_STATS_LOCK = threading.Lock()


def _bump(k, v=1):
    with _STATS_LOCK:
        _STATS[k] += v


def stats_snapshot() -> dict:
    with _STATS_LOCK:
        return dict(_STATS)


class CompileCacheCorruptionError(RuntimeError):
    """A cached compile artifact failed verification (bad magic, short
    file, or payload sha256 mismatch). Names the offending file."""


def _metric(name, **labels):
    try:
        from ..observability.catalog import metric
        return metric(name, **labels)
    except Exception:  # noqa: BLE001 — cache never fails over metrics
        class _Nop:
            def inc(self, v=1):
                pass

            def set(self, v):
                pass
        return _Nop()


def cache_key(canonical_hash: str, *, sharding: str = "replicated",
              extra: dict = None) -> str:
    """Artifact key: (canonical IR hash, mesh/sharding spec, dtype/flag
    environment, jax version, backend platform, pipeline version) —
    everything that changes the compiled executable. Sharding-aware by
    construction (GSPMD, arxiv 2105.04663: partitioning decisions are
    part of the program identity)."""
    import jax

    from ..framework import flags as _flags
    from .passes import PIPELINE_VERSION

    def flag(k):
        # some flags register lazily on their module's import;
        # unregistered reads key as None
        try:
            return _flags.flag_value(k)
        except KeyError:
            return None

    env = {
        "ir": canonical_hash,
        "sharding": sharding,
        "jax": jax.__version__,
        "platform": jax.default_backend(),
        "pipeline": PIPELINE_VERSION,
        "flags": {k: flag(k) for k in (
            "matmul_precision", "use_bfloat16_matmul",
            "flash_attention_backend", "pir_passes")},
    }
    if extra:
        env["extra"] = {k: str(v) for k, v in sorted(extra.items())}
    text = json.dumps(env, sort_keys=True, default=str)
    return hashlib.sha256(text.encode()).hexdigest()


class CompileCache:
    def __init__(self, directory: str, max_bytes: int = 1 << 28):
        self.dir = directory
        self.max_bytes = int(max_bytes)
        os.makedirs(directory, exist_ok=True)

    def _path(self, key: str) -> str:
        return os.path.join(self.dir, key + _SUFFIX)

    # -- read ---------------------------------------------------------------
    def get(self, key: str):
        """Return (payload_bytes, meta_dict) or None on miss. Raises
        CompileCacheCorruptionError on a failed verification, OSError-
        family on IO trouble (callers treat both as recompile)."""
        from ..resilience.faults import fault_point
        path = self._path(key)
        if not os.path.exists(path):
            return None
        fault_point("compile.cache_read", path=path)
        with open(path, "rb") as f:
            blob = f.read()
        if len(blob) < 8 or blob[:4] != _MAGIC:
            raise CompileCacheCorruptionError(
                f"compile-cache artifact {path} has a bad header "
                "(magic mismatch)")
        (hlen,) = struct.unpack("<I", blob[4:8])
        if len(blob) < 8 + hlen:
            raise CompileCacheCorruptionError(
                f"compile-cache artifact {path} is truncated")
        try:
            header = json.loads(blob[8:8 + hlen].decode())
        except Exception as e:
            raise CompileCacheCorruptionError(
                f"compile-cache artifact {path} has an unreadable "
                f"header: {e}") from None
        payload = blob[8 + hlen:]
        digest = hashlib.sha256(payload).hexdigest()
        if digest != header.get("sha256"):
            raise CompileCacheCorruptionError(
                f"compile-cache artifact {path} failed sha256 "
                f"verification (have {digest[:12]}, "
                f"recorded {str(header.get('sha256'))[:12]})")
        os.utime(path, None)          # LRU recency = last verified read
        return payload, header.get("meta", {})

    # -- write --------------------------------------------------------------
    def put(self, key: str, payload: bytes, meta: dict = None):
        from ..resilience.faults import fault_point
        path = self._path(key)
        header = json.dumps({
            "sha256": hashlib.sha256(payload).hexdigest(),
            "meta": meta or {},
        }).encode()
        tmp = path + f".tmp.{os.getpid()}"
        fault_point("compile.cache_write", path=path)
        with open(tmp, "wb") as f:
            f.write(_MAGIC)
            f.write(struct.pack("<I", len(header)))
            f.write(header)
            f.write(payload)
        os.replace(tmp, path)
        self._evict()

    def drop(self, key: str):
        try:
            os.unlink(self._path(key))
        except OSError:
            pass

    # -- eviction -----------------------------------------------------------
    def entries(self):
        out = []
        try:
            names = os.listdir(self.dir)
        except OSError:
            return out
        for n in names:
            if not n.endswith(_SUFFIX):
                continue
            p = os.path.join(self.dir, n)
            try:
                st = os.stat(p)
            except OSError:
                continue
            out.append((p, st.st_mtime, st.st_size))
        return out

    def total_bytes(self) -> int:
        return sum(sz for _, _, sz in self.entries())

    def _evict(self):
        """Size-capped LRU: drop least-recently-read artifacts until the
        directory fits max_bytes."""
        ents = self.entries()
        total = sum(sz for _, _, sz in ents)
        _metric("compile_cache_bytes").set(total)
        if total <= self.max_bytes:
            return
        evicted = 0
        for p, _, sz in sorted(ents, key=lambda e: e[1]):
            if total <= self.max_bytes:
                break
            try:
                os.unlink(p)
            except OSError:
                continue
            total -= sz
            evicted += 1
        if evicted:
            _bump("evict", evicted)
            _metric("compile_cache_evict_total").inc(evicted)
            _metric("compile_cache_bytes").set(total)


def default_cache():
    """CompileCache from FLAGS_compile_cache_dir ('' = disabled)."""
    from ..framework import flags as _flags
    d = _flags.flag_value("compile_cache_dir")
    if not d:
        return None
    return CompileCache(d, _flags.flag_value("compile_cache_max_bytes"))
