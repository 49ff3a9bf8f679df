"""Per-shape attention backend router.

reference capability: paddle/phi/kernels/autotune/ (per-signature algorithm
choice) + python/paddle/nn/functional/flash_attention.py's
sdp_kernel-style backend selection — generalized into the shape-keyed
dispatch the r5 hardware A/B demanded: the f32-operand flash kernels of
that round lost to dense XLA at most production shapes (fwd 0.71-0.86x
dense at s1024/s2048) and won at others (1.23x at s4096). The rows were
re-measured in PR 27 with the two-level-tile kernels, which win all four
(PERF.md section 6; ROADMAP D4 decides what is left of this module).

Design (three sources, in priority order, every decision carrying
provenance):

1. **Baked ledger** — a versioned on-disk table
   (``attention_ledger.json`` next to this module, or
   ``FLAGS_attention_ledger_path``) written by
   ``tools/bake_flash_blocks.py --ledger`` from real hardware timings
   (``.flash_vs_xla.json``) and end-to-end train A/Bs
   (``.bench_tpu_wins.jsonl``).  End-to-end entries (exact
   batch*heads match) outrank isolated-kernel entries: r5 measured the
   full-pallas backward WINNING end-to-end (0.4261 vs 0.4063 MFU) at the
   535m shape even though isolated timing favored the hybrid — HBM
   pressure from the O(S^2) remat buffer dominates the kernel gap.
   Ledger entries are ignored on a different device_kind.  The ledger
   ranks backends only: the kernels' tiles come from
   ``flash_attention.choose_tiles``, and every Decision carries them with
   the grid steps they give.
2. **Measurement fallback** — on a ledger miss when the live backend is
   a TPU, time flash-vs-dense directly (scan-amortized, like the block
   autotuner). A backend that fails to compile or run is disqualified
   with the compiler's message logged and counted
   (``attention_backend_failures_total``); if neither backend runs the
   error is raised. Only when the live backend is the CPU: a
   deterministic analytic roofline proxy (clearly labeled: a hypothesis,
   not a measurement).
3. **Heuristic** — the legacy seq/head_dim thresholds, only when
   measurement is disabled, or when routing for a TPU from a process
   that has none (tests).

Before the second and third, on a ledger miss: **dense-too-large**. Where
dense attention's float32 scores (batch_heads x seq_q x seq_k) would pass
``_DENSE_SCORES_LIMIT`` (4 GiB, a quarter of a v5e's memory), dense is no
candidate on a TPU: the kernels are chosen, nothing is measured (the
measurement would itself allocate those scores beside whatever the
process already holds) and the decision says why. Causal GQA 32/8 at seq
8192 (8.6 GB of scores) is routed so.

The router covers fwd and bwd independently: fwd=pallas + bwd=xla is the
hybrid (flash forward, dense-remat backward), which round 5 measured
winning at zero-padded head dims (d96) and PR 27's end-to-end A/B at that
shape measured losing (0.4137 against 0.5775 MFU).  ``nn/functional``
attention, the flash custom-vjp backward, ``incubate`` fused ops,
``inference/serving`` prefill, and ``bench.py`` all consult this module,
so a backend choice is made once, per shape, from data — and a re-bake
after a hardware session updates every call site at once.
"""

from __future__ import annotations

import dataclasses
import json
import os
from typing import Any, Optional

from ...framework import flags as _flags

__all__ = ["Decision", "route", "load_ledger", "decision_log",
           "clear_routing_cache", "LEDGER_FORMAT"]

LEDGER_FORMAT = 1

_DEFAULT_LEDGER = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "attention_ledger.json")

_flags.define_flag(
    "attention_router", "auto",
    "per-shape attention backend selection: 'auto' (baked ledger, then "
    "measurement fallback, then heuristic), 'ledger' (ledger or heuristic "
    "only — never measure), 'heuristic' (legacy thresholds; ignores the "
    "ledger)")
_flags.define_flag(
    "attention_ledger_path", "",
    "override path for the baked attention-backend ledger ('' = the "
    "attention_ledger.json shipped next to ops/pallas/attention_router.py)")


@dataclasses.dataclass(frozen=True)
class Decision:
    """One routed choice for an attention shape.

    fwd/bwd: 'pallas' or 'xla'.  fwd=pallas + bwd=xla is the hybrid
    (flash forward, dense-remat backward).  tiles: the
    flash_attention.Tiles the Pallas kernels take at this shape
    (flash_attention.tiles_for_shape, the resolver the kernels' own entry
    points use; (resident, streamed, sub) rows of fa_fwd, fa_bwd_dq,
    fa_bwd_dkv) and grid_steps: each kernel's grid steps in one call, by
    kernel name — whichever backend was chosen, so the count that says
    what the kernels would do is always there to read
    (tests/test_flash_attention.py holds it to the grids of traced
    calls).  source is machine-readable ('ledger-e2e' | 'ledger' |
    'dense-too-large' | 'measured-tpu' | 'proxy' | 'heuristic');
    provenance is the human-readable audit string."""

    fwd: str
    bwd: str
    tiles: Any = None
    grid_steps: Optional[dict] = None
    source: str = "heuristic"
    provenance: str = ""


# --------------------------------------------------------------------------
# ledger loading
# --------------------------------------------------------------------------

_ledger_cache: dict[str, Any] = {}
_route_cache: dict[Any, Decision] = {}
_decision_log: list[tuple] = []


def _ledger_path() -> str:
    return _flags.flag_value("attention_ledger_path") or _DEFAULT_LEDGER


def load_ledger(path: Optional[str] = None):
    """Parse (and cache) the baked ledger; None when absent or when the
    on-disk format version is not the one this code understands (a stale
    table must fail OPEN to the measurement/heuristic path, never
    silently misroute)."""
    path = path or _ledger_path()
    if path in _ledger_cache:
        return _ledger_cache[path]
    doc = None
    try:
        with open(path) as f:
            parsed = json.load(f)
        if isinstance(parsed, dict) and \
                parsed.get("ledger_format") == LEDGER_FORMAT:
            doc = parsed
    except Exception:
        doc = None
    _ledger_cache[path] = doc
    return doc


def clear_routing_cache():
    """Drop cached ledgers and decisions (tests; after re-baking)."""
    _ledger_cache.clear()
    _route_cache.clear()
    _decision_log.clear()


def decision_log():
    """[(key, Decision)] for every distinct shape routed this process —
    bench.py and the serving engine surface these for audit."""
    return list(_decision_log)


def _norm_dtype(dtype) -> str:
    s = str(dtype)
    return s.split(".")[-1].replace("'>", "").replace("<class ", "")


def _device_kind(platform: Optional[str]) -> str:
    if platform is None or platform == "tpu":
        import jax
        if jax.default_backend() == "tpu":
            return jax.devices()[0].device_kind
    return platform or "cpu"


def _match_entries(ledger, bh, sq, sk, d, dtype, causal, device_kind):
    """-> (e2e_entry, isolated_entry) matching this shape (either None).

    End-to-end entries need an exact (seq, head_dim, bh) match — they
    describe one measured train config.  Isolated entries match on
    (seq, head_dim, causal, dtype) with the nearest recorded batch*heads
    (block ranking depends on grid parallelism, so a bh=8 winner is a
    weaker prior for a bh=128 caller — prefer the closest)."""
    if ledger is None or sq != sk:
        return None, None
    if ledger.get("device_kind") and ledger["device_kind"] != device_kind:
        return None, None

    def _ok(e):
        return (e.get("seq") == sq and e.get("head_dim") == d
                and bool(e.get("causal", True)) == bool(causal)
                and e.get("dtype", "bfloat16") == dtype)

    e2e = None
    for e in ledger.get("end_to_end", []):
        if _ok(e) and e.get("bh") == bh:
            e2e = e
            break
    isolated = None
    best_gap = None
    for e in ledger.get("entries", []):
        if not _ok(e):
            continue
        gap = abs((e.get("bh") or 0) - bh)
        if best_gap is None or gap < best_gap:
            isolated, best_gap = e, gap
    return e2e, isolated


def epilogue_fusion_wins(bh: int, sq: int, sk: int, d: int, dtype,
                         causal: bool = True,
                         device_kind: Optional[str] = None) -> bool:
    """Whether the baked ledger marks the fused RMSNorm+residual flash
    epilogue a winner at this shape (entry field `fused_epilogue_wins`,
    written by the bake tool once a hardware A/B measures it). False on
    any miss: the wider fusion is opt-in per measured shape — exactly
    the FlashFuser argument, applied with evidence."""
    dk = device_kind or _device_kind(None)
    _, iso = _match_entries(load_ledger(), bh, sq, sk, d,
                            _norm_dtype(dtype), causal, dk)
    return bool(iso and iso.get("fused_epilogue_wins"))


# --------------------------------------------------------------------------
# measurement fallback
# --------------------------------------------------------------------------

# deterministic roofline constants for the CPU proxy. eff_* are MXU
# utilization fractions: dense pinned to the r5 on-TPU measurement
# (~13.4/197); flash assumes the bf16-operand rewrite reaches the same
# MXU mode as the dense einsum (the whole point of the rewrite) — an
# explicit HYPOTHESIS until hardware numbers exist, and labeled so.
_PROXY = {"peak_flops": 197e12, "eff_dense": 0.068, "eff_flash": 0.068,
          "hbm_bps": 820e9}


def _proxy_ms(kind, bh, sq, sk, d, dtype, causal, backend) -> float:
    """Analytic max(compute, memory) time in ms. Deterministic: pure
    arithmetic on the shape key, no clocks, no randomness."""
    nbytes = 2 if dtype == "bfloat16" else 4
    fwd_flops = 4.0 * bh * sq * sk * d            # QK^T + PV
    io = bh * (sq + 2 * sk) * d * nbytes + bh * sq * d * nbytes
    if kind == "bwd":
        fwd_flops *= 2.5                          # dS, dQ, dK, dV dots
        io *= 2.0
    if backend == "pallas":
        # the causal sweep visits only what the diagonal leaves
        flops = fwd_flops * (0.5 if causal else 1.0)
        t = max(flops / (_PROXY["peak_flops"] * _PROXY["eff_flash"]),
                io / _PROXY["hbm_bps"])
    else:
        # dense materializes the (sq, sk) f32 scores at least once
        # (write + read through softmax); the remat backward pays it
        # again on the recompute
        s2 = bh * sq * sk * 4.0 * (3.0 if kind == "bwd" else 2.0)
        t = max(fwd_flops / (_PROXY["peak_flops"] * _PROXY["eff_dense"]),
                (io + s2) / _PROXY["hbm_bps"])
    return t * 1e3


def _backend_failed(site: str, err: Exception):
    """A TPU attention backend failed to compile or run: never silent.
    Logged with the compiler's message and counted by call site."""
    import warnings
    warnings.warn(
        f"attention backend failure at {site}: {type(err).__name__}: "
        f"{str(err)[:2000]}", RuntimeWarning, stacklevel=3)
    from ...observability.catalog import metric as _obs_metric
    _obs_metric("attention_backend_failures_total", site=site).inc()


def _measure_tpu(bh, sq, sk, d, dtype, causal):
    """Real flash-vs-dense timing on the live TPU (scan-amortized, 8
    iters per dispatch so launch overhead does not rank the candidates).
    Returns {(kind, backend): ms} holding the arms that ran; an arm that
    raised is reported through _backend_failed and left out."""
    import time as _time

    import jax
    import jax.numpy as jnp
    from .flash_attention import (_flash_fwd_bhsd, _flash_bwd_bhsd,
                                  _xla_attention_bhsd)
    tb = min(bh, 64)
    jdt = jnp.bfloat16 if dtype == "bfloat16" else jnp.float32
    q = jnp.zeros((tb, sq, d), jdt)
    k = jnp.zeros((tb, sk, d), jdt)
    v = jnp.zeros((tb, sk, d), jdt)

    def _timed(step):
        @jax.jit
        def loop():
            def body(c, _):
                s = step(q + c)
                return (s * 0).astype(q.dtype), None
            c, _ = jax.lax.scan(body, jnp.zeros((), q.dtype), None,
                                length=8)
            return c
        jax.block_until_ready(loop())   # compile + warm
        best = float("inf")
        for _ in range(2):
            t0 = _time.perf_counter()
            jax.block_until_ready(loop())
            best = min(best, _time.perf_counter() - t0)
        return best / 8 * 1e3

    # o / lse only need the forward's shapes: timing is on zeros
    lse = jnp.zeros((tb, sq), jnp.float32)

    def _pallas_bwd(qq):
        return sum(jnp.sum(x.astype(jnp.float32)) for x in _flash_bwd_bhsd(
            qq, k, v, q, lse, q, causal, 1.0))

    def _dense_grad(qq):
        g = jax.grad(lambda a: jnp.sum(_xla_attention_bhsd(
            a, k, v, causal, 1.0).astype(jnp.float32)))(qq)
        return jnp.sum(g.astype(jnp.float32))

    arms = {
        ("fwd", "pallas"): lambda qq: jnp.sum(
            _flash_fwd_bhsd(qq, k, v, causal, 1.0)[0].astype(jnp.float32)),
        ("fwd", "xla"): lambda qq: jnp.sum(
            _xla_attention_bhsd(qq, k, v, causal, 1.0).astype(jnp.float32)),
        ("bwd", "pallas"): _pallas_bwd,
        ("bwd", "xla"): _dense_grad,
    }
    out = {}
    for (kind, backend), step in arms.items():
        try:
            out[(kind, backend)] = _timed(step)
        except Exception as e:  # noqa: BLE001 — any compiler/runtime class
            _backend_failed(f"measure_{kind}_{backend}", e)
    return out


# dense attention holds batch_heads x seq_q x seq_k float32 scores; past
# this many bytes it is not a candidate on a TPU (a quarter of a v5e's HBM)
_DENSE_SCORES_LIMIT = 4 * 2 ** 30


def _heuristic(bh, sq, sk, d) -> str:
    """The legacy _use_pallas thresholds (calibrated to the r4/r5
    f32-operand kernels; kept only as the last-resort fallback)."""
    if d % 128 == 0:
        return "pallas" if sq >= 1024 else "xla"
    return "pallas" if (d >= 96 and sq >= 2048) else "xla"


# --------------------------------------------------------------------------
# the router
# --------------------------------------------------------------------------

def route(batch_heads: int, seq_q: int, seq_k: int, head_dim: int, dtype,
          causal: bool, platform: Optional[str] = None,
          device_kind: Optional[str] = None) -> Decision:
    """Resolve the attention backend for one shape key.

    batch_heads = batch * num_query_heads (the flash grid's parallel
    axis).  platform/device_kind default to the live jax backend; tests
    pass them explicitly to route for a device they are not running on.
    Decisions are cached per (key, ledger path, mode and autotune flags)."""
    dtype = _norm_dtype(dtype)
    mode = _flags.flag_value("attention_router")
    dk = device_kind or _device_kind(platform)
    plat = platform or ("tpu" if dk.lower().startswith("tpu") else "cpu")
    from .autotune import autotune_enabled   # it changes Decision.tiles
    key = (batch_heads, seq_q, seq_k, head_dim, dtype, bool(causal),
           plat, dk, _ledger_path(), mode, autotune_enabled())
    hit = _route_cache.get(key)
    if hit is not None:
        return hit

    dec = None

    if mode != "heuristic":
        led = load_ledger()
        e2e, iso = _match_entries(led, batch_heads, seq_q, seq_k, head_dim,
                                  dtype, causal, dk)
        if e2e is not None:
            dec = Decision(
                fwd=e2e.get("fwd", "pallas"), bwd=e2e.get("bwd", "pallas"),
                source="ledger-e2e",
                provenance=(
                    f"ledger v{led.get('version')} r{led.get('round')} "
                    f"end-to-end [{e2e.get('config')}] on "
                    f"{led.get('device_kind')}: fwd={e2e.get('fwd')} "
                    f"bwd={e2e.get('bwd')} ({e2e.get('note', 'measured')})"))
        elif iso is not None:
            dec = Decision(
                fwd=iso.get("fwd", "pallas"), bwd=iso.get("bwd", "pallas"),
                source="ledger",
                provenance=(
                    f"ledger v{led.get('version')} r{led.get('round')} "
                    f"measured on {led.get('device_kind')} at bh="
                    f"{iso.get('bh')}: fwd={iso.get('fwd')} "
                    f"({json.dumps(iso.get('fwd_ms', {}))}) "
                    f"bwd={iso.get('bwd')} "
                    f"({json.dumps(iso.get('bwd_ms', {}))})"))

    scores = 4 * batch_heads * seq_q * seq_k
    if dec is None and plat == "tpu" and scores > _DENSE_SCORES_LIMIT:
        dec = Decision(
            fwd="pallas", bwd="pallas", source="dense-too-large",
            provenance=(f"no ledger row; dense attention would hold "
                        f"{scores / 2 ** 30:.1f} GiB of float32 scores "
                        f"(limit {_DENSE_SCORES_LIMIT / 2 ** 30:.0f} GiB): "
                        "the flash kernels, nothing measured"))

    if dec is None and mode == "auto":
        import jax
        live_tpu = jax.default_backend() == "tpu"
        if plat == "tpu" and live_tpu:
            ms = _measure_tpu(batch_heads, seq_q, seq_k, head_dim, dtype,
                              causal)
            ran = {kind: [b for b in ("pallas", "xla") if (kind, b) in ms]
                   for kind in ("fwd", "bwd")}
            if not ran["fwd"] or not ran["bwd"]:
                raise RuntimeError(
                    f"no attention backend ran on {dk} for shape "
                    f"{key[:6]} (see the attention backend failure "
                    "warnings above)")
            fwd = min(ran["fwd"], key=lambda b: ms[("fwd", b)])
            bwd = min(ran["bwd"], key=lambda b: ms[("bwd", b)])
            dec = Decision(
                fwd=fwd, bwd=bwd, source="measured-tpu",
                provenance=("measured live on "
                            f"{dk} (ledger miss): "
                            + json.dumps({f"{k[0]}_{k[1]}": round(v, 3)
                                          for k, v in ms.items()})))
        elif plat != "tpu" and not live_tpu:
            est = {(k, b): _proxy_ms(k, batch_heads, seq_q, seq_k,
                                     head_dim, dtype, causal, b)
                   for k in ("fwd", "bwd") for b in ("pallas", "xla")}
            fwd = min(("pallas", "xla"), key=lambda b: est[("fwd", b)])
            bwd = min(("pallas", "xla"), key=lambda b: est[("bwd", b)])
            dec = Decision(
                fwd=fwd, bwd=bwd, source="proxy",
                provenance=("analytic roofline proxy (CPU backend; "
                            "NOT a measurement — assumes the bf16-operand "
                            "kernels reach dense-einsum MXU efficiency): "
                            + json.dumps({f"{k[0]}_{k[1]}": round(v, 3)
                                          for k, v in est.items()})))

    if dec is None:
        b = _heuristic(batch_heads, seq_q, seq_k, head_dim)
        dec = Decision(fwd=b, bwd="pallas", source="heuristic",
                       provenance=("legacy seq/head_dim thresholds "
                                   "(calibrated to the retired f32-operand "
                                   "kernels; no ledger entry, no "
                                   "measurement on this backend)"))

    # what the flash kernels take at this shape, from the resolver their
    # own entry points use
    from .flash_attention import tiles_for_shape
    tiles = tiles_for_shape(batch_heads, seq_q, seq_k, head_dim, dtype,
                            causal)
    dec = dataclasses.replace(
        dec, tiles=tiles,
        grid_steps=tiles.grid_steps(batch_heads, seq_q, seq_k))
    _route_cache[key] = dec
    _decision_log.append((key[:6], dec))
    del _decision_log[:-256]  # bound the audit log
    try:
        # the structured successor of the audit list: every FRESH decision
        # (cache hits excluded) counted by source, exported with the rest
        # of the registry — bench rows and the serving engine read these
        from ...observability.catalog import metric as _obs_metric
        _obs_metric("attention_router_decisions_total",
                    source=dec.source).inc()
    except Exception:  # noqa: BLE001 — routing must never fail on telemetry
        pass
    return dec
