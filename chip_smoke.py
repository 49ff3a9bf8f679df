"""chip_smoke.py — the quickest proof that the system still starts on the chip.

    python chip_smoke.py              one TPU chip: trainer, kernel, server
    python chip_smoke.py --four-chip  the trainer phase on a four-chip host

One process drives the two main paths through the entry points a user calls,
at the full width of models the repo supports, with seeded random weights:

  kernel   the Pallas flash-attention forward/backward compiled for the chip
           (interpret=False) at the trainer's attention shape, against the
           dense reference in float32 "highest" precision
  server   inference.ContinuousBatchingEngine over LlamaForCausalLM at the
           llama_535m widths, a dozen mixed-length requests, two
           of them checked against a plain jax.numpy forward
  trainer  parallel.SpmdTrainer + GPT_SHARDING_RULES over GPTForCausalLM at
           gpt3_1p3b widths with the depth cut to what one chip holds

It fails at once unless jax.devices()[0].platform == "tpu", exits non-zero
if any phase raised, fell back or was skipped, and on success prints as its
last line {"ok": true, "device": {"platform", "kind", "count"}}. The timings
it prints are bring-up facts named with the device they ran on, not
benchmark results. tests/test_chip_smoke.py runs every phase function at
tiny sizes on the CPU so the script cannot rot.
"""

from __future__ import annotations

import dataclasses
import gc
import json
import os
import sys
import time
import traceback

REPO = os.path.dirname(os.path.abspath(__file__))

# ---------------------------------------------------------------------------
# sizes. Widths are the published ones; only depth is cut, here.
# ---------------------------------------------------------------------------

# models/gpt.py gpt3_1p3b: hidden 2048, 16 heads x 128, FFN 8192, vocab 50304,
# seq 2048, 24 layers. Params, grads and both AdamW moments are bf16 here
# (8 B/param), so 24 layers are 10.5 GB before activations and do not leave
# room on a 16 GB chip. Depth is cut to TRAIN_DEPTH (measured: see PERF.md
# "Bring-up on the chip tool"); every width is as published.
TRAIN_WIDTHS = dict(vocab_size=50304, hidden_size=2048, num_attention_heads=16,
                    max_position_embeddings=2048)
TRAIN_DEPTH = 12
TRAIN_BATCH, TRAIN_SEQ, TRAIN_STEPS, TRAIN_LR = 4, 2048, 5, 1e-4
# the four-chip host holds the published depth (mp=2 x ZeRO-2 sharding=2)
FOUR_CHIP_DEPTH = 24
FOUR_CHIP_MESH = dict(mp=2, sharding=2)
# first-step loss, four chips vs one, same weights and batch: bf16 partial
# sums are reduced in a different order across the mp shards, nothing else
FOUR_CHIP_LOSS_TOL = 0.02

# llama_535m (the model the engine reads parameter names of): all 8 layers
# fit beside the pool, so nothing is cut.
SERVE_CONFIG = dict(vocab_size=32000, hidden_size=2048, intermediate_size=5504,
                    num_hidden_layers=8, num_attention_heads=16,
                    max_position_embeddings=2048)
# deployment-size pool: 3072 blocks x 16 tokens x 64 KiB/token (8 layers x
# K,V x 16 heads x 128 x bf16) = 3 GiB; 24 lanes x 128 blocks is exactly a
# full batch of 2048-token sequences. Prefill does not donate the pool, and
# about three copies of it are alive while prompts prefill back to back
# (measured, PERF.md), so 3 GiB is what fits beside the weights with room
# to spare. Everything else is the engine default.
SERVE_ENGINE = dict(num_blocks=3072, max_batch=24, max_blocks_per_seq=128)
# mixed prompt lengths; 1100, 1300 and 1500 exceed the largest prefill
# bucket (1024), so chunked prefill runs
SERVE_PROMPTS = (40, 100, 200, 400, 900, 1100, 1500, 64, 330, 1024, 1300, 700)
SERVE_NEW_TOKENS = 32
SERVE_CHECK = (0, 5)   # one single-chunk request, one chunked
# Engine tokens vs the float32 reference, on logits: at every generated
# position the reference logit of the engine's token must be within this
# many reference-logit standard deviations of the reference maximum. bf16
# weights and activations move a logit by about 1% of that spread; a cache
# or matmul in a lower precision than bf16 moves it by several times more.
SERVE_LOGIT_TOL = 0.05

# flash kernel vs dense float32: outputs are O(1) and bf16 carries 8 bits, so
# 3e-2 absolute on the output and 3e-2 of the largest reference gradient
KERNEL_OUT_TOL = 3e-2
KERNEL_GRAD_TOL = 3e-2


def log(msg):
    print(f"[chip_smoke] {msg}", flush=True)


# ---------------------------------------------------------------------------
# compile accounting (jax.monitoring: what the compiler itself reports)
# ---------------------------------------------------------------------------

class CompileMeter:
    """Backend-compile seconds by program and persistent-cache hits/misses,
    from jax's own monitoring events."""

    _BACKEND = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.by_program = {}
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **kw):
        if event == self._BACKEND:
            name = kw.get("fun_name", "?")
            self.by_program[name] = self.by_program.get(name, 0.0) + secs

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def total(self):
        return sum(self.by_program.values())

    def since(self, before):
        """{program: seconds} compiled since the `dict(by_program)` copy."""
        return {k: round(v - before.get(k, 0.0), 2)
                for k, v in self.by_program.items()
                if v - before.get(k, 0.0) > 0.005}


def _peak_bytes(device):
    stats = device.memory_stats()
    return None if not stats else stats.get("peak_bytes_in_use")


def _gib(n):
    return "not reported" if n is None else f"{n / 2**30:.2f} GiB"


# ---------------------------------------------------------------------------
# device
# ---------------------------------------------------------------------------

def device_report():
    """First lines of the run: what jax found, and the installation."""
    import jax
    import jaxlib
    d = jax.devices()[0]
    info = {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}
    try:
        import libtpu
        libtpu_version = libtpu.__version__
    except ImportError:
        libtpu_version = "not installed"
    log(f"platform={info['platform']} device_kind={info['kind']!r} "
        f"count={info['count']}")
    log(f"jax={jax.__version__} jaxlib={jaxlib.__version__} "
        f"libtpu={libtpu_version}")
    return info


# ---------------------------------------------------------------------------
# kernel phase
# ---------------------------------------------------------------------------

def _compare(name, got, want):
    """Flash (out, dq, dk, dv) against the float32 reference: max absolute
    error of the output, max error of each gradient relative to the largest
    reference gradient. Raises past the tolerances."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def errs(got, want):
        diff = [jnp.max(jnp.abs(a.astype(jnp.float32) - b))
                for a, b in zip(got, want)]
        return diff[0], [x / jnp.maximum(jnp.max(jnp.abs(b)), 1e-6)
                         for x, b in zip(diff[1:], want[1:])]

    out_err, grad_errs = errs(tuple(got), tuple(want))
    out_err = float(out_err)
    grad_err = dict(zip(("dq", "dk", "dv"), map(float, grad_errs)))
    if not out_err <= KERNEL_OUT_TOL:
        raise AssertionError(f"{name}: flash forward differs from the dense "
                             f"reference by {out_err} > {KERNEL_OUT_TOL}")
    bad = {n: e for n, e in grad_err.items() if not e <= KERNEL_GRAD_TOL}
    if bad:
        raise AssertionError(f"{name}: flash backward differs from the "
                             f"dense reference: {bad} > {KERNEL_GRAD_TOL}")
    return {"case": name, "out_max_abs_err": out_err,
            "grad_max_rel_err": grad_err}


def _attention_case(name, bh, bh_kv, seq, d, tiles, interpret, seed):
    """One flash fwd+bwd (bf16, causal) against the dense float32 reference.
    tiles: a flash_attention.Tiles, or None for the chooser's. Returns the
    max errors; raises if they exceed the tolerances."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.ops.pallas import flash_attention as fa

    rs = np.random.RandomState(seed)
    q = jnp.asarray(rs.randn(bh, seq, d), jnp.bfloat16)
    k = jnp.asarray(rs.randn(bh_kv, seq, d), jnp.bfloat16)
    v = jnp.asarray(rs.randn(bh_kv, seq, d), jnp.bfloat16)
    g = jnp.asarray(rs.randn(bh, seq, d), jnp.bfloat16)
    scale = 1.0 / d ** 0.5
    rep = bh // bh_kv
    if tiles is None:
        tiles = fa.choose_tiles(seq, seq, d, 2)

    @jax.jit
    def flash_fwd_bwd(q_, k_, v_, g_):
        out, lse = fa._flash_fwd_bhsd(q_, k_, v_, True, scale, tiles=tiles,
                                      interpret=interpret, q_per_kv=rep)
        return (out,) + tuple(fa._flash_bwd_bhsd(
            q_, k_, v_, out, lse, g_, True, scale, tiles=tiles,
            interpret=interpret, q_per_kv=rep))

    t0 = time.perf_counter()
    got = jax.block_until_ready(flash_fwd_bwd(q, k, v, g))
    wall = time.perf_counter() - t0

    def dense(q_, k_, v_):
        # GQA: the reference sees each kv head repeated over its group
        k_ = jnp.repeat(k_, rep, axis=0)
        v_ = jnp.repeat(v_, rep, axis=0)
        return fa._xla_attention_bhsd(q_, k_, v_, True, scale)

    @jax.jit
    def dense_fwd_bwd(q_, k_, v_, g_):
        with jax.default_matmul_precision("highest"):
            ref_, pull = jax.vjp(dense, *(a.astype(jnp.float32)
                                          for a in (q_, k_, v_)))
            return (ref_,) + pull(g_.astype(jnp.float32))

    # heads are independent: the reference runs a few kv heads at a time so
    # its O(S^2) float32 buffers stay small beside the 16 GB of the chip
    step = max(1, min(bh_kv, 8 // rep))
    parts = [dense_fwd_bwd(q[i * rep:(i + step) * rep], k[i:i + step],
                           v[i:i + step], g[i * rep:(i + step) * rep])
             for i in range(0, bh_kv, step)]
    want = parts[0] if len(parts) == 1 else tuple(
        jnp.concatenate(x) for x in zip(*parts))

    res = _compare(name, got, want)
    steps = tiles.grid_steps(bh, seq, seq)
    res.update(bh=bh, bh_kv=bh_kv, seq=seq, head_dim=d,
               tiles={"fwd": tiles.fwd, "dq": tiles.dq, "dkv": tiles.dkv},
               grid_steps=steps, first_call_s=round(wall, 2))
    log(f"kernel {name}: tiles (resident, streamed, sub) fwd={tiles.fwd} "
        f"dq={tiles.dq} dkv={tiles.dkv} grid steps {steps} "
        f"out_err={res['out_max_abs_err']:.4f} "
        + " ".join(f"{n}_err={e:.4f}"
                   for n, e in res["grad_max_rel_err"].items())
        + f" first_call (compile + run) {wall:.2f}s")
    return res


def _pad96_case(b, seq, h):
    """head_dim 96 through the public wrapper, which zero-pads to 128."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.nn.functional.attention import _xla_attention
    from paddle_tpu.ops.pallas import flash_attention as fa

    rs = np.random.RandomState(96)
    q, k, v, g = (jnp.asarray(rs.randn(b, seq, h, 96), jnp.bfloat16)
                  for _ in range(4))

    @jax.jit
    def flash(q_, k_, v_, g_):
        out, pull = jax.vjp(
            lambda a, b_, c: fa.flash_attention_bshd(a, b_, c, causal=True),
            q_, k_, v_)
        return (out,) + pull(g_)

    @jax.jit
    def dense(q_, k_, v_, g_):
        with jax.default_matmul_precision("highest"):
            ref, pull = jax.vjp(
                lambda a, b_, c: _xla_attention(a, b_, c, causal=True),
                *(a.astype(jnp.float32) for a in (q_, k_, v_)))
            return (ref,) + pull(g_.astype(jnp.float32))

    got = jax.block_until_ready(flash(q, k, v, g))
    res = _compare("d96_zero_pad", got, dense(q, k, v, g))
    log(f"kernel d96_zero_pad: out_err={res['out_max_abs_err']:.4f} "
        + " ".join(f"{n}_err={e:.4f}"
                   for n, e in res["grad_max_rel_err"].items()))
    return res


def _epilogue_case(b, seq, h, d):
    """The fused rmsnorm(attn + residual) * gamma flush: its (8, d) gamma
    block is the one block spec no other kernel has."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.nn.functional.attention import _xla_attention
    from paddle_tpu.ops.pallas import flash_attention as fa

    rs = np.random.RandomState(8)
    q, k, v, res = (jnp.asarray(rs.randn(b, seq, h, d), jnp.bfloat16)
                    for _ in range(4))
    w = jnp.asarray(rs.rand(d) + 0.5, jnp.bfloat16)

    @jax.jit
    def err(q_, k_, v_, res_, w_):
        out = fa.flash_attention_rms_epilogue_bshd(q_, k_, v_, res_, w_)
        with jax.default_matmul_precision("highest"):
            attn = _xla_attention(*(a.astype(jnp.float32)
                                    for a in (q_, k_, v_)), causal=True)
        hsum = attn + res_.astype(jnp.float32)
        ref = (hsum * jax.lax.rsqrt(jnp.mean(hsum * hsum, -1, keepdims=True)
                                    + 1e-6)) * w_.astype(jnp.float32)
        return jnp.max(jnp.abs(out.astype(jnp.float32) - ref))

    e = float(err(q, k, v, res, w))
    log(f"kernel rms_epilogue: out_err={e:.4f}")
    if not e <= 2 * KERNEL_OUT_TOL:     # one more bf16 rounding (the norm)
        raise AssertionError(f"rms-epilogue flash differs from dense by {e}")
    return {"case": "rms_epilogue", "out_max_abs_err": e}


def _grouped_matmul_case(rows, k, n, groups, tiles=None):
    """`parallel/moe.py grouped_matmul` (bf16; on the chip, at widths with
    an entry in its `_GMM_TILES`, jax's Pallas grouped matmul: a fall-back
    to `lax.ragged_dot` there fails the case) against `lax.ragged_dot`,
    the product and both gradients, on uneven groups, one of them empty,
    that leave the buffer's last rows to no group. tiles: the CPU test's,
    interpreted."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.parallel import moe

    rs = np.random.RandomState(9)
    share = rs.dirichlet(np.full(groups - 1, 8.0)) * rows * 0.6
    sizes = jnp.asarray([0] + [int(s) for s in share], jnp.int32)
    live = (jnp.arange(rows) < jnp.sum(sizes))[:, None]
    lhs = jnp.asarray(rs.randn(rows, k), jnp.bfloat16)
    rhs = jnp.asarray(rs.randn(groups, k, n) * 0.02, jnp.bfloat16)
    cot = jnp.where(live, jnp.asarray(rs.randn(rows, n), jnp.bfloat16), 0)
    if tiles is None and moe._gmm_tiles(lhs, rhs) is None:
        raise AssertionError(f"grouped_matmul fell back to lax.ragged_dot "
                             f"at {lhs.shape} x {rhs.shape}")

    def three(product):
        out, back = jax.vjp(lambda a, b: product(a, b, sizes), lhs, rhs)
        d_lhs, d_rhs = back(cot)
        return [jnp.where(live, out, 0), jnp.where(live, d_lhs, 0), d_rhs]

    ours = moe.grouped_matmul if tiles is None else (
        lambda a, b, s: moe._gmm(a, b, s, tiles, True))
    errs = {}
    for name, got, want in zip(("out", "d_rows", "d_weights"),
                               jax.jit(lambda: three(ours))(),
                               jax.jit(lambda: three(jax.lax.ragged_dot))()):
        got, want = got.astype(jnp.float32), want.astype(jnp.float32)
        errs[name] = float(jnp.max(jnp.abs(got - want))
                           / jnp.max(jnp.abs(want)))
    log(f"kernel grouped_matmul {rows}x{k}x{n}, {groups} groups "
        f"{[int(s) for s in sizes]}: {errs}")
    # two bf16 roundings of float32 sums in different orders
    bad = {name: e for name, e in errs.items() if not e <= KERNEL_GRAD_TOL}
    if bad:
        raise AssertionError(f"grouped_matmul differs from lax.ragged_dot: "
                             f"{bad} > {KERNEL_GRAD_TOL}")
    return {"case": "grouped_matmul", "shape": (rows, k, n, groups),
            "max_rel_err": errs}


def kernel_phase(bh=TRAIN_BATCH * 16, seq=TRAIN_SEQ, d=128, small_seq=512,
                 gqa=(16, 4), interpret=False,
                 grouped=((8192, 2304, 1792, 16), (18432, 4096, 1536, 9),
                          (18432, 768, 4096, 9))):
    """Compile (interpret=False on the chip) and run the bf16 flash kernels
    at the trainer's attention shape with the tiles the chooser hands out,
    then with one-lane-tile sub-blocks inside a streamed tile shorter than
    the sequence (the clamped index maps and the loops' bounds both at
    work), GQA, the d=96 zero-pad and the RMS epilogue once each; then the
    routed experts' grouped matmul at each case of `grouped`: the Mellum
    cell's first product, and the granite cell's two over its buffer of
    18,432 rows."""
    from paddle_tpu.ops.pallas.flash_attention import Tiles

    log(f"kernel phase: bh={bh} seq={seq} d={d} bf16 causal")
    small = (128, min(512, seq), 128)
    cases = [
        _attention_case("chooser_tiles", bh, bh, seq, d, None, interpret, 1),
        _attention_case("small_tiles", bh, bh, seq, d,
                        Tiles(fwd=small, dq=small, dkv=small), interpret, 2),
        _attention_case("gqa", gqa[0], gqa[1], small_seq, d, None, interpret,
                        3),
        _pad96_case(2, small_seq, 4),
        _epilogue_case(2, small_seq, 4, d),
    ] + [_grouped_matmul_case(*case) for case in grouped]
    return {"cases": cases}


# ---------------------------------------------------------------------------
# server phase
# ---------------------------------------------------------------------------

def llama_reference_logits(state, cfg, ids):
    """Plain float32 jax.numpy Llama forward over one token sequence — no
    kernels, no cache, no batching. state: name -> array (any dtype; upcast
    here); ids: (S,) int. Returns (S, vocab) float32 logits. Call under
    jax.default_matmul_precision("highest")."""
    import jax
    import jax.numpy as jnp

    def w(name):
        return jnp.asarray(state[name], jnp.float32)

    def rms(x, g):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                                 + cfg.rms_norm_eps) * g

    s = ids.shape[0]
    nh, nkv = cfg.num_attention_heads, cfg.num_key_value_heads
    hd = cfg.hidden_size // nh
    inv = 1.0 / (cfg.rope_theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32)
                                    / hd))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None]   # (S, 1, hd)
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None]

    def rope(t):                                  # neox rotate-half
        t1, t2 = jnp.split(t, 2, axis=-1)
        return t * cos + jnp.concatenate([-t2, t1], -1) * sin

    causal = jnp.tril(jnp.ones((s, s), bool))
    x = w("llama.embed_tokens.weight")[ids]
    for i in range(cfg.num_hidden_layers):
        p = f"llama.layers.{i}."
        h = rms(x, w(p + "input_layernorm.weight"))
        q = rope((h @ w(p + "self_attn.q_proj.weight")).reshape(s, nh, hd))
        k = rope((h @ w(p + "self_attn.k_proj.weight")).reshape(s, nkv, hd))
        v = (h @ w(p + "self_attn.v_proj.weight")).reshape(s, nkv, hd)
        k = jnp.repeat(k, nh // nkv, axis=1)
        v = jnp.repeat(v, nh // nkv, axis=1)
        sc = jnp.einsum("qhd,khd->hqk", q, k) / hd ** 0.5
        pr = jax.nn.softmax(jnp.where(causal[None], sc, -jnp.inf), axis=-1)
        a = jnp.einsum("hqk,khd->qhd", pr, v).reshape(s, nh * hd)
        x = x + a @ w(p + "self_attn.o_proj.weight")
        h = rms(x, w(p + "post_attention_layernorm.weight"))
        x = x + (jax.nn.silu(h @ w(p + "mlp.gate_proj.weight"))
                 * (h @ w(p + "mlp.up_proj.weight"))
                 ) @ w(p + "mlp.down_proj.weight")
    x = rms(x, w("llama.norm.weight"))
    head = (w("lm_head.weight") if "lm_head.weight" in state
            else w("llama.embed_tokens.weight").T)
    return x @ head


def server_phase(config=None, engine=None, prompts=SERVE_PROMPTS,
                 new_tokens=SERVE_NEW_TOKENS, check=SERVE_CHECK,
                 dtype="bfloat16", meter=None):
    """ContinuousBatchingEngine answers seeded requests of mixed prompt
    length through add_request / run(). Passes when every request ends
    length/eos, nothing was shed, deferred or rejected, no program fell
    back from the PIR pipeline, and the checked requests' tokens agree with
    the float32 reference on logits within SERVE_LOGIT_TOL."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu import observability as obs
    from paddle_tpu.inference import ContinuousBatchingEngine
    from paddle_tpu.inference.loadgen import _counter_total
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    obs.enable()      # the shed / deferral / fallback counters are the check
    compiled_before = dict(meter.by_program) if meter else {}
    cfg = LlamaConfig(**(config or SERVE_CONFIG))
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    jdt = jnp.dtype(dtype)
    for t in model.state_dict().values():
        if jnp.issubdtype(t._data.dtype, jnp.floating):
            t._data = t._data.astype(jdt)
    eng = ContinuousBatchingEngine(model, **(engine or SERVE_ENGINE))
    pool_bytes = int(eng.pool.k.nbytes + eng.pool.v.nbytes)
    log(f"server phase: llama {cfg.num_hidden_layers}L hidden "
        f"{cfg.hidden_size} {jdt.name}, {model.num_params() / 1e6:.0f}M "
        f"params; pool {eng.pool.num_blocks} blocks = {_gib(pool_bytes)}, "
        f"max_batch {eng.max_batch}, buckets {eng.buckets}, "
        f"decode_steps {eng.decode_steps}")
    log(f"server attention: paged prefill/decode attention is jax.numpy "
        f"(ops/paged_attention.py), no Pallas kernel on this path; "
        f"engine.attention_route (audit of the widest prefill bucket) = "
        f"{eng.attention_route}")

    rs = np.random.RandomState(7)
    sent = {}
    for n in prompts:
        p = rs.randint(1, cfg.vocab_size, (n,))
        sent[eng.add_request(p, max_new_tokens=new_tokens)] = p
    counters = ("serving_shed_total", "serving_deferred_total",
                "serving_rejected_total", "pir_fallback_total")
    snap = obs.snapshot()
    before = {c: _counter_total(snap, c) for c in counters}
    t0 = time.perf_counter()
    results = eng.run()
    wall = time.perf_counter() - t0
    snap = obs.snapshot()
    delta = {c: _counter_total(snap, c) - before[c] for c in counters}

    reasons = {rid: eng.finished[rid].finish_reason for rid in sent}
    tokens_out = sum(len(results[rid]) for rid in sent)
    fallbacks = {k: getattr(r, "fallback", "no report")
                 for k, r in eng.compile_reports.items()}
    compiled = meter.since(compiled_before) if meter else {}
    glue = round(sum(v for k, v in compiled.items() if "serving" not in k), 2)
    compiled = {k: v for k, v in compiled.items() if "serving" in k}
    log(f"server: {len(sent)} requests, {tokens_out} tokens out in "
        f"{wall:.2f}s wall (first call of every program included)")
    log(f"server finish reasons: {sorted(set(reasons.values()))}; "
        f"counters since start: {delta}")
    log(f"server programs (PIR fallback stage or None): {fallbacks}")
    log(f"server compile seconds by program: {compiled}; weight init and "
        f"glue ops {glue}s")

    problems = []
    bad = {r: why for r, why in reasons.items()
           if why not in ("length", "eos")}
    if bad:
        problems.append(f"requests did not finish length/eos: {bad}")
    problems += [f"{c} rose by {v}" for c, v in delta.items() if v]
    problems += [f"program {k} fell back at PIR stage {v!r}"
                 for k, v in fallbacks.items() if v is not None]
    if not any(k.startswith("decode") for k in fallbacks) or \
            not any(k.startswith("prefill") for k in fallbacks):
        problems.append(f"missing compile reports: {sorted(fallbacks)}")
    chunked = [n for n in prompts if n > eng.buckets[-1]]
    log(f"server chunked prefill ran for prompts {chunked} "
        f"(largest bucket {eng.buckets[-1]})")

    # reference: teacher-forced float32 forward over prompt + engine tokens
    state = {k: v._data for k, v in model.state_dict().items()}
    reference = jax.jit(lambda st, ids: llama_reference_logits(st, cfg, ids))
    checks = []
    for rid in check:
        prompt, gen = sent[rid], np.asarray(results[rid], np.int32)
        ids = jnp.asarray(np.concatenate([prompt, gen[:-1]]), jnp.int32)
        with jax.default_matmul_precision("highest"):
            logits = np.asarray(reference(state, ids))
        rows = logits[prompt.size - 1:]                 # one per generated
        top = rows.max(-1)
        picked = rows[np.arange(gen.size), gen]
        gap = (top - picked) / rows.std(-1)
        exact = int((rows.argmax(-1) == gen).sum())
        checks.append({"rid": rid, "prompt": int(prompt.size),
                       "tokens": int(gen.size), "argmax_equal": exact,
                       "max_gap_in_logit_std": float(gap.max())})
        log(f"server reference rid={rid} prompt={prompt.size}: "
            f"{exact}/{gen.size} tokens are the reference argmax, worst "
            f"gap {gap.max():.4f} logit-std (tolerance {SERVE_LOGIT_TOL})")
        if not gap.max() <= SERVE_LOGIT_TOL:
            problems.append(f"rid {rid}: engine token differs from the "
                            f"reference by {gap.max():.4f} logit-std")
    # the allocator's peak counts live arrays (weights, pools and their
    # copies) over the whole process; main() runs this phase last, after
    # phases that keep far fewer, so the peak is the server's own
    peak = _peak_bytes(jax.devices()[0])
    log(f"server device memory: allocator peak_bytes_in_use {_gib(peak)} "
        f"with a {_gib(pool_bytes)} pool")
    if problems:
        raise AssertionError("server phase: " + "; ".join(problems))
    return {"requests": len(sent), "tokens_out": tokens_out,
            "wall_s": round(wall, 2), "pool_bytes": pool_bytes,
            "compile_s_by_program": compiled, "reference": checks,
            "peak_bytes_in_use": peak}


# ---------------------------------------------------------------------------
# trainer phase
# ---------------------------------------------------------------------------

def _trainer_attention(batch, heads, seq, head_dim, dtype, batch_split=1,
                       head_split=1):
    """What scaled_dot_product_attention selects for the trainer's causal
    attention: the rule the model asks. Under a mesh the kernel runs per
    shard (flash_attention _mesh_spec), so the tiles and grid steps
    reported are the shard's."""
    from paddle_tpu.ops.pallas.attention_router import route
    dec = route(batch * heads, seq, seq, head_dim, dtype, True)
    if dec.fwd != "pallas":
        return {"forward": "xla_dense", "backward": "xla_autodiff",
                "why": dec.why}
    bh = (batch // batch_split) * (heads // head_split)
    local = route(bh, seq, seq, head_dim, dtype, True)
    return {"forward": "pallas_flash", "backward": local.bwd,
            "why": dec.why, "per_shard_bh": bh,
            "tiles": dataclasses.asdict(local.tiles),
            "grid_steps": local.grid_steps,
            "partitioned_by": ("shard_map over batch and heads"
                               if batch_split * head_split > 1 else None)}


def trainer_phase(widths=None, depth=TRAIN_DEPTH, batch=TRAIN_BATCH,
                  seq=TRAIN_SEQ, steps=TRAIN_STEPS, mesh_axes=None,
                  devices=None, dtype="bfloat16", meter=None):
    """SpmdTrainer + GPT_SHARDING_RULES take `steps` AdamW steps on one
    repeated seeded batch. mesh_axes: create_mesh kwargs (default: a
    one-device mesh). Passes when every loss is finite and the loss fell."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P
    import paddle_tpu as paddle
    from paddle_tpu import optimizer
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    from paddle_tpu.parallel import (GPT_SHARDING_RULES, SpmdTrainer,
                                     create_mesh)

    compile_before = meter.total() if meter else 0.0
    mesh_axes = dict(mesh_axes or {})
    need = int(np.prod(list(mesh_axes.values()) or [1]))
    devices = list(devices or jax.devices())[:need]
    mesh = create_mesh(devices=devices, **mesh_axes)
    zero = mesh_axes.get("sharding", 1) > 1
    cfg = GPTConfig(num_hidden_layers=depth, **(widths or TRAIN_WIDTHS))
    paddle.seed(0)
    model = GPTForCausalLM(cfg)
    n_params = sum(int(np.prod(t.shape)) for t in model.state_dict().values())
    opt = optimizer.AdamW(TRAIN_LR, parameters=model.parameters())
    # the ZeRO axis is a data-parallel axis: the batch is split over it
    data_axes = tuple(a for a in ("sharding", "dp")
                      if mesh_axes.get(a, 1) > 1)
    trainer = SpmdTrainer(model, opt, mesh, GPT_SHARDING_RULES, dtype=dtype,
                          batch_spec=P(data_axes or None),
                          sharding_stage=2 if zero else 0)
    log(f"trainer phase: gpt {depth}L hidden {cfg.hidden_size} "
        f"{cfg.num_attention_heads} heads FFN {cfg.intermediate_size} vocab "
        f"{cfg.vocab_size}, {n_params / 1e6:.0f}M params {dtype}; batch "
        f"{batch} x seq {seq}; mesh {dict(mesh.shape)} on "
        f"{len(devices)} device(s)")
    ids = jnp.asarray(np.random.RandomState(0).randint(
        0, cfg.vocab_size, (batch, seq)), jnp.int32)

    losses, walls = [], []
    for _ in range(steps):
        t0 = time.perf_counter()
        loss = trainer.step((ids, ids))
        loss.block_until_ready()
        walls.append(time.perf_counter() - t0)
        losses.append(float(loss))
    steady = sorted(walls[1:])[len(walls[1:]) // 2] if steps > 1 else None
    compile_s = (meter.total() - compile_before) if meter else None
    log(f"trainer losses: {[round(x, 4) for x in losses]}")
    log(f"trainer first step (trace + compile + run) {walls[0]:.2f}s, "
        f"backend compile {compile_s if compile_s is None else round(compile_s, 2)}s, "
        f"steady step (median of {max(steps - 1, 0)}, around "
        f"block_until_ready) "
        f"{'n/a' if steady is None else f'{steady:.3f}s'}")
    # memory_stats() counts live arrays only (measured: its peak after the
    # steps equals bytes_in_use between them), so the step's own footprint,
    # activations included, is XLA's buffer assignment
    xla = trainer.step_memory((ids, ids))
    step_bytes = xla["argument"] + xla["output"] - xla["alias"] + xla["temp"]
    memory = {"xla_step": xla, "xla_step_total_bytes": step_bytes,
              "allocator": {str(d.id): {
                  "peak_bytes_in_use": _peak_bytes(d),
                  "bytes_in_use": (d.memory_stats() or {}).get("bytes_in_use")}
                  for d in devices}}
    log(f"trainer step memory per device (XLA buffer assignment): "
        f"{_gib(step_bytes)} = arguments {_gib(xla['argument'])} + outputs "
        f"{_gib(xla['output'])} - donated {_gib(xla['alias'])} + temporaries "
        f"{_gib(xla['temp'])}")
    log("trainer allocator peak_bytes_in_use (process so far; live arrays "
        "only) / bytes_in_use now: "
        + ", ".join(f"dev{i}={_gib(m['peak_bytes_in_use'])} / "
                    f"{_gib(m['bytes_in_use'])}"
                    for i, m in memory["allocator"].items()))
    shards = {}
    for name in ("gpt.h.0.attn.qkv_proj.weight", "gpt.h.0.fc2.weight"):
        a = trainer.params[name]
        m1 = trainer.opt_state[name]["moment1"]
        shards[name] = {
            "global": list(a.shape), "spec": str(a.sharding.spec),
            "param_shards": {str(s.device.id): list(s.data.shape)
                             for s in a.addressable_shards},
            "moment1_spec": str(m1.sharding.spec),
            "moment1_shards": {str(s.device.id): list(s.data.shape)
                               for s in m1.addressable_shards}}
        log(f"trainer shards {name}: global {shards[name]['global']} "
            f"param {shards[name]['spec']} -> {shards[name]['param_shards']}"
            f"; moment1 {shards[name]['moment1_spec']} -> "
            f"{shards[name]['moment1_shards']}")
    attention = _trainer_attention(
        batch, cfg.num_attention_heads, seq,
        cfg.hidden_size // cfg.num_attention_heads, dtype,
        batch_split=int(np.prod([mesh.shape[a] for a in data_axes] or [1])),
        head_split=mesh.shape["mp"])
    log(f"trainer attention backend: {attention}")
    if not all(np.isfinite(losses)):
        raise AssertionError(f"trainer: non-finite loss in {losses}")
    if steps > 1 and not losses[-1] < losses[0]:
        raise AssertionError(f"trainer: loss did not fall on a repeated "
                             f"batch: {losses}")
    return {"depth": depth, "params": n_params, "mesh": dict(mesh.shape),
            "losses": losses, "first_step_s": round(walls[0], 2),
            "backend_compile_s": compile_s, "steady_step_s": steady,
            "memory": memory, "shards": shards, "attention": attention}


def four_chip_phase(depth_equal=TRAIN_DEPTH, depth_full=FOUR_CHIP_DEPTH,
                    mesh_axes=None, steps=TRAIN_STEPS, meter=None, **sizes):
    """The trainer on a four-chip host: one chip and four chips take the
    first step at equal depth from the same weights and batch (losses must
    agree within FOUR_CHIP_LOSS_TOL), then four chips run the depth they
    hold. Each trainer is dropped before the next is built."""
    import jax
    mesh_axes = mesh_axes or FOUR_CHIP_MESH
    devs = jax.devices()[:4]
    for d in devs:
        log(f"device {d.id}: coords={getattr(d, 'coords', None)} "
            f"core_on_chip={getattr(d, 'core_on_chip', None)}")
    one = trainer_phase(depth=depth_equal, steps=1, devices=devs[:1],
                        meter=meter, **sizes)
    gc.collect()
    # where four chips hold no more than one, the equal-depth run is also
    # the full run (the CPU test); on the chip they differ
    four = trainer_phase(depth=depth_equal,
                         steps=steps if depth_full == depth_equal else 1,
                         mesh_axes=mesh_axes, devices=devs, meter=meter,
                         **sizes)
    gc.collect()
    diff = abs(one["losses"][0] - four["losses"][0])
    log(f"four-chip first-step loss at depth {depth_equal}: one chip "
        f"{one['losses'][0]:.5f}, four chips {four['losses'][0]:.5f}, "
        f"|diff| {diff:.5f} (tolerance {FOUR_CHIP_LOSS_TOL})")
    if not diff <= FOUR_CHIP_LOSS_TOL:
        raise AssertionError(f"four-chip first-step loss differs from one "
                             f"chip's by {diff} > {FOUR_CHIP_LOSS_TOL}")
    for name, sh in four["shards"].items():
        if len(sh["param_shards"]) != len(devs):
            raise AssertionError(f"{name} is not held by all devices: {sh}")
    full = four if depth_full == depth_equal else trainer_phase(
        depth=depth_full, steps=steps, mesh_axes=mesh_axes, devices=devs,
        meter=meter, **sizes)
    return {"one_chip": one, "four_chip_equal_depth": four,
            "four_chip_full_depth": full, "first_step_loss_diff": diff}


# ---------------------------------------------------------------------------
# entry
# ---------------------------------------------------------------------------

def _cache_record(cache_dir, meter, mode):
    """Show the persistent cache working: each process leaves its compile
    seconds beside the cache it filled, and prints the earlier processes'
    next to its own. Display only — never part of pass/fail."""
    path = os.path.join(cache_dir, "chip_smoke_compile_seconds.json")
    try:
        with open(path) as f:
            runs = json.load(f)
    except (OSError, ValueError):
        runs = []
    mine = {"mode": mode, "pid": os.getpid(),
            "backend_compile_s": round(meter.total(), 2),
            "cache_hits": meter.hits, "cache_misses": meter.misses}
    earlier = [r for r in runs if r.get("mode") == mode]
    log(f"compile cache at {cache_dir}: this process compiled for "
        f"{mine['backend_compile_s']}s (persistent-cache hits "
        f"{meter.hits}, misses {meter.misses}); earlier processes on this "
        f"cache: {earlier or 'none'}")
    os.makedirs(cache_dir, exist_ok=True)
    with open(path, "w") as f:
        json.dump(runs + [mine], f)


def main(argv=None):
    argv = sys.argv[1:] if argv is None else argv
    four_chip = "--four-chip" in argv
    import jax
    device = jax.devices()[0]
    if device.platform != "tpu":
        print(f"chip_smoke: jax found no TPU (jax.devices()[0].platform == "
              f"{device.platform!r}); nothing was run", file=sys.stderr)
        return 2
    from paddle_tpu.framework.compile_cache import setup_compile_cache
    cache_dir = setup_compile_cache()
    info = device_report()
    import paddle_tpu._native as native
    log(f"paddle_tpu._native.available={native.available}")
    meter = CompileMeter()
    if four_chip:
        if info["count"] < 4:
            print(f"chip_smoke --four-chip: {info['count']} device(s)",
                  file=sys.stderr)
            return 2
        phases = [("four_chip", lambda: four_chip_phase(meter=meter))]
    else:
        # server last: the earlier phases keep fewer live arrays, so the
        # allocator's process peak after it is the server's own
        phases = [("trainer", lambda: trainer_phase(meter=meter)),
                  ("kernel", kernel_phase),
                  ("server", lambda: server_phase(meter=meter))]
    results, failed = {}, []
    t_all = time.perf_counter()
    for name, fn in phases:
        t0 = time.perf_counter()
        try:
            results[name] = fn()
            log(f"phase {name}: PASS in {time.perf_counter() - t0:.1f}s")
        except Exception:  # noqa: BLE001 — the next phase still reports;
            # the run fails and prints no result
            traceback.print_exc()
            failed.append(name)
            log(f"phase {name}: FAILED after {time.perf_counter() - t0:.1f}s")
        gc.collect()
        log(f"allocator peak of the process after phase {name}: "
            f"{_gib(_peak_bytes(device))}")
    from paddle_tpu.ops.pallas.attention_router import decision_log, route
    # the two attention shapes of the cell with window and full layers
    # (benchmark/configs/mellum2-12b-a2.5b-d8.json): 2 x 32 heads x 16,384
    for window in (1024, None):
        route(64, 16384, 16384, 128, "bfloat16", True, window=window)
    for key, dec in decision_log():
        log(f"attention decision (bh, sq, sk, d, dtype, causal, window)="
            f"{key}: fwd={dec.fwd} bwd={dec.bwd} why={dec.why!r} "
            f"grid steps {dec.grid_steps} visited/needed pairs "
            f"{dec.visited_pair_share}")
    _cache_record(cache_dir, meter, "four-chip" if four_chip else "one-chip")
    log(f"total {time.perf_counter() - t_all:.1f}s")
    out_dir = os.path.join(REPO, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "chip_smoke_four_chip.json" if four_chip
                           else "chip_smoke.json"), "w") as f:
        json.dump({"device": info, "failed": failed, "results": results}, f,
                  indent=1, default=str)
    if failed:
        print(f"chip_smoke: FAILED phases: {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": info}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
