"""Compiled autoregressive generation with a dense KV cache.

reference capability: the serving path the reference builds from
block_multihead_attention / masked_multihead_attention fused kernels
(paddle/phi/kernels/fusion/gpu/block_multi_head_attention_kernel.cu,
incubate/nn/functional/masked_multihead_attention.py) plus top_p_sampling
(tensor/search.py:1363) — prefill once, then one-token decode steps
against a KV cache.

TPU-native design: the whole generate() is ONE jit per
(batch, prompt_len, max_new_tokens) signature — prefill fills per-layer
K/V caches (static max length, position-masked), then `lax.scan` runs the
decode steps; layer weights are stacked (L, ...) arrays so each decode
step is itself a `lax.scan` over depth (compiled size O(1) in L). Greedy
or sampled (temperature / top-k / top-p) next-token choice happens inside
the scan. The paged-cache variant for many-sequence serving lives in
ops/paged_attention.py; this dense path is the single-program analog of
the reference's masked_multihead_attention decode.

Supports LlamaForCausalLM (flagship) and any causal LM exposing
`model(input_ids) -> logits` through the recompute fallback.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .framework.core import Tensor, no_grad
from .framework import random as _random
from .observability import span as _span
from .observability.catalog import metric as _metric
from .observability.tracing import get_tracer as _tracer
from .observability.tracing import new_trace_id as _new_trace_id

__all__ = ["generate", "GenerationConfig", "WeightOnlyGenerator"]


class GenerationConfig:
    """reference: the generation knobs of top_p_sampling + sampling loops."""

    def __init__(self, max_new_tokens=32, do_sample=False, temperature=1.0,
                 top_k=0, top_p=1.0, eos_token_id=None):
        self.max_new_tokens = max_new_tokens
        self.do_sample = do_sample
        self.temperature = temperature
        self.top_k = top_k
        self.top_p = top_p
        self.eos_token_id = eos_token_id


# ---------------------------------------------------------------------------
# pure llama math over stacked params (mirrors models/llama.py exactly).
# DELIBERATE duplication: the cache-threaded decode step can't reuse the
# module forward (functional_call returns no per-layer K/V). Divergence is
# gated by tests/test_generation.py's exact greedy-parity checks against
# the module forward (incl. GQA + tied-embedding configs) — change the
# model math and those tests fail here.
# ---------------------------------------------------------------------------


def _rms(x, w, eps):
    x32 = x.astype(jnp.float32)
    ms = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(ms + eps)).astype(x.dtype) * w


def _rope(x, pos, theta):
    """neox-style rope at absolute positions `pos` (any shape broadcastable
    to x[..., :0]); x: (..., heads, head_dim)."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    freqs = pos[..., None].astype(jnp.float32) * inv      # (..., d/2)
    emb = jnp.concatenate([freqs, freqs], axis=-1)        # (..., d)
    s, c = jnp.sin(emb), jnp.cos(emb)
    s = s[..., None, :].astype(x.dtype)                   # add head axis
    c = c[..., None, :].astype(x.dtype)
    x1, x2 = jnp.split(x, 2, axis=-1)
    rot = jnp.concatenate([-x2, x1], axis=-1)
    return x * c + rot * s


def _gqa(a, rep):
    if rep == 1:
        return a
    b, s, hkv, d = a.shape
    return jnp.broadcast_to(a[:, :, :, None, :],
                            (b, s, hkv, rep, d)).reshape(b, s, hkv * rep, d)


def _prefill_flash_routed(bh, s, d, dtype):
    """Whether prefill attention of s tokens runs the flash kernels: the
    same rule as the train path."""
    from .ops.pallas.attention_router import route
    return route(bh, s, s, d, dtype, True).fwd == "pallas"


def _llama_mlp(lp, h, eps):
    """h + MLP(norm(h)): the MLP sub-block of every Llama layer program,
    under its component scope (observability/catalog.py TRACE_SCOPES)."""
    with jax.named_scope("pt.mlp"):
        x = _rms(h, lp["post_attention_layernorm.weight"], eps)
        gate = x @ lp["mlp.gate_proj.weight"]
        up = x @ lp["mlp.up_proj.weight"]
        return h + (jax.nn.silu(gate) * up) @ lp["mlp.down_proj.weight"]


def _llama_layer_prefill(lp, h, pos, cfg):
    """Full-sequence layer forward; returns (h_out, (k, v)) with k/v rotated
    and UNexpanded (kv heads)."""
    eps, theta = cfg["eps"], cfg["theta"]
    nh, nkv, hd = cfg["heads"], cfg["kv_heads"], cfg["head_dim"]
    b, s, _ = h.shape
    with jax.named_scope("pt.attn"):
        x = _rms(h, lp["input_layernorm.weight"], eps)
        q = (x @ lp["self_attn.q_proj.weight"]).reshape(b, s, nh, hd)
        k = (x @ lp["self_attn.k_proj.weight"]).reshape(b, s, nkv, hd)
        v = (x @ lp["self_attn.v_proj.weight"]).reshape(b, s, nkv, hd)
        q = _rope(q, pos, theta)
        k = _rope(k, pos, theta)
        if _prefill_flash_routed(b * nh, s, hd, h.dtype):
            # routed flash prefill: GQA-native (kv stays unexpanded), causal.
            # Every prefill caller passes pos = arange rows, so the pos-based
            # mask below IS the standard causal structure the kernel applies.
            from .ops.pallas.flash_attention import flash_attention_bshd
            attn = flash_attention_bshd(q, k, v, causal=True).reshape(
                b, s, nh * hd)
        else:
            kx, vx = _gqa(k, nh // nkv), _gqa(v, nh // nkv)
            scores = jnp.einsum(
                "bqhd,bkhd->bhqk", q, kx,
                preferred_element_type=jnp.float32) / (hd ** 0.5)
            causal = pos[:, :, None] >= pos[:, None, :]       # (b, s, s)
            scores = jnp.where(causal[:, None], scores, -1e30)
            probs = jax.nn.softmax(scores, axis=-1).astype(vx.dtype)
            attn = jnp.einsum("bhqk,bkhd->bqhd", probs, vx).reshape(
                b, s, nh * hd)
        h = h + attn @ lp["self_attn.o_proj.weight"]
    h = _llama_mlp(lp, h, eps)
    return h, (k, v)


def _llama_layer_prefill_chunk(lp, h, kc, vc, table_row, start, cfg,
                               fmt=None, kc_scale=None, vc_scale=None,
                               lora=None):
    """One layer forward over a prompt CHUNK against the paged pool (the
    serving engine's chunked prefill): rotate the chunk's Q/K at absolute
    positions, scatter the chunk's K/V into the pool (multi-token write),
    then attend over every cached position `<=` the query's absolute
    position — previous chunks plus causal-within-chunk in one softmax.

    h: (1, C, H) chunk hidden states; kc/vc: ONE layer's
    (num_blocks, block_size, KVH, D) pool slice; table_row: (max_blocks,)
    block table of the owning sequence; start: absolute position of the
    chunk's first token. Returns (h_out, (kc, vc)) — with a quantized
    `fmt` (and its per-(token, head) scale pool slices) the writes encode
    and the attention read dequantizes in place, and the second element
    becomes (kc, vc, kc_scale, vc_scale). fmt=None keeps the original
    trace byte-for-byte.

    `lora` (round 22, multi-adapter serving): an optional
    (A_q [H, r], B_q [r, Dq], A_v [H, r], B_v [r, Dv]) tuple of this
    layer's already-gathered low-rank factors; the q/v projections gain
    `x @ A @ B` deltas in one batched einsum each. lora=None keeps the
    original trace byte-for-byte (the all-zeros base slot makes
    adapter_id 0 numerically identical even when wired).
    """
    from .ops.paged_attention import (kv_write_chunk,
                                      paged_attention_prefill_chunk,
                                      write_chunk_to_cache)
    eps, theta = cfg["eps"], cfg["theta"]
    nh, nkv, hd = cfg["heads"], cfg["kv_heads"], cfg["head_dim"]
    b, c, _ = h.shape                      # b == 1: one admission at a time
    pos = start + jnp.arange(c)[None]      # (1, C) absolute positions
    with jax.named_scope("pt.attn"):
        x = _rms(h, lp["input_layernorm.weight"], eps)
        q_lin = x @ lp["self_attn.q_proj.weight"]
        v_lin = x @ lp["self_attn.v_proj.weight"]
        if lora is not None:
            a_q, b_q, a_v, b_v = lora
            q_lin = q_lin + jnp.einsum("bch,hr,rd->bcd", x,
                                       a_q.astype(x.dtype),
                                       b_q.astype(x.dtype))
            v_lin = v_lin + jnp.einsum("bch,hr,rd->bcd", x,
                                       a_v.astype(x.dtype),
                                       b_v.astype(x.dtype))
        q = q_lin.reshape(b, c, nh, hd)
        k = (x @ lp["self_attn.k_proj.weight"]).reshape(b, c, nkv, hd)
        v = v_lin.reshape(b, c, nkv, hd)
        q = _rope(q, pos, theta)
        k = _rope(k, pos, theta)
        quant = fmt is not None and fmt.quantized
        if quant:
            kc, vc, kc_scale, vc_scale = kv_write_chunk(
                fmt, kc, vc, kc_scale, vc_scale, k[0], v[0], table_row, start)
        else:
            kc, vc = write_chunk_to_cache(kc, vc, k[0], v[0], table_row, start)
        attn = paged_attention_prefill_chunk(q[0], kc, vc, table_row, start,
                                             scale=1.0 / (hd ** 0.5),
                                             fmt=fmt if quant else None,
                                             k_scale_cache=kc_scale,
                                             v_scale_cache=vc_scale)
        h = h + attn.reshape(b, c, nh * hd) @ lp["self_attn.o_proj.weight"]
    h = _llama_mlp(lp, h, eps)
    if quant:
        return h, (kc, vc, kc_scale, vc_scale)
    return h, (kc, vc)


def _llama_layer_decode(lp, h, k_cache, v_cache, t, cfg):
    """One-token layer forward against the cache; h: (b, 1, H). The caches
    hold rotated K / V at positions < t (positions >= t are masked)."""
    eps, theta = cfg["eps"], cfg["theta"]
    nh, nkv, hd = cfg["heads"], cfg["kv_heads"], cfg["head_dim"]
    b = h.shape[0]
    T = k_cache.shape[1]
    with jax.named_scope("pt.attn"):
        x = _rms(h, lp["input_layernorm.weight"], eps)
        q = (x @ lp["self_attn.q_proj.weight"]).reshape(b, 1, nh, hd)
        k = (x @ lp["self_attn.k_proj.weight"]).reshape(b, 1, nkv, hd)
        v = (x @ lp["self_attn.v_proj.weight"]).reshape(b, 1, nkv, hd)
        pos = jnp.full((b, 1), t, jnp.int32)
        q = _rope(q, pos, theta)
        k = _rope(k, pos, theta)
        k_cache = jax.lax.dynamic_update_slice_in_dim(k_cache, k, t, axis=1)
        v_cache = jax.lax.dynamic_update_slice_in_dim(v_cache, v, t, axis=1)
        kx = _gqa(k_cache, nh // nkv)
        vx = _gqa(v_cache, nh // nkv)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, kx,
                            preferred_element_type=jnp.float32) / (hd ** 0.5)
        valid = (jnp.arange(T) <= t)[None, None, None, :]
        scores = jnp.where(valid, scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1).astype(vx.dtype)
        attn = jnp.einsum("bhqk,bkhd->bqhd", probs, vx).reshape(b, 1, nh * hd)
        h = h + attn @ lp["self_attn.o_proj.weight"]
    h = _llama_mlp(lp, h, eps)
    return h, k_cache, v_cache


def _sample(logits, key, gc: GenerationConfig, temperature, top_p):
    """do_sample / top_k / whether-top-p-filters are STRUCTURAL (change the
    program); the temperature and top_p VALUES are traced scalars so knob
    changes within a variant never recompile."""
    if not gc.do_sample:
        return jnp.argmax(logits, axis=-1)
    logits = logits / jnp.maximum(temperature, 1e-6)
    if gc.top_k and gc.top_k > 0:
        kth = jnp.sort(logits, axis=-1)[..., -gc.top_k][..., None]
        logits = jnp.where(logits < kth, -1e30, logits)
    if gc.top_p < 1.0:  # top_p == 1 skips the full-vocab sort entirely
        probs = jax.nn.softmax(logits, axis=-1)
        order = jnp.argsort(-probs, axis=-1)
        sorted_p = jnp.take_along_axis(probs, order, axis=-1)
        cum = jnp.cumsum(sorted_p, axis=-1)
        keep_sorted = (cum - sorted_p) < top_p
        keep = jnp.zeros_like(keep_sorted).at[
            jnp.arange(logits.shape[0])[:, None], order].set(keep_sorted)
        logits = jnp.where(keep, logits, -1e30)
    return jax.random.categorical(key, logits, axis=-1)


def _build_llama_generate(config, tied: bool, gc: GenerationConfig):
    """Compile-once decode program. Weights enter as ARGUMENTS (not baked
    constants), so one executable serves the model across optimizer steps /
    set_state_dict and holds no weight copies of its own."""
    cfg = dict(eps=config.rms_norm_eps, theta=config.rope_theta,
               heads=config.num_attention_heads,
               kv_heads=config.num_key_value_heads,
               head_dim=config.hidden_size // config.num_attention_heads)

    def run(stacked, embed_w, norm_w, head_w, input_ids, key, temperature,
            top_p):
        def logits_of(h_last):
            h = _rms(h_last, norm_w, cfg["eps"])
            w = embed_w.T if tied else head_w
            return (h @ w).astype(jnp.float32)

        b, s = input_ids.shape
        total = s + gc.max_new_tokens
        pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
        h = jnp.take(embed_w, input_ids, axis=0)

        # ---- prefill: scan over stacked layers, collecting K/V ----------
        def prefill_layer(hh, lp):
            hh, (k, v) = _llama_layer_prefill(lp, hh, pos, cfg)
            return hh, (k, v)

        h, (ks, vs) = jax.lax.scan(prefill_layer, h, stacked)
        # ks: (L, b, s, kvh, hd) -> pad the time axis to `total`
        padt = ((0, 0), (0, 0), (0, gc.max_new_tokens), (0, 0), (0, 0))
        k_cache = jnp.pad(ks, padt)
        v_cache = jnp.pad(vs, padt)

        first_logits = logits_of(h[:, -1])
        key, sub = jax.random.split(key)
        first_tok = _sample(first_logits, sub, gc, temperature, top_p)

        # ---- decode: scan over steps; inner scan over layers ------------
        def step(carry, i):
            tok, kc, vc, key, done = carry
            t = s + i
            hh = jnp.take(embed_w, tok[:, None], axis=0)  # (b, 1, H)

            def dec_layer(hcar, layer_in):
                lp, kl, vl = layer_in
                hh2, kl2, vl2 = _llama_layer_decode(lp, hcar, kl, vl, t, cfg)
                return hh2, (kl2, vl2)

            hh, (kc, vc) = jax.lax.scan(dec_layer, hh, (stacked, kc, vc))
            logits = logits_of(hh[:, -1])
            key, sub = jax.random.split(key)
            nxt = _sample(logits, sub, gc, temperature, top_p)
            if gc.eos_token_id is not None:
                done = done | (tok == gc.eos_token_id)
                nxt = jnp.where(done, gc.eos_token_id, nxt)
            return (nxt, kc, vc, key, done), tok

        done0 = jnp.zeros((b,), bool)
        (last, _, _, _, _), toks = jax.lax.scan(
            step, (first_tok, k_cache, v_cache, key, done0),
            jnp.arange(gc.max_new_tokens - 1))
        out = jnp.concatenate([jnp.moveaxis(toks, 0, 1), last[:, None]],
                              axis=1)
        return jnp.concatenate([input_ids, out], axis=1)

    return jax.jit(run)


def _generic_generate(model, input_ids, gc: GenerationConfig, key):
    """Fallback for models without a cache path: recompute the full prefix
    each step (O(n) forwards). Correct for any causal LM returning logits.
    No tape: a forward that nobody differentiates records and keeps
    nothing for a backward."""
    ids = input_ids
    done = jnp.zeros((ids.shape[0],), bool)
    for _ in range(gc.max_new_tokens):
        with _span("generation.decode_step"), no_grad():
            out = model(Tensor(ids))
        logits = (out[0] if isinstance(out, tuple) else out)._data
        key, sub = jax.random.split(key)
        nxt = _sample(logits[:, -1].astype(jnp.float32), sub, gc,
                      jnp.float32(gc.temperature), jnp.float32(gc.top_p))
        if gc.eos_token_id is not None:
            nxt = jnp.where(done, gc.eos_token_id, nxt)
            done = done | (nxt == gc.eos_token_id)
        ids = jnp.concatenate([ids, nxt[:, None]], axis=1)
    return ids


def generate(model, input_ids, max_new_tokens=32, do_sample=False,
             temperature=1.0, top_k=0, top_p=1.0, eos_token_id=None,
             seed=None):
    """Generate continuations. Returns (batch, prompt+max_new_tokens) ids.

    LlamaForCausalLM runs the compiled KV-cache path (one jit: prefill +
    lax.scan decode); other causal LMs use the recompute fallback.
    """
    gc = GenerationConfig(max_new_tokens, do_sample, temperature, top_k,
                          top_p, eos_token_id)
    ids = input_ids._data if isinstance(input_ids, Tensor) \
        else jnp.asarray(input_ids)
    ids = ids.astype(jnp.int32)
    if max_new_tokens <= 0:
        # both paths must agree: zero new tokens returns the prompt as-is
        # (the compiled llama path would otherwise still emit first_tok)
        return Tensor(ids)
    if do_sample:
        key = (jax.random.key(seed) if seed is not None
               else _random.next_key())
    else:  # greedy uses no randomness — don't advance the global stream
        key = jax.random.key(0)
    from .models.llama import LlamaForCausalLM
    # one trace id per call; children (build / prefill_decode) inherit it
    # through the span stack, same correlation scheme as serving Requests
    tid = _new_trace_id("gen-") if _tracer().enabled else None
    if isinstance(model, LlamaForCausalLM):
        _metric("generation_requests_total", path="llama_compiled").inc()
        with _span("generation.generate", path="llama_compiled",
                   batch=int(ids.shape[0]), prompt=int(ids.shape[1]),
                   new_tokens=int(max_new_tokens), trace_id=tid):
            from .parallel.functional import split_stacked_layer_params
            # CURRENT weights fetched per call and passed as jit arguments —
            # the compiled program is keyed only on config/shapes, never
            # holds weight copies, and stays correct across optimizer steps
            state = {k: v._data for k, v in model.state_dict().items()}
            stacked, other = split_stacked_layer_params(state)
            tied = "lm_head.weight" not in other
            c = model.config
            # structural knobs only: temperature/top_p are traced arguments,
            # so per-request knob changes never recompile
            cache_key = ((c.hidden_size, c.num_hidden_layers,
                          c.num_attention_heads, c.num_key_value_heads,
                          c.vocab_size, c.rms_norm_eps, c.rope_theta, tied),
                         max_new_tokens, do_sample, int(top_k),
                         top_p < 1.0, eos_token_id)
            cached = _GEN_CACHE.get(cache_key)
            if cached is None:
                # prefill + decode fuse into ONE compiled program here, so
                # the trace can only split build (trace/compile) from run;
                # the serving engine's two-program path is where separate
                # prefill/decode spans nest (serving.prefill/.decode_step)
                with _span("generation.build"):
                    cached = _build_llama_generate(c, tied, gc)
                    _GEN_CACHE[cache_key] = cached
            head_w = other.get("lm_head.weight")
            if head_w is None:  # jit needs concrete leaf; tied path ignores
                head_w = jnp.zeros((0,), jnp.float32)
            with _span("generation.prefill_decode"):
                out = cached(stacked, other["llama.embed_tokens.weight"],
                             other["llama.norm.weight"], head_w, ids, key,
                             jnp.float32(temperature), jnp.float32(top_p))
                if _tracer().enabled:
                    # sync only when tracing, so the span covers device
                    # time; the disabled path keeps async dispatch
                    out.block_until_ready()
            return Tensor(out)
    _metric("generation_requests_total", path="generic_recompute").inc()
    with _span("generation.generate", path="generic_recompute",
               batch=int(ids.shape[0]), prompt=int(ids.shape[1]),
               new_tokens=int(max_new_tokens), trace_id=tid):
        return Tensor(_generic_generate(model, ids, gc, key))


_GEN_CACHE: dict = {}


class WeightOnlyGenerator:
    """Weight-only int8 serving wrapper for LlamaForCausalLM.

    Snapshots the model's weights ONCE, stores every stacked per-layer
    matmul weight (and the untied lm head) as int8 with per-output-channel
    scales, and dequantizes INSIDE the compiled generate program — weights
    sit in HBM at 1 byte/param. This is the serving analog of the
    reference's weight-only GEMM path (python/paddle/nn/quant/
    weight_quantize + weight_only_linear over the fused decode kernels in
    paddle/phi/kernels/fusion/gpu/). Embeddings and norm vectors stay in
    the compute dtype (a gather and tiny vectors gain nothing from int8).

    The dequantized bf16 copy exists transiently per call (XLA materializes
    it ahead of the prefill/decode scans); steady-state HBM holds only the
    int8 weights, which is what lets a bigger model fit a serving chip.
    """

    def __init__(self, model, max_new_tokens=32, do_sample=False,
                 temperature=1.0, top_k=0, top_p=1.0, eos_token_id=None,
                 algo="weight_only_int8", share_weights_from=None):
        from .models.llama import LlamaForCausalLM
        from .parallel.functional import split_stacked_layer_params
        if not isinstance(model, LlamaForCausalLM):
            raise TypeError(
                "WeightOnlyGenerator supports LlamaForCausalLM; for other "
                "models use generate() with externally quantized weights")
        if algo != "weight_only_int8":
            raise NotImplementedError(
                f"algo={algo!r}: only weight_only_int8 is supported "
                "(int4 packing has no TPU-native gain over int8 here)")
        self._gc = GenerationConfig(max_new_tokens, do_sample, temperature,
                                    top_k, top_p, eos_token_id)
        if share_weights_from is not None:
            # reuse another generator's quantized tensors (e.g. serving
            # the same snapshot at several generation lengths) — only the
            # compiled program differs
            src = share_weights_from
            self._q, self._s, self._fp = src._q, src._s, src._fp
            self._embed, self._norm = src._embed, src._norm
            self._qh, self._sh = src._qh, src._sh
            self._tied = src._tied
        else:
            state = {k: v._data for k, v in model.state_dict().items()}
            stacked, other = split_stacked_layer_params(state)
            self._tied = "lm_head.weight" not in other

            def quant(v):
                # per-output-channel absmax: contraction axis is -2 (h @ w
                # with w[..., in, out]), so scales live per out column
                scale = jnp.maximum(
                    jnp.max(jnp.abs(v.astype(jnp.float32)), axis=-2,
                            keepdims=True) / 127.0, 1e-8)
                q = jnp.clip(jnp.round(v.astype(jnp.float32) / scale),
                             -127, 127).astype(jnp.int8)
                return q, scale

            self._q, self._s, self._fp = {}, {}, {}
            for k, v in stacked.items():
                if v.ndim >= 3:          # (L, in, out) matmul weights
                    self._q[k], self._s[k] = quant(v)
                else:                    # (L, H) norm vectors
                    self._fp[k] = v
            self._embed = other["llama.embed_tokens.weight"]
            self._norm = other["llama.norm.weight"]
            if self._tied:
                self._qh = jnp.zeros((0, 0), jnp.int8)
                self._sh = jnp.zeros((0, 0), jnp.float32)
            else:
                self._qh, self._sh = quant(other["lm_head.weight"])
        run = _build_llama_generate(model.config, self._tied, self._gc)
        cdt = self._embed.dtype
        tied = self._tied

        def qrun(q, s, fp, embed_w, norm_w, qh, sh, ids, key, temp, top_p):
            # dequantize in fp32, THEN cast: rounding the fp32 scale to the
            # bf16 compute dtype first would double the per-weight error
            layers = dict(fp)
            for k in q:
                layers[k] = (q[k].astype(jnp.float32) * s[k]).astype(cdt)
            head = (jnp.zeros((0,), jnp.float32) if tied
                    else (qh.astype(jnp.float32) * sh).astype(cdt))
            return run(layers, embed_w, norm_w, head, ids, key, temp, top_p)

        self._qrun = jax.jit(qrun)

    def quantized_bytes(self):
        """HBM held by the quantized weights (int8 + scales + fp leftovers)."""
        total = sum(a.nbytes for a in self._q.values())
        total += sum(a.nbytes for a in self._s.values())
        total += sum(a.nbytes for a in self._fp.values())
        return total + self._embed.nbytes + self._norm.nbytes \
            + self._qh.nbytes + self._sh.nbytes

    def generate(self, input_ids, seed=None):
        ids = input_ids._data if isinstance(input_ids, Tensor) \
            else jnp.asarray(input_ids)
        ids = ids.astype(jnp.int32)
        if self._gc.max_new_tokens <= 0:
            return Tensor(ids)
        if self._gc.do_sample:
            key = (jax.random.key(seed) if seed is not None
                   else _random.next_key())
        else:
            key = jax.random.key(0)
        return Tensor(self._qrun(
            self._q, self._s, self._fp, self._embed, self._norm,
            self._qh, self._sh, ids, key,
            jnp.float32(self._gc.temperature),
            jnp.float32(self._gc.top_p)))
