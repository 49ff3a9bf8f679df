"""Fused transformer functionals — the TPU hot-op layer.

reference: python/paddle/incubate/nn/functional/ — fused_rms_norm.py,
fused_rotary_position_embedding.py, swiglu.py, fused_moe.py,
block_multihead_attention.py, masked_multihead_attention.py,
variable_length_memory_efficient_attention.py, fused_dot_product_attention.py.

TPU-native: "fused" means one XLA fusion (these compositions fuse fully) or
a Pallas kernel where XLA can't (flash attention). APIs keep reference names
so model code ports verbatim.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ....framework.core import Tensor, execute
from ....nn import functional as F

__all__ = ["fused_rms_norm", "fused_layer_norm",
           "fused_rotary_position_embedding", "swiglu", "fused_linear",
           "fused_linear_activation", "fused_bias_dropout_residual_layer_norm",
           "fused_dot_product_attention", "fused_multi_head_attention",
           "fused_feedforward", "masked_multihead_attention",
           "variable_length_memory_efficient_attention",
           "block_multihead_attention", "fused_moe",
           "fused_attention_rms_epilogue"]


def fused_rms_norm(x, norm_weight=None, norm_bias=None, epsilon=1e-6,
                   begin_norm_axis=-1, bias=None, residual=None,
                   quant_scale=-1, **kw):
    """reference: incubate/nn/functional/fused_rms_norm.py. One XLA fusion:
    (optional residual-add) → rms-normalize → scale."""
    args = [x]
    if residual is not None:
        args.append(residual)
    if bias is not None:
        args.append(bias)
    if norm_weight is not None:
        args.append(norm_weight)

    def f(a, *rest):
        i = 0
        if residual is not None:
            a = a + rest[i]; i += 1
        if bias is not None:
            a = a + rest[i]; i += 1
        a32 = a.astype(jnp.float32)
        ms = jnp.mean(a32 * a32, axis=-1, keepdims=True)
        out = (a32 * jax.lax.rsqrt(ms + epsilon)).astype(a.dtype)
        if norm_weight is not None:
            out = out * rest[i]
        return out

    out = execute(f, *args, _name="rms_norm")
    if residual is not None:
        return out, (x + residual if bias is None else x + residual + bias)
    return out


def fused_layer_norm(x, norm_weight, norm_bias, epsilon=1e-5,
                     begin_norm_axis=-1, bias=None, residual=None, **kw):
    if residual is not None:
        x = x + residual
    if bias is not None:
        x = x + bias
    out = F.layer_norm(x, x.shape[-1], norm_weight, norm_bias, epsilon)
    if residual is not None:
        return out, x
    return out


def fused_rotary_position_embedding(q, k=None, v=None, sin=None, cos=None,
                                    position_ids=None, use_neox_rotary_style=True,
                                    time_major=False, rotary_emb_base=10000.0):
    """RoPE. reference: incubate/nn/functional/fused_rotary_position_embedding.py.
    q/k: (batch, seq, heads, head_dim)."""

    def make_sincos(seq, dim, dtype):
        inv = 1.0 / (rotary_emb_base ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
        t = jnp.arange(seq, dtype=jnp.float32)
        freqs = jnp.outer(t, inv)  # (seq, dim/2)
        if use_neox_rotary_style:
            emb = jnp.concatenate([freqs, freqs], axis=-1)
        else:
            emb = jnp.repeat(freqs, 2, axis=-1)
        return jnp.sin(emb).astype(dtype), jnp.cos(emb).astype(dtype)

    def rotate_half(x):
        if use_neox_rotary_style:
            x1, x2 = jnp.split(x, 2, axis=-1)
            return jnp.concatenate([-x2, x1], axis=-1)
        x1 = x[..., ::2]
        x2 = x[..., 1::2]
        return jnp.stack([-x2, x1], axis=-1).reshape(x.shape)

    def apply_one(x, s, c, pos):
        if pos is not None:
            s = jnp.take(s, pos, axis=0)
            c = jnp.take(c, pos, axis=0)
            s = s[:, :, None, :]
            c = c[:, :, None, :]
        else:
            s = s[None, :, None, :]
            c = c[None, :, None, :]
        return (x * c + rotate_half(x) * s).astype(x.dtype)

    tensors = [t for t in (q, k, v) if t is not None]
    extra = []
    if sin is not None:
        extra = [sin, cos]
    if position_ids is not None:
        extra.append(position_ids)

    def f(*arrs):
        n = len(tensors)
        qa = arrs[0]
        seq, dim = qa.shape[1], qa.shape[-1]
        idx = n
        if sin is not None:
            s_, c_ = arrs[idx], arrs[idx + 1]
            s_ = s_.reshape(s_.shape[-2], s_.shape[-1])
            c_ = c_.reshape(c_.shape[-2], c_.shape[-1])
            idx += 2
        else:
            s_, c_ = make_sincos(seq, dim, qa.dtype)
        pos = arrs[idx] if position_ids is not None else None
        outs = tuple(apply_one(arrs[i], s_, c_, pos) for i in range(n))
        return outs if len(outs) > 1 else outs[0]

    outs = execute(f, *(tensors + extra), _name="fused_rope")
    if not isinstance(outs, tuple):
        outs = (outs,)
    result = []
    it = iter(outs)
    for t in (q, k, v):
        result.append(next(it) if t is not None else None)
    return tuple(result)


def swiglu(x, y=None, name=None):
    """reference: incubate/nn/functional/swiglu.py — silu(x) * y (y defaults
    to the second half of x)."""
    if y is None:
        def f(a):
            a1, a2 = jnp.split(a, 2, axis=-1)
            return jax.nn.silu(a1) * a2
        return execute(f, x, _name="swiglu")
    return execute(lambda a, b: jax.nn.silu(a) * b, x, y, _name="swiglu")


def fused_linear(x, weight, bias=None, transpose_weight=False, name=None):
    # reference: fused_linear is a wrapper over fused_matmul_bias
    return fused_matmul_bias(x, weight, bias, transpose_y=transpose_weight)


def fused_linear_activation(x, y, bias, trans_x=False, trans_y=False,
                            activation="gelu"):
    def f(a, w, b):
        if trans_x:
            a = a.T
        if trans_y:
            w = w.T
        out = a @ w + b
        if activation == "gelu":
            return jax.nn.gelu(out)
        if activation == "relu":
            return jax.nn.relu(out)
        return out
    return execute(f, x, y, bias, _name="linear")


def fused_bias_dropout_residual_layer_norm(x, residual, bias=None,
                                           ln_scale=None, ln_bias=None,
                                           dropout_rate=0.5, ln_epsilon=1e-5,
                                           training=True, mode="upscale_in_train",
                                           name=None):
    out = x if bias is None else x + bias
    out = F.dropout(out, dropout_rate, training=training, mode=mode)
    out = out + residual
    return F.layer_norm(out, out.shape[-1], ln_scale, ln_bias, ln_epsilon)


def fused_dot_product_attention(q, k, v, attn_mask=None, dropout_p=0.0,
                                is_causal=False, training=True, **kw):
    # pallas flash or dense XLA: chosen by the rule in
    # ops/pallas/attention_router through the shared sdpa path
    return F.scaled_dot_product_attention(q, k, v, attn_mask=attn_mask,
                                          dropout_p=dropout_p,
                                          is_causal=is_causal, training=training)


def fused_attention_rms_epilogue(q, k, v, residual, norm_weight,
                                 epsilon=1e-6, causal=True, name=None):
    """Causal attention with the rmsnorm(attn + residual) * weight
    epilogue, as an XLA composition (differentiable).

    q/residual: (batch, seq, heads, head_dim); k/v GQA-native (kv heads
    may divide heads); norm_weight: (head_dim,) — the norm axis is the
    head dim (per-head RMSNorm; pass heads=1 tensors for a full-hidden
    norm). The same math with the epilogue inside the flash kernel's
    flush is ops/pallas/flash_attention.flash_attention_rms_epilogue_bshd
    (forward-only; no measurement has shown it winning, so nothing here
    selects it)."""

    def f(q_, k_, v_, res_, w_):
        kx, vx = _expand_gqa(k_, v_, q_.shape[2])
        att = _sdpa_dense(q_, kx, vx, causal)
        hh = (att + res_).astype(jnp.float32)
        ms = jnp.mean(hh * hh, axis=-1, keepdims=True)
        return (hh * jax.lax.rsqrt(ms + epsilon)
                * w_.astype(jnp.float32)).astype(q_.dtype)

    return execute(f, q, k, v, residual, norm_weight,
                   _name="fused_attention_rms_epilogue")


def _expand_gqa(k, v, num_heads):
    kvh = k.shape[2]
    if kvh == num_heads:
        return k, v
    rep = num_heads // kvh

    def ex(a):
        bs, sk, _, d = a.shape
        return jnp.broadcast_to(a[:, :, :, None, :],
                                (bs, sk, kvh, rep, d)).reshape(
                                    bs, sk, num_heads, d)
    return ex(k), ex(v)


def _sdpa_dense(q, k, v, causal):
    d = q.shape[-1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) / (d ** 0.5)
    if causal:
        ql, kl = q.shape[1], k.shape[1]
        mask = jnp.tril(jnp.ones((ql, kl), jnp.bool_), k=kl - ql)
        s = jnp.where(mask, s, jnp.float32(-1e30))
    p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def fused_multi_head_attention(x, qkv_weight, linear_weight,
                               pre_layer_norm=False, pre_ln_scale=None,
                               pre_ln_bias=None, ln_scale=None, ln_bias=None,
                               pre_ln_epsilon=1e-5, qkv_bias=None,
                               linear_bias=None, cache_kv=None,
                               attn_mask=None, dropout_rate=0.5,
                               attn_dropout_rate=0.5, ln_epsilon=1e-5,
                               training=True, mode="upscale_in_train",
                               ring_id=-1, num_heads=-1, transpose_qkv_wb=False,
                               name=None):
    """reference: incubate/nn/functional/fused_transformer.py:513 — one
    transformer attention block: (pre-)LN -> qkv proj -> MHA -> out proj ->
    dropout -> residual add -> (post-)LN. On TPU the whole chain is XLA
    fusions around the attention matmuls; semantics match the pseudo-code
    in the reference docstring.

    x: (batch, seq, embed). qkv_weight: (3, num_heads, head_dim, embed)
    (or (embed, 3*embed) with transpose_qkv_wb). linear_weight:
    (embed, embed). Returns the block output (batch, seq, embed)."""
    if cache_kv is not None:
        raise NotImplementedError(
            "fused_multi_head_attention with cache_kv (incremental decode) "
            "is not supported; use masked_multihead_attention for the "
            "decode step")
    if transpose_qkv_wb and num_heads <= 0:
        raise ValueError(
            "fused_multi_head_attention: num_heads must be given (> 0) "
            "when transpose_qkv_wb=True (qkv_weight carries no head dim)")
    from ....framework.random import next_key
    dk = next_key() if (training and dropout_rate > 0.0) else None
    dk_attn = next_key() if (training and attn_dropout_rate > 0.0) else None

    args = [x, qkv_weight, linear_weight]
    opt = {"pre_ln_scale": pre_ln_scale, "pre_ln_bias": pre_ln_bias,
           "ln_scale": ln_scale, "ln_bias": ln_bias, "qkv_bias": qkv_bias,
           "linear_bias": linear_bias, "attn_mask": attn_mask}
    names = [k for k, v in opt.items() if v is not None]
    args += [opt[k] for k in names]

    def f(xa, qkv_w, lin_w, *rest):
        r = dict(zip(names, rest))
        b, s, e = xa.shape
        residual = xa
        h = xa
        if pre_layer_norm:
            h = _ln(h, r.get("pre_ln_scale"), r.get("pre_ln_bias"),
                    pre_ln_epsilon)
        if transpose_qkv_wb:
            nh = num_heads
            qkv = h @ qkv_w                      # (b, s, 3e)
            if "qkv_bias" in r:
                qkv = qkv + r["qkv_bias"]
            qkv = qkv.reshape(b, s, 3, nh, e // nh)
        else:
            nh, hd = qkv_w.shape[1], qkv_w.shape[2]
            qkv = jnp.einsum("bse,thde->bsthd", h, qkv_w)
            if "qkv_bias" in r:
                qkv = qkv + r["qkv_bias"]
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]  # (b, s, nh, hd)
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                            preferred_element_type=jnp.float32)
        logits = logits / jnp.sqrt(jnp.float32(q.shape[-1]))
        if "attn_mask" in r:
            logits = logits + r["attn_mask"].astype(logits.dtype)
        probs = jax.nn.softmax(logits, axis=-1)
        if dk_attn is not None:
            keepm = jax.random.bernoulli(dk_attn, 1.0 - attn_dropout_rate,
                                         probs.shape)
            probs = jnp.where(keepm, probs / (1.0 - attn_dropout_rate), 0.0)
        ctx = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v)
        out = ctx.reshape(b, s, -1) @ lin_w
        if "linear_bias" in r:
            out = out + r["linear_bias"]
        if dk is not None:
            keepo = jax.random.bernoulli(dk, 1.0 - dropout_rate, out.shape)
            out = jnp.where(keepo, out / (1.0 - dropout_rate), 0.0)
        out = residual + out
        if not pre_layer_norm:
            out = _ln(out, r.get("ln_scale"), r.get("ln_bias"), ln_epsilon)
        return out

    return execute(f, *args, _name="fused_multi_head_attention")


def _ln(h, scale, bias, eps):
    mu = jnp.mean(h, axis=-1, keepdims=True)
    var = jnp.var(h, axis=-1, keepdims=True)
    out = (h - mu) * jax.lax.rsqrt(var + eps)
    if scale is not None:
        out = out * scale
    if bias is not None:
        out = out + bias
    return out


def fused_feedforward(x, linear1_weight, linear2_weight, linear1_bias=None,
                      linear2_bias=None, ln1_scale=None, ln1_bias=None,
                      ln2_scale=None, ln2_bias=None, dropout1_rate=0.5,
                      dropout2_rate=0.5, activation="relu", ln1_epsilon=1e-5,
                      ln2_epsilon=1e-5, pre_layer_norm=False, training=True,
                      mode="upscale_in_train", ring_id=-1, add_residual=True,
                      name=None):
    """reference: incubate/nn/functional/fused_transformer.py:47 — the
    transformer FFN block: residual = x; (pre-)LN -> linear1 -> activation
    -> dropout1 -> linear2 -> dropout2 -> residual add -> (post-)LN.
    One XLA fusion chain around two MXU matmuls."""
    from ....framework.random import next_key
    k1 = next_key() if (training and dropout1_rate > 0.0) else None
    k2 = next_key() if (training and dropout2_rate > 0.0) else None
    act = {"relu": jax.nn.relu, "gelu": jax.nn.gelu,
           "swish": jax.nn.silu, "silu": jax.nn.silu}[activation]

    args = [x, linear1_weight, linear2_weight]
    opt = {"linear1_bias": linear1_bias, "linear2_bias": linear2_bias,
           "ln1_scale": ln1_scale, "ln1_bias": ln1_bias,
           "ln2_scale": ln2_scale, "ln2_bias": ln2_bias}
    names = [k for k, v in opt.items() if v is not None]
    args += [opt[k] for k in names]

    def f(xa, w1, w2, *rest):
        r = dict(zip(names, rest))
        residual = xa
        h = xa
        if pre_layer_norm:
            h = _ln(h, r.get("ln1_scale"), r.get("ln1_bias"), ln1_epsilon)
        h = h @ w1
        if "linear1_bias" in r:
            h = h + r["linear1_bias"]
        h = act(h)
        if k1 is not None:
            keep = jax.random.bernoulli(k1, 1.0 - dropout1_rate, h.shape)
            h = jnp.where(keep, h / (1.0 - dropout1_rate), 0.0)
        h = h @ w2
        if "linear2_bias" in r:
            h = h + r["linear2_bias"]
        if k2 is not None:
            keep = jax.random.bernoulli(k2, 1.0 - dropout2_rate, h.shape)
            h = jnp.where(keep, h / (1.0 - dropout2_rate), 0.0)
        if add_residual:
            h = residual + h
        if not pre_layer_norm:
            h = _ln(h, r.get("ln2_scale"), r.get("ln2_bias"), ln2_epsilon)
        return h

    return execute(f, *args, _name="fused_feedforward")


def masked_multihead_attention(x, cache_kv=None, bias=None, src_mask=None,
                               cum_offsets=None, sequence_lengths=None,
                               rotary_tensor=None, beam_cache_offset=None,
                               qkv_out_scale=None, out_shift=None,
                               out_smooth=None, seq_len=1, rotary_emb_dims=0,
                               use_neox_rotary_style=False,
                               compute_dtype="default", out_scale=-1,
                               quant_round_type=1, quant_max_bound=127.0,
                               quant_min_bound=-127.0):
    """Decode-step (single-token) MHA against a KV cache.

    reference: incubate/nn/functional/masked_multihead_attention.py — the
    generation-time fused kernel. x: (batch, 3*num_head*head_dim) packed
    qkv for ONE step; cache_kv: (2, batch, num_head, max_seq_len, head_dim);
    sequence_lengths: (batch, 1) current lengths (this step's kv is written
    at that position). Returns (out (batch, num_head*head_dim), cache_kv).

    TPU design: the cache update is a dynamic-slice scatter and the
    attention is one masked (1, L) x (L, d) matmul per head — static
    shapes, fully fusable. Quant/beam arguments are not supported."""
    if cache_kv is None:
        raise ValueError("masked_multihead_attention requires cache_kv")
    for unsupported, nm in ((beam_cache_offset, "beam_cache_offset"),
                            (qkv_out_scale, "qkv_out_scale"),
                            (out_shift, "out_shift"),
                            (out_smooth, "out_smooth"),
                            (cum_offsets, "cum_offsets"),
                            (rotary_tensor, "rotary_tensor")):
        if unsupported is not None:
            raise NotImplementedError(
                f"masked_multihead_attention: {nm} is not supported on TPU "
                "(apply fused_rotary_position_embedding to q/k before the "
                "call for RoPE)")
    if rotary_emb_dims:
        raise NotImplementedError(
            "masked_multihead_attention: in-kernel RoPE is not supported; "
            "apply fused_rotary_position_embedding to q/k first")

    args = [x, cache_kv]
    opt = {"bias": bias, "src_mask": src_mask,
           "sequence_lengths": sequence_lengths}
    names = [k for k, v in opt.items() if v is not None]
    args += [opt[k] for k in names]

    def f(xa, cache, *rest):
        r = dict(zip(names, rest))
        _, b, nh, max_len, hd = cache.shape
        qkv = xa.reshape(b, 3, nh, hd)
        if "bias" in r:
            qkv = qkv + r["bias"][None]
        q, k_new, v_new = qkv[:, 0], qkv[:, 1], qkv[:, 2]  # (b, nh, hd)
        if "sequence_lengths" in r:
            pos = r["sequence_lengths"].reshape(b).astype(jnp.int32)
        else:
            pos = jnp.zeros((b,), jnp.int32)
        bi = jnp.arange(b)
        cache = cache.at[0, bi, :, pos].set(k_new)
        cache = cache.at[1, bi, :, pos].set(v_new)
        keys, vals = cache[0], cache[1]          # (b, nh, L, hd)
        logits = jnp.einsum("bhd,bhld->bhl", q, keys,
                            preferred_element_type=jnp.float32)
        logits = logits / jnp.sqrt(jnp.float32(hd))
        valid = jnp.arange(max_len)[None, :] <= pos[:, None]  # (b, L)
        logits = jnp.where(valid[:, None, :], logits, jnp.float32(-1e30))
        if "src_mask" in r:
            logits = logits + r["src_mask"].reshape(
                b, 1, -1)[..., :max_len].astype(logits.dtype)
        probs = jax.nn.softmax(logits, axis=-1).astype(vals.dtype)
        out = jnp.einsum("bhl,bhld->bhd", probs, vals)
        return out.reshape(b, nh * hd), cache

    return execute(f, *args, _name="masked_multihead_attention")


def variable_length_memory_efficient_attention(query, key, value, seq_lens,
                                               kv_seq_lens, mask=None,
                                               scale=None, causal=False,
                                               pre_cache_length=0):
    # static-shape TPU design: dense attention with a length mask
    import numpy as np
    def f(q, k, v, sl, kl, *rest):
        b, h, sq, d = q.shape  # this API uses (b, h, s, d)
        sk = k.shape[2]
        qv = jnp.swapaxes(q, 1, 2)
        kv_ = jnp.swapaxes(k, 1, 2)
        vv = jnp.swapaxes(v, 1, 2)
        logits = jnp.einsum("bqhd,bkhd->bhqk", qv, kv_,
                            preferred_element_type=jnp.float32)
        s = scale if scale is not None else 1.0 / (d ** 0.5)
        logits = logits * s
        kmask = jnp.arange(sk)[None, :] < kl[:, None]
        logits = jnp.where(kmask[:, None, None, :], logits, -1e30)
        if causal:
            cm = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
            logits = jnp.where(cm, logits, -1e30)
        if rest:
            logits = logits + rest[0]
        p = jax.nn.softmax(logits, -1).astype(v.dtype)
        out = jnp.einsum("bhqk,bkhd->bqhd", p, vv)
        return jnp.swapaxes(out, 1, 2)
    args = [query, key, value, seq_lens, kv_seq_lens] + ([mask] if mask is not None else [])
    return execute(f, *args, _name="varlen_attention")


def block_multihead_attention(qkv, key_cache, value_cache, seq_lens,
                              block_tables, write_pos=None, num_heads=None,
                              num_kv_heads=None, name=None, **kwargs):
    """Paged-KV decode attention. reference:
    incubate/nn/functional/block_multihead_attention.py + CUDA kernel
    phi/kernels/fusion/gpu/block_multi_head_attention_kernel.cu.

    Decode-phase subset: qkv [B, (H + 2*KVH) * D] packed single new token;
    caches [num_blocks, block_size, KVH, D]; block_tables [B, max_blocks];
    seq_lens [B] length INCLUDING the new token. Writes the new K/V into the
    cache, attends over the paged prefix. Returns (out [B, H*D], k_cache,
    v_cache). Full serving loop: paddle_tpu.ops.paged_attention.
    """
    from ....ops.paged_attention import (paged_attention_decode,
                                         write_to_cache)
    dropped = {k: v for k, v in kwargs.items() if v is not None}
    if dropped:
        raise NotImplementedError(
            "block_multihead_attention: unsupported reference arguments "
            f"{sorted(dropped)} would change numerics if ignored; apply "
            "rope/bias to qkv before calling (see "
            "fused_rotary_position_embedding)")
    kvh = key_cache.shape[2] if num_kv_heads is None else num_kv_heads
    d = key_cache.shape[3]

    def f(qkv_a, kc, vc, lens, tables):
        B = qkv_a.shape[0]
        h = qkv_a.shape[1] // d - 2 * kvh
        q, k_new, v_new = jnp.split(
            qkv_a.reshape(B, -1, d), [h, h + kvh], axis=1)
        pos = lens - 1 if write_pos is None else write_pos
        kc, vc = write_to_cache(kc, vc, k_new, v_new, tables, pos)
        out = paged_attention_decode(q, kc, vc, tables, lens)
        return out.reshape(B, h * d), kc, vc

    return execute(f, qkv, key_cache, value_cache, seq_lens, block_tables,
                   _name="block_multihead_attention")


def fused_moe(x, gate_weight, expert_weights1, expert_bias1, expert_weights2,
              expert_bias2, quant_method="None", moe_topk=2, norm_topk_prob=True):
    """Dense-einsum MoE (every token × every expert masked by top-k gate) —
    the XLA-friendly formulation for moderate expert counts; the all-to-all
    EP version lives in incubate.distributed.models.moe."""
    def f(a, gw, w1, b1, w2, b2):
        scores = jax.nn.softmax(a @ gw, axis=-1)
        topv, topi = jax.lax.top_k(scores, moe_topk)
        if norm_topk_prob:
            topv = topv / jnp.sum(topv, -1, keepdims=True)
        n_exp = w1.shape[0]
        onehot = jax.nn.one_hot(topi, n_exp, dtype=a.dtype)  # (..., topk, E)
        gates = jnp.einsum("...ke,...k->...e", onehot, topv)
        h = jnp.einsum("...d,edh->...eh", a, w1) + b1
        h = jax.nn.gelu(h)
        out = jnp.einsum("...eh,ehd->...ed", h, w2) + b2
        return jnp.einsum("...ed,...e->...d", out, gates)
    return execute(f, x, gate_weight, expert_weights1, expert_bias1,
                   expert_weights2, expert_bias2, _name="fused_moe")


def fused_matmul_bias(x, y, bias=None, transpose_x=False, transpose_y=False,
                      name=None):
    """reference: incubate/nn/functional/fused_matmul_bias.py — matmul +
    bias epilogue; XLA fuses the add into the MXU matmul epilogue."""
    def f(a, b, *rest):
        if transpose_x:
            a = jnp.swapaxes(a, -1, -2)
        if transpose_y:
            b = jnp.swapaxes(b, -1, -2)
        out = a @ b
        if rest:
            out = out + rest[0]
        return out
    args = (x, y) + ((bias,) if bias is not None else ())
    return execute(f, *args, _name="fused_matmul_bias")


def fused_bias_act(x, bias=None, dequant_scales=None, shift=None, smooth=None,
                   act_method="gelu", compute_dtype="default", quant_scale=-1,
                   quant_round_type=0, quant_max_bound=0, quant_min_bound=0):
    """reference: incubate/nn/functional/fused_bias_act.py — bias +
    activation (+ optional int8 dequant/shift/smooth epilogue)."""
    acts = {"gelu": jax.nn.gelu, "relu": jax.nn.relu, "silu": jax.nn.silu,
            "swiglu": lambda a: jax.nn.silu(a[..., : a.shape[-1] // 2])
            * a[..., a.shape[-1] // 2:],
            "geglu": lambda a: jax.nn.gelu(a[..., : a.shape[-1] // 2])
            * a[..., a.shape[-1] // 2:]}
    if act_method not in acts:
        raise ValueError(f"act_method must be one of {sorted(acts)}, got "
                         f"{act_method!r}")
    if quant_scale > 0:
        raise NotImplementedError(
            "fused_bias_act: int8 output quantization is not supported on "
            "TPU — use nn.quant / quantization for serving quant")

    dtypes = {"default": None, "fp16": jnp.float16, "bf16": jnp.bfloat16,
              "fp32": jnp.float32}
    if compute_dtype not in dtypes:
        raise ValueError(f"compute_dtype must be one of {sorted(dtypes)}, "
                         f"got {compute_dtype!r}")

    def f(a, *rest):
        it = iter(rest)
        in_dtype = a.dtype
        if dequant_scales is not None:
            a = a.astype(jnp.float32) * next(it)
        if bias is not None:
            a = a + next(it)
        if shift is not None:
            a = a + next(it)
        if smooth is not None:
            a = a * next(it)
        out = acts[act_method](a)
        want = dtypes[compute_dtype]
        if want is not None:
            return out.astype(want)
        if dequant_scales is not None:  # default after int dequant: fp16
            return out.astype(jnp.float16)
        return out.astype(in_dtype)

    args = (x,) + tuple(t for t in (dequant_scales, bias, shift, smooth)
                        if t is not None)
    return execute(f, *args, _name="fused_bias_act")


def fused_dropout_add(x, y, p=0.5, training=True, mode="upscale_in_train",
                      seed=None, name=None):
    """reference: incubate/nn/functional/fused_dropout_add.py —
    dropout(x) + y in ONE traced region (one dispatch; XLA fuses the mask,
    scale, and add). `seed` pins the mask for reproducible serving."""
    from ....framework.random import next_key

    def f(a, b):
        if not training or p == 0.0:
            if mode == "downscale_in_infer" and not training:
                return a * (1.0 - p) + b
            return a + b
        key = jax.random.key(seed) if seed is not None else next_key()
        keep = jax.random.bernoulli(key, 1.0 - p, a.shape)
        if mode == "upscale_in_train":
            return jnp.where(keep, a / (1.0 - p), 0.0).astype(a.dtype) + b
        return jnp.where(keep, a, 0.0).astype(a.dtype) + b

    return execute(f, x, y, _name="fused_dropout_add")


def blha_get_max_len(seq_lens_encoder, seq_lens_decoder, batch_size=None,
                     name=None):
    """reference: incubate/nn/functional/blha_get_max_len.py — max
    encoder/decoder sequence lengths for block attention scheduling."""
    def f(enc, dec):
        return jnp.max(enc).reshape(1), jnp.max(dec).reshape(1)
    return execute(f, seq_lens_encoder, seq_lens_decoder,
                   _name="blha_get_max_len")


def fused_multi_transformer(
        x, ln_scales, ln_biases, qkv_weights, qkv_biases, linear_weights,
        linear_biases, ffn_ln_scales, ffn_ln_biases, ffn1_weights,
        ffn1_biases, ffn2_weights, ffn2_biases, pre_layer_norm=True,
        epsilon=1e-5, residual_alpha=1.0, cache_kvs=None, beam_offset=None,
        pre_caches=None, seq_lens=None, rotary_embs=None, time_step=None,
        attn_mask=None, dropout_rate=0.0, rotary_emb_dims=0,
        activation="gelu", training=False, mode="upscale_in_train",
        trans_qkvw=True, ring_id=-1, name=None):
    """Whole-stack fused transformer (inference serving).

    reference: incubate/nn/functional/fused_transformer.py:976 — one op
    running L pre-LN transformer layers; qkv_weights[i] shaped
    (3, num_head, head_dim, embed) with trans_qkvw=True. TPU-native: the
    layers are composed jnp inside one traced region — XLA's fusion is the
    kernel fusion the CUDA op hand-writes. Decode caches belong to
    generation.py / ops.paged_attention; the unsupported serving extras
    raise rather than silently change numerics.
    """
    if training and dropout_rate > 0:
        raise NotImplementedError(
            "fused_multi_transformer: training-mode dropout is not "
            "supported (this is the inference-serving op)")
    for unsupported, nm in ((cache_kvs, "cache_kvs"),
                            (pre_caches, "pre_caches"),
                            (rotary_embs, "rotary_embs"),
                            (time_step, "time_step"),
                            (seq_lens, "seq_lens"),
                            (beam_offset, "beam_offset")):
        if unsupported is not None:
            raise NotImplementedError(
                f"fused_multi_transformer: {nm} is not supported — use "
                "paddle_tpu.generation (KV-cache decode) or "
                "ops.paged_attention for serving caches")
    if not pre_layer_norm:
        raise NotImplementedError(
            "fused_multi_transformer: only pre_layer_norm=True (the "
            "reference default and the served configuration)")
    acts = {"gelu": jax.nn.gelu, "relu": jax.nn.relu}
    act = acts.get(activation)
    if act is None:
        raise ValueError(f"activation must be gelu/relu, got {activation!r}")

    n_layers = len(qkv_weights)

    def layer_norm(a, scale, bias_):
        mu = jnp.mean(a, axis=-1, keepdims=True)
        var = jnp.var(a, axis=-1, keepdims=True)
        out = (a - mu) * jax.lax.rsqrt(var + epsilon)
        return out * scale + bias_

    has_mask = attn_mask is not None

    def f(a, *rest):
        mask = rest[0] if has_mask else None
        it = iter(rest[1:] if has_mask else rest)
        per_layer = [tuple(next(it) for _ in range(12))
                     for _ in range(n_layers)]
        for (lns, lnb, qkvw, qkvb, lw, lb, flns, flnb, f1w, f1b, f2w,
             f2b) in per_layer:
            resid = a
            h = layer_norm(a, lns, lnb)
            if trans_qkvw:  # (3, H, D, E): project E -> (3, H, D)
                qkv = jnp.einsum("bse,nhde->bsnhd", h, qkvw) + qkvb
            else:  # reference layout (E, 3, H, D) — no reshape needed
                qkv = jnp.einsum("bse,enhd->bsnhd", h, qkvw) + qkvb
            q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
            d = q.shape[-1]
            s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                           preferred_element_type=jnp.float32) / (d ** 0.5)
            if mask is not None:
                s = s + mask
            p = jax.nn.softmax(s, axis=-1).astype(a.dtype)
            attn = jnp.einsum("bhqk,bkhd->bqhd", p, v)
            attn = attn.reshape(attn.shape[0], attn.shape[1], -1)
            a = resid * residual_alpha + attn @ lw + lb
            resid = a
            h = layer_norm(a, flns, flnb)
            h = act(h @ f1w + f1b)
            a = resid * residual_alpha + h @ f2w + f2b
        return a

    flat = []
    for i in range(n_layers):
        flat += [ln_scales[i], ln_biases[i], qkv_weights[i], qkv_biases[i],
                 linear_weights[i], linear_biases[i], ffn_ln_scales[i],
                 ffn_ln_biases[i], ffn1_weights[i], ffn1_biases[i],
                 ffn2_weights[i], ffn2_biases[i]]
    args = ((x, attn_mask) if has_mask else (x,)) + tuple(flat)
    return execute(f, *args, _name="fused_multi_transformer")


__all__ += ["fused_matmul_bias", "fused_bias_act", "fused_dropout_add",
            "blha_get_max_len", "fused_multi_transformer"]
