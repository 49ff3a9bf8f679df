"""Attention functionals — the TPU hot path.

reference: python/paddle/nn/functional/flash_attention.py:195 flash_attention,
:976 scaled_dot_product_attention; kernel paddle/phi/kernels/gpu/flash_attn_kernel.cu
(FlashAttention-2 via dynload).

TPU-native design: an XLA attention that computes in fp32 with bf16
inputs, and the Pallas flash-attention kernels
(paddle_tpu/ops/pallas/flash_attention.py); under
FLAGS_flash_attention_backend=auto the rule in
ops/pallas/attention_router.py picks between them from the shape.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ...framework import flags as _flags
from ...framework.core import Tensor, execute
from ...framework.random import next_key

__all__ = ["scaled_dot_product_attention", "flash_attention",
           "flash_attn_unpadded", "sdp_kernel", "attention_bshd"]


def _xla_attention(q, k, v, bias=None, causal=False, scale=None, dropout_p=0.0,
                   dropout_key=None, window=None):
    # q,k,v: (batch, seq, heads, head_dim) — paddle flash_attention layout
    hd = q.shape[-1]
    s = scale if scale is not None else 1.0 / (hd ** 0.5)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * s
    if bias is not None:
        logits = logits + bias.astype(logits.dtype)
    if causal:
        ql, kl = q.shape[1], k.shape[1]
        if window is None:
            mask = jnp.tril(jnp.ones((ql, kl), dtype=jnp.bool_), k=kl - ql)
        else:
            from ...ops.pallas.flash_attention import band_mask
            mask = band_mask(ql, kl, window)
        logits = jnp.where(mask, logits, jnp.float32(-1e30))
    probs = jax.nn.softmax(logits, axis=-1)
    if dropout_p > 0.0 and dropout_key is not None:
        keep = jax.random.bernoulli(dropout_key, 1.0 - dropout_p, probs.shape)
        probs = jnp.where(keep, probs / (1.0 - dropout_p), 0.0)
    probs = probs.astype(v.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def _expand_kv(k, v, num_heads):
    """GQA: broadcast kv heads up to num_heads for the dense path (the
    Pallas kernel consumes the unexpanded heads natively)."""
    kvh = k.shape[2]
    if kvh == num_heads:
        return k, v
    rep = num_heads // kvh

    def expand(a):
        bs, sk, _, d = a.shape
        return jnp.broadcast_to(
            a[:, :, :, None, :], (bs, sk, kvh, rep, d)
        ).reshape(bs, sk, num_heads, d)

    return expand(k), expand(v)


def _use_pallas(q_shape, head_dim, has_bias, dtype=None, causal=True,
                seq_k=None, window=None):
    if has_bias:
        # the pallas kernel takes no bias/mask — never select it silently
        return False
    # the one rule (ops/pallas/attention_router.route): it reads
    # FLAGS_flash_attention_backend, the live backend and the shape. On a
    # TPU an import or kernel failure propagates: a broken kernel path
    # must not read as "dense was chosen".
    from ...ops.pallas.attention_router import route
    b, seq = q_shape[0], q_shape[1]
    heads = q_shape[2] if len(q_shape) > 3 else 1
    return route(b * heads, seq, seq if seq_k is None else seq_k, head_dim,
                 dtype if dtype is not None else "bfloat16",
                 causal, window=window).fwd == "pallas"


def attention_bshd(q, k, v, is_causal=True, scale=None, window=None):
    """Attention on raw arrays, (batch, seq, heads, head_dim) with
    GQA-native k/v, for code that is already a pure jax function (a
    rematerialised sub-block): the flash kernels or dense XLA attention,
    chosen exactly as scaled_dot_product_attention chooses. scale: the
    softmax scale (default 1/sqrt(head_dim)). window (with is_causal): a
    query sees `window` keys, its own included; the kernels skip what the
    band hides and the dense path masks it."""
    if window is not None and not is_causal:
        raise ValueError("a window is a band under the causal diagonal: "
                         "is_causal=True")
    if _use_pallas(tuple(q.shape), q.shape[-1], False, dtype=q.dtype,
                   causal=is_causal, seq_k=k.shape[1], window=window):
        from ...ops.pallas.flash_attention import flash_attention_bshd
        return flash_attention_bshd(q, k, v, causal=is_causal, scale=scale,
                                    window=window)
    k, v = _expand_kv(k, v, q.shape[2])
    return _xla_attention(q, k, v, causal=is_causal, scale=scale,
                          window=window)


def scaled_dot_product_attention(query, key, value, attn_mask=None,
                                 dropout_p=0.0, is_causal=False, training=True,
                                 name=None):
    """paddle layout: (batch, seq, num_heads, head_dim)."""
    dropout_key = next_key() if (dropout_p > 0.0 and training) else None
    use_pallas = _use_pallas(tuple(query.shape), query.shape[-1],
                             attn_mask is not None,
                             dtype=getattr(query, "dtype", None),
                             causal=is_causal, seq_k=key.shape[1]
                             ) and dropout_key is None

    if use_pallas:
        from ...ops.pallas.flash_attention import flash_attention_bshd
        args = [query, key, value]
        def f(q, k, v):
            # GQA-native: unexpanded kv heads go straight to the kernel
            return flash_attention_bshd(q, k, v, causal=is_causal)

        def f_dense(q, k, v):
            # mathematically-equal dense recompute, differentiable at any
            # order — recorded as the node's higher-order forward so
            # create_graph=True works through the flash path (the Pallas
            # bwd kernels are custom_vjp and stop at first order)
            k, v = _expand_kv(k, v, q.shape[2])
            return _xla_attention(q, k, v, causal=is_causal)

        return execute(f, *args, _name="flash_attention_pallas",
                       _ho_fwd=f_dense)

    args = [query, key, value] + ([attn_mask] if attn_mask is not None else [])

    def f(q, k, v, *rest):
        bias = rest[0] if rest else None
        # GQA on the dense path: expand inside the traced fn
        k, v = _expand_kv(k, v, q.shape[2])
        return _xla_attention(q, k, v, bias=bias, causal=is_causal,
                              dropout_p=dropout_p if training else 0.0,
                              dropout_key=dropout_key)

    return execute(f, *args, _name="scaled_dot_product_attention")


def flash_attention(query, key, value, dropout=0.0, causal=False,
                    return_softmax=False, *, fixed_seed_offset=None,
                    rng_name="", training=True, name=None):
    out = scaled_dot_product_attention(query, key, value, dropout_p=dropout,
                                       is_causal=causal, training=training)
    return out, None


def flash_attn_unpadded(query, key, value, cu_seqlens_q, cu_seqlens_k,
                        max_seqlen_q, max_seqlen_k, scale, dropout=0.0,
                        causal=False, return_softmax=False, *,
                        fixed_seed_offset=None, rng_name="", training=True,
                        name=None):
    """Varlen (packed/ragged) attention.

    reference: python/paddle/nn/functional/flash_attention.py
    flash_attn_unpadded (varlen FlashAttention-2 over cu_seqlens).

    TPU design: XLA wants static shapes, so the packed (total_tokens,
    heads, dim) layout is gathered into a padded (batch, max_seqlen, ...)
    batch using the static max_seqlen_q/k, attention runs once batched
    with a per-sequence length mask (O(batch * max_len^2) memory, not
    O(total^2)), and results scatter back to the packed layout.
    cu_seqlens_*: (batch+1,) int32 prefix sums.
    """
    dropout_p = dropout if training else 0.0
    dropout_key = next_key() if dropout_p > 0.0 else None
    mq, mk = int(max_seqlen_q), int(max_seqlen_k)

    def f(q, k, v, cq, ck):
        tq = q.shape[0]
        tk = k.shape[0]
        len_q = cq[1:] - cq[:-1]                       # (nb,)
        len_k = ck[1:] - ck[:-1]
        iq = cq[:-1, None] + jnp.arange(mq)[None]      # (nb, mq)
        ik = ck[:-1, None] + jnp.arange(mk)[None]
        valid_q = jnp.arange(mq)[None] < len_q[:, None]
        valid_k = jnp.arange(mk)[None] < len_k[:, None]
        qb = q[jnp.clip(iq, 0, tq - 1)]                # (nb, mq, h, d)
        kb = k[jnp.clip(ik, 0, tk - 1)]
        vb = v[jnp.clip(ik, 0, tk - 1)]
        logits = jnp.einsum("bqhd,bkhd->bhqk", qb, kb,
                            preferred_element_type=jnp.float32) * scale
        mask = valid_q[:, None, :, None] & valid_k[:, None, None, :]
        if causal:
            # bottom-right alignment per sequence (FlashAttention-2 varlen
            # convention, same as the dense reference's tril(k=len_k-len_q)):
            # query i of sequence b sees keys j with i + len_k[b]-len_q[b] >= j
            off = (len_k - len_q)[:, None, None, None]
            mask = mask & (jnp.arange(mq)[:, None] + off
                           >= jnp.arange(mk)[None, :])
        logits = jnp.where(mask, logits, jnp.float32(-1e30))
        probs = jax.nn.softmax(logits, axis=-1)
        probs = jnp.where(mask, probs, 0.0)            # fully-masked pad rows
        if dropout_key is not None:
            keep = jax.random.bernoulli(dropout_key, 1.0 - dropout_p,
                                        probs.shape)
            probs = jnp.where(keep, probs / (1.0 - dropout_p), 0.0)
        probs = probs.astype(v.dtype)
        outb = jnp.einsum("bhqk,bkhd->bqhd", probs, vb)
        # scatter back to packed rows; pad rows route out of range and drop
        flat_idx = jnp.where(valid_q, iq, tq).reshape(-1)
        return jnp.zeros_like(q).at[flat_idx].set(
            outb.reshape(-1, *outb.shape[2:]), mode="drop")

    out = execute(f, query, key, value, cu_seqlens_q, cu_seqlens_k,
                  _name="flash_attn_unpadded")
    return out, None


class sdp_kernel:
    """Context manager parity shim (torch-style backend selection)."""

    def __init__(self, enable_flash=True, enable_math=True, enable_mem_efficient=True):
        self.enable_flash = enable_flash

    def __enter__(self):
        self._prev = _flags.flag_value("flash_attention_backend")
        _flags.set_flags({"flash_attention_backend": "pallas" if self.enable_flash else "xla"})
        return self

    def __exit__(self, *exc):
        _flags.set_flags({"flash_attention_backend": self._prev})
        return False
