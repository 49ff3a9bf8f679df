"""Plain float32 jax.numpy forward of the Llama/Mistral decoder family.

Mistral-7B-v0.3 (huggingface.co/mistralai/Mistral-7B-v0.3, and Jiang et
al. 2023, arXiv:2310.06825) is this block: RMSNorm, grouped-query
attention with rotary embeddings (rotate-half, theta from the config), no
biases, SwiGLU MLP, untied output head. v0.3 has no sliding window
(`sliding_window: null`), so attention is causal over the whole context.
No kernels, no cache, no batching: prefill-then-decode through the
engine's paged cache must agree with this full forward. Copied from
chip_smoke.py `llama_reference_logits`, with layers run one at a time
through one jitted block so that it fits beside a full engine, and logits
computed only for the rows that are judged.

Weights use the names of models/llama.py's state_dict ([in, out]
matrices). Call under jax.default_matmul_precision("highest").
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * _f32(g)


def block(x, p, nh, nkv, theta, eps):
    """One decoder layer on (S, H) float32 activations; p by suffix."""
    s, hidden = x.shape
    hd = hidden // nh
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None]

    def rope(t):                                  # neox rotate-half
        t1, t2 = jnp.split(t, 2, axis=-1)
        return t * cos + jnp.concatenate([-t2, t1], -1) * sin

    h = _rms(x, p["input_layernorm.weight"], eps)
    q = rope((h @ _f32(p["self_attn.q_proj.weight"])).reshape(s, nh, hd))
    k = rope((h @ _f32(p["self_attn.k_proj.weight"])).reshape(s, nkv, hd))
    v = (h @ _f32(p["self_attn.v_proj.weight"])).reshape(s, nkv, hd)
    k = jnp.repeat(k, nh // nkv, axis=1)
    v = jnp.repeat(v, nh // nkv, axis=1)
    sc = jnp.einsum("qhd,khd->hqk", q, k) / hd ** 0.5
    causal = jnp.tril(jnp.ones((s, s), bool))
    pr = jax.nn.softmax(jnp.where(causal[None], sc, -jnp.inf), axis=-1)
    a = jnp.einsum("hqk,khd->qhd", pr, v).reshape(s, nh * hd)
    x = x + a @ _f32(p["self_attn.o_proj.weight"])
    h = _rms(x, p["post_attention_layernorm.weight"], eps)
    return x + (jax.nn.silu(h @ _f32(p["mlp.gate_proj.weight"]))
                * (h @ _f32(p["mlp.up_proj.weight"]))
                ) @ _f32(p["mlp.down_proj.weight"])


def head(x, norm_w, head_w, eps):
    return _rms(x, norm_w, eps) @ _f32(head_w)


def logits(layer_params, embed_w, norm_w, head_w, ids, first_row, *, nh, nkv,
           theta, eps):
    """(S - first_row, vocab) float32 logits of one token sequence `ids`
    (S,), for rows first_row.. only. `layer_params`: one dict per layer, by
    suffix; head_w is (hidden, vocab)."""
    blk = jax.jit(block, static_argnums=(2, 3, 4, 5))
    hd_ = jax.jit(head, static_argnums=(3,))
    with jax.default_matmul_precision("highest"):
        x = _f32(embed_w[ids])
        for p in layer_params:
            x = blk(x, p, nh, nkv, theta, eps)
        return hd_(x[first_row:], norm_w, head_w, eps)
