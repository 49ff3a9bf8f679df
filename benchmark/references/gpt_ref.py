"""Plain float32 jax.numpy GPT-3 forward and next-token loss.

Pre-LN transformer with learned positions, as Brown et al. 2020 (GPT-3)
and Radford et al. 2019 (GPT-2) describe it: LayerNorm with bias, fused
QKV projection, causal softmax attention scaled by 1/sqrt(d_head), GELU
(exact, erf) MLP of width 4 x d_model, output head tied to the token
embedding, mean cross-entropy of token t+1 given tokens <= t. No kernels,
no sharding, no recomputation. Departure from the paper: GPT-3 alternates
dense and locally banded sparse attention layers; the repo's model
(models/gpt.py) and this reference use dense attention in every layer.

Weights use the names of models/gpt.py's state_dict ([in, out] matrices).
Call under jax.default_matmul_precision("highest"): on a TPU a float32
matmul otherwise runs in bf16 passes.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _ln(x, w, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * _f32(w) + _f32(b)


def block(x, p, num_heads, eps):
    """One transformer block on (S, H) float32 activations. p: the block's
    parameters by their suffix ("ln_1.weight", "attn.qkv_proj.weight", ...)."""
    s, h = x.shape
    hd = h // num_heads
    y = _ln(x, p["ln_1.weight"], p["ln_1.bias"], eps)
    qkv = (y @ _f32(p["attn.qkv_proj.weight"])
           + _f32(p["attn.qkv_proj.bias"])).reshape(s, 3, num_heads, hd)
    q, k, v = qkv[:, 0], qkv[:, 1], qkv[:, 2]
    sc = jnp.einsum("qhd,khd->hqk", q, k) / hd ** 0.5
    causal = jnp.tril(jnp.ones((s, s), bool))
    pr = jax.nn.softmax(jnp.where(causal[None], sc, -jnp.inf), axis=-1)
    a = jnp.einsum("hqk,khd->qhd", pr, v).reshape(s, h)
    x = x + a @ _f32(p["attn.out_proj.weight"]) + _f32(p["attn.out_proj.bias"])
    y = _ln(x, p["ln_2.weight"], p["ln_2.bias"], eps)
    y = jax.nn.gelu(y @ _f32(p["fc1.weight"]) + _f32(p["fc1.bias"]),
                    approximate=False)
    return x + y @ _f32(p["fc2.weight"]) + _f32(p["fc2.bias"])


def embed(params, ids):
    return (_f32(params["gpt.wte.weight"])[ids]
            + _f32(params["gpt.wpe.weight"])[: ids.shape[0]])


def head_loss(x, params, ids, eps):
    """Mean next-token cross-entropy of one sequence from its final hidden
    states (S, H)."""
    x = _ln(x, params["gpt.ln_f.weight"], params["gpt.ln_f.bias"], eps)
    logits = x[:-1] @ _f32(params["gpt.wte.weight"]).T
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, ids[1:, None], axis=-1))


def loss(params, ids, num_layers, num_heads, eps=1e-5):
    """Mean next-token loss over a (B, S) batch: sequences one at a time,
    layers one at a time through one jitted block, so that the float32
    reference of a model that fills the chip in bf16 still fits beside it."""
    blk = jax.jit(block, static_argnums=(2, 3))
    emb = jax.jit(embed)
    hl = jax.jit(head_loss, static_argnums=(3,))
    outer = {k: v for k, v in params.items() if not k.startswith("gpt.h.")}
    total = 0.0
    with jax.default_matmul_precision("highest"):
        for b in range(ids.shape[0]):
            x = emb(outer, ids[b])
            for i in range(num_layers):
                pre = f"gpt.h.{i}."
                x = blk(x, {k[len(pre):]: v for k, v in params.items()
                            if k.startswith(pre)}, num_heads, eps)
            total += float(hl(x, outer, ids[b], eps))
    return total / ids.shape[0]
