"""Plain float32 jax.numpy Granite 4.0-H (HF `granitemoehybrid`) forward,
next-token loss and, through jax.grad, gradients.

Written from the published config's keys and the Mamba-2 paper's
recurrence (Dao & Gu 2024, section 3), not from the program under test
(paddle_tpu/models/granite_moe_hybrid.py), of which it imports nothing:

    h = embedding_multiplier * E[ids]
    per layer:  h = h + residual_multiplier * Mixer(RMSNorm(h))
                x = RMSNorm(h)
                h = h + residual_multiplier * (Routed(x) + Shared(x))
    logits = RMSNorm(h) @ E^T / logits_scaling;  mean cross entropy of
    token t + 1 given tokens <= t.

- attention: q/k/v/o without bias, GQA, causal softmax of
  q k^T * attention_multiplier, no positions of any kind ("nope"); DENSE,
  every (query, key) score of a head at once;
- Mamba-2: [z | xBC | dt] = x W_in; xBC = silu(conv1d(xBC) + b) (depthwise,
  causal, the last tap on the current position); x as heads, B and C of
  d_state shared by all heads; dt = softplus(dt + dt_bias), A = -exp(A_log);
  the recurrence ONE TIME STEP AT A TIME (`lax.scan`, no chunks):
  S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T, y_t = S_t C_t + D x_t;
  y = RMSNorm(y * silu(z)) * w over all channels; out = y W_out;
- routed experts: top-k of x W_r over ALL experts, gates = softmax over
  the chosen logits, expert e = (silu(x W1_e[:, :I]) * x W1_e[:, I:]) W2_e,
  as a plain loop over the experts that are held; shared expert: the same
  gated form.

Departures from the published model, all of them:
1. `experts_held = (first, count)`: only those experts' terms of the
   routed sum are computed; what the others would add is left out (the
   chip's share of an expert-parallel group; model-configs guide, section
   4). With every expert held it is the whole layer.
2. no auxiliary (load-balancing) loss: the published checkpoint's training
   recipe is not in config.json.
3. the vocabulary is whatever `embed_tokens` has rows for (a slice).
4. float32 everywhere with `jax.default_matmul_precision("highest")`,
   where the released weights run in bf16. `dtype=jnp.bfloat16` computes
   everything, the recurrent state too, in bf16: the nearest precision
   below the program's (bf16 operands, float32 accumulation): the control
   the cell's limits are set against, as is `cfg["scan_dtype"]`, the
   recurrence alone in another precision (PERF.md section 4).
5. memory only: `loss()` runs sequences, sub-blocks and attention heads one
   at a time and casts one sub-block's weights at a time, so that it fits
   beside a trainer that fills the chip. The arithmetic is unchanged.

Weights use the names of the model's state_dict ([in, out] matrices;
experts stacked [count, in, out]; conv taps [width, channels]).
`cfg` is a plain dict of the config's keys in CFG_KEYS.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

CFG_KEYS = ("hidden_size", "layer_types", "num_attention_heads",
            "num_key_value_heads", "attention_multiplier",
            "embedding_multiplier", "residual_multiplier", "logits_scaling",
            "num_experts_per_tok", "mamba_n_heads", "mamba_d_head",
            "mamba_d_state", "rms_norm_eps", "experts_held")


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _gated(x, w_in, w_out):
    h = x @ w_in
    i = w_out.shape[0]
    return (jax.nn.silu(h[:, :i]) * h[:, i:]) @ w_out


def attention(x, p, cfg):
    """(S, H) -> (S, H): dense causal GQA, one query head at a time."""
    s = x.shape[0]
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg["hidden_size"] // nh
    q = (x @ p["q_proj.weight"]).reshape(s, nh, hd)
    k = (x @ p["k_proj.weight"]).reshape(s, nkv, hd)
    v = (x @ p["v_proj.weight"]).reshape(s, nkv, hd)
    causal = jnp.tril(jnp.ones((s, s), bool))

    def head(i):
        kv = i // (nh // nkv)
        sc = (q[:, i] @ k[:, kv].T) * cfg["attention_multiplier"]
        pr = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
        return pr @ v[:, kv]

    out = jax.lax.map(head, jnp.arange(nh))                 # (nh, S, hd)
    return jnp.moveaxis(out, 0, 1).reshape(s, nh * hd) @ p["o_proj.weight"]


def mamba(x, p, cfg):
    """(S, H) -> (S, H): the Mamba-2 mixer, recurrence step by step."""
    s = x.shape[0]
    heads, hd, n = (cfg["mamba_n_heads"], cfg["mamba_d_head"],
                    cfg["mamba_d_state"])
    inter = heads * hd
    conv_dim = inter + 2 * n
    zxbcdt = x @ p["in_proj.weight"]
    z = zxbcdt[:, :inter]
    xbc = zxbcdt[:, inter:inter + conv_dim]
    dt = zxbcdt[:, inter + conv_dim:]
    taps = p["conv1d.weight"]
    width = taps.shape[0]
    padded = jnp.pad(xbc, ((width - 1, 0), (0, 0)))
    xbc = jax.nn.silu(sum(padded[i:i + s] * taps[i] for i in range(width))
                      + p["conv1d.bias"])
    xs = xbc[:, :inter].reshape(s, heads, hd)
    bm, cm = xbc[:, inter:inter + n], xbc[:, inter + n:]
    dt = jax.nn.softplus(dt + p["dt_bias"])                 # (S, heads)
    a = -jnp.exp(p["A_log"])                                # (heads,)

    # `scan_dtype` (a control, not a key of the config): the recurrence
    # alone, its state too, in another precision than the rest
    sd = jnp.dtype(cfg.get("scan_dtype", x.dtype))
    xs, bm, cm, dt, a, d = (t.astype(sd) for t in (xs, bm, cm, dt, a, p["D"]))

    def step(state, t):
        x_t, b_t, c_t, dt_t = t
        state = (jnp.exp(dt_t * a)[:, None, None] * state
                 + (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :])
        return state, state @ c_t + d[:, None] * x_t

    state0 = jnp.zeros((heads, hd, n), sd)
    _, y = jax.lax.scan(step, state0, (xs, bm, cm, dt))
    y = y.astype(x.dtype).reshape(s, inter) * jax.nn.silu(z)
    return _rms(y, p["norm.weight"], cfg["rms_norm_eps"]) \
        @ p["out_proj.weight"]


def routed(x, p, cfg):
    """(S, H) -> (S, H): the held experts' terms of the routed sum."""
    first, count = cfg["experts_held"]
    logits = x @ p["router.weight"]
    top, ids = jax.lax.top_k(logits, cfg["num_experts_per_tok"])
    gates = jax.nn.softmax(top, axis=-1)
    out = jnp.zeros_like(x)
    for j in range(count):
        gate = jnp.sum(jnp.where(ids == first + j, gates, 0), axis=-1)
        out = out + gate[:, None].astype(x.dtype) * _gated(
            x, p["experts.input_linear"][j], p["experts.output_linear"][j])
    return out


BLOCKS = ("mamba", "self_attn", "block_sparse_moe")    # sub-layer names


def _cast(p, prefix, dtype):
    return {k[len(prefix):]: jnp.asarray(v, dtype) for k, v in p.items()
            if k.startswith(prefix)}


def mixer_block(h, p, kind, cfg, dtype=jnp.float32):
    """The layer's first residual sub-block on (S, H): the Mamba-2 or the
    attention mixer. p: the layer's parameters by the suffix after
    "model.layers.<i>."."""
    name, fn = ("mamba.", mamba) if kind == "mamba" \
        else ("self_attn.", attention)
    mp = _cast(p, name, dtype)
    x = _rms(h, mp["input_layernorm.weight"], cfg["rms_norm_eps"])
    return h + cfg["residual_multiplier"] * fn(x, mp, cfg)


def ffn_block(h, p, cfg, dtype=jnp.float32):
    """The layer's second residual sub-block: routed + shared experts."""
    fp = _cast(p, "block_sparse_moe.", dtype)
    x = _rms(h, fp["post_attention_layernorm.weight"], cfg["rms_norm_eps"])
    shared = _gated(x, fp["shared_mlp.input_linear"],
                    fp["shared_mlp.output_linear"])
    return h + cfg["residual_multiplier"] * (routed(x, fp, cfg) + shared)


def layer(h, p, kind, cfg, dtype=jnp.float32):
    """One decoder layer on (S, H)."""
    return ffn_block(mixer_block(h, p, kind, cfg, dtype), p, cfg, dtype)


def embed(table, ids, cfg, dtype=jnp.float32):
    return jnp.asarray(table, dtype)[ids] * cfg["embedding_multiplier"]


def head_loss(h, table, norm_w, ids, cfg, dtype=jnp.float32):
    """Mean next-token cross entropy of one sequence from (S, H)."""
    x = _rms(h, jnp.asarray(norm_w, dtype), cfg["rms_norm_eps"])
    logits = (x[:-1] @ jnp.asarray(table, dtype).T) / cfg["logits_scaling"]
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, ids[1:, None], axis=-1))


def _layer_params(params, i):
    pre = f"model.layers.{i}."
    return {k[len(pre):]: v for k, v in params.items() if k.startswith(pre)}


def forward_loss(params, ids, cfg, dtype=jnp.float32):
    """Mean loss over a (B, S) batch as one pure function (differentiable:
    jax.grad gives the reference's gradients at a test size)."""
    table = params["model.embed_tokens.weight"]
    total = 0.0
    for b in range(ids.shape[0]):
        h = embed(table, ids[b], cfg, dtype)
        for i, kind in enumerate(cfg["layer_types"]):
            h = layer(h, _layer_params(params, i), kind, cfg, dtype)
        total = total + head_loss(h, table, params["model.norm.weight"],
                                  ids[b], cfg, dtype)
    return total / ids.shape[0]


def loss(params, ids, cfg, dtype=jnp.float32, on_block=None):
    """The same number, frugally: sequences and sub-blocks one at a time
    through jitted pieces, each layer's weights cast as they are used.
    `on_block(layer index, sub-layer name in BLOCKS, hidden in, hidden
    out)` is called after every sub-block of the first sequence, so that a
    caller can hold another implementation to the same sub-block on the
    same input."""
    frozen = _freeze(cfg)
    table = params["model.embed_tokens.weight"]
    total = 0.0
    for b in range(ids.shape[0]):
        h = _embed_jit(table, ids[b], frozen, dtype)
        for i, kind in enumerate(cfg["layer_types"]):
            p = _layer_params(params, i)
            mid = _mixer_jit(h, p, kind, frozen, dtype)
            out = _ffn_jit(mid, p, frozen, dtype)
            if on_block is not None and b == 0:
                on_block(i, "mamba" if kind == "mamba" else "self_attn",
                         h, mid)
                on_block(i, "block_sparse_moe", mid, out)
            h = out
        total += float(_head_jit(h, table, params["model.norm.weight"],
                                 ids[b], frozen, dtype))
    return total / ids.shape[0]


def _freeze(cfg):
    return tuple(sorted((k, tuple(v) if isinstance(v, (list, tuple)) else v)
                        for k, v in cfg.items()))


# "highest" is set inside each jitted piece, not around loss(): on_block
# runs the caller's code, which keeps its own matmul precision

@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _mixer_jit(h, p, kind, frozen, dtype):
    with jax.default_matmul_precision("highest"):
        return mixer_block(h, p, kind, dict(frozen), dtype)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _ffn_jit(h, p, frozen, dtype):
    with jax.default_matmul_precision("highest"):
        return ffn_block(h, p, dict(frozen), dtype)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _embed_jit(table, ids, frozen, dtype):
    return embed(table, ids, dict(frozen), dtype)


@functools.partial(jax.jit, static_argnums=(4, 5))
def _head_jit(h, table, norm_w, ids, frozen, dtype):
    with jax.default_matmul_precision("highest"):
        return head_loss(h, table, norm_w, ids, dict(frozen), dtype)
