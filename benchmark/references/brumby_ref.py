"""Plain float32 jax.numpy Brumby (HF `brumby`, Manifest AI) forward,
next-token loss and, through jax.grad, gradients.

Written from the published config's keys and the power-retention paper's
attention form ("Scaling Context Requires Rethinking Attention",
arXiv:2507.04239), not from the program under test (paddle_tpu/models/
brumby.py, paddle_tpu/ops/power_retention.py), of which it imports
nothing. For hidden states h (T x hidden) of one sequence:

    x   = RMSNorm(h; w_in)
    q   = x W_q -> (T, heads, d)    k = x W_k, v = x W_v -> (T, kv_heads, d)
    gam = x W_g + b_g -> (T, kv_heads)
    q   = RMSNorm_d(q; w_qn)   k = RMSNorm_d(k; w_kn)      per head
    q,k = RoPE(q, k; theta, rotate-half pairing (i, i + d/2), positions 0..T-1)
    L_t = sum_{r<=t} log sigmoid(gam_r)                    per state head
    for query head i, state head j = i // (heads / kv_heads), s <= t:
        a_ts = exp(L_t - L_s) (q_t . k_s / sqrt(d))^2
        y_t  = sum_{s<=t} a_ts v_s / (sum_{s<=t} a_ts + eps)
    h = h + concat_i(y^(i)) W_o
    h = h + W_down(silu(W_gate x') * (W_up x')),   x' = RMSNorm(h; w_post)
    logits = RMSNorm(h; w_f) W_head;  mean cross entropy of token t + 1
    given tokens <= t.

The retention is the QUADRATIC form: every a_ts of a head written out, no
chunks, no carried state, no feature expansion.

Departures from the published model, all of them:
1. config.json names the layer ("power retention layers") and not its
   constants: degree 2, one sigmoid gate per key/value head from a linear
   map (with bias) of the layer's normed input, the 1 / sqrt(d) scale
   inside the power and eps = 1e-6 in the normaliser are this reference's
   reading of the paper, listed under the configuration's `assumed`.
2. the vocabulary is whatever `embed_tokens` has rows for (a slice).
3. float32 everywhere with `jax.default_matmul_precision("highest")`,
   where the released weights run in bf16. `dtype=jnp.bfloat16` computes
   everything, the running sum of the log-decays too, in bf16: the
   nearest precision below the program's (bf16 operands, float32 sums and
   decays), the control the cell's limits are set against.
4. memory only: `loss()` runs sequences and sub-blocks one at a time, the
   a_ts of ROW_BLOCK queries of one head at a time, the FFN and the head
   over ROW_BLOCK rows at a time, and casts one sub-block's weights at a
   time, so that it fits beside a trainer that fills the chip. The
   arithmetic is unchanged.
5. `cfg["window"]` (a control, not a key of the config): a_ts = 0 where
   s is more than `window` positions behind t, what a program that
   dropped the state carried between its chunks would compute.

Weights use the names of the model's state_dict ([in, out] matrices).
`cfg` is a plain dict of the config's keys in CFG_KEYS.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

CFG_KEYS = ("num_hidden_layers", "num_attention_heads", "num_key_value_heads",
            "head_dim", "rms_norm_eps", "rope_theta", "retention_eps")
BLOCKS = ("retention", "mlp")                       # sub-layer names
ROW_BLOCK = 2048


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """(T, heads, d): entry i pairs with i + d/2; float32 angles whatever
    x's type, the tables then in x's type."""
    t, _, d = x.shape
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.cos(ang).astype(x.dtype)[:, None, :]
    sin = jnp.sin(ang).astype(x.dtype)[:, None, :]
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _row_blocks(fn, *rows):
    """fn over the arrays' leading axis in blocks of ROW_BLOCK, one after
    the other (memory only): fn takes one block of each."""
    n = rows[0].shape[0]
    if n <= ROW_BLOCK or n % ROW_BLOCK:
        return fn(*rows)
    out = jax.lax.map(lambda block: fn(*block), tuple(
        r.reshape((n // ROW_BLOCK, ROW_BLOCK) + r.shape[1:]) for r in rows))
    return out.reshape((n,) + out.shape[2:])


def retention(x, p, cfg):
    """(T, hidden) -> (T, hidden): the mixer after its input norm, the
    quadratic form one query head at a time."""
    t = x.shape[0]
    nh, nkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    q = (x @ p["q_proj.weight"]).reshape(t, nh, d)
    k = (x @ p["k_proj.weight"]).reshape(t, nkv, d)
    v = (x @ p["v_proj.weight"]).reshape(t, nkv, d)
    gam = x @ p["g_proj.weight"] + p["g_proj.bias"]          # (T, nkv)
    q = _rope(_rms(q, p["q_norm.weight"], cfg["rms_norm_eps"]),
              cfg["rope_theta"])
    k = _rope(_rms(k, p["k_norm.weight"], cfg["rms_norm_eps"]),
              cfg["rope_theta"])
    cum = jnp.cumsum(jax.nn.log_sigmoid(gam), axis=0)        # L, in x's type
    pos = jnp.arange(t)
    window = cfg.get("window")

    def head(i):
        j = i // (nh // nkv)
        kj, vj, lj = k[:, j], v[:, j], cum[:, j]

        def rows(qi, li, ti):                            # (R, d), (R,), (R,)
            keep = pos[None, :] <= ti[:, None]
            if window is not None:
                keep = keep & (ti[:, None] - pos[None, :] <= window)
            decay = jnp.exp(jnp.where(keep, li[:, None] - lj[None, :],
                                      -jnp.inf))
            a = decay * jnp.square((qi @ kj.T) * (d ** -0.5))
            return (a @ vj) / (jnp.sum(a, -1, keepdims=True)
                               + cfg["retention_eps"])

        return _row_blocks(rows, q[:, i], lj, pos)

    y = jax.lax.map(head, jnp.arange(nh))                    # (nh, T, d)
    return jnp.moveaxis(y, 0, 1).reshape(t, nh * d) @ p["o_proj.weight"]


def _cast(p, prefix, dtype):
    return {k[len(prefix):]: jnp.asarray(v, dtype) for k, v in p.items()
            if k.startswith(prefix)}


def retention_block(h, p, cfg, dtype=jnp.float32):
    """The layer's first residual sub-block on (T, hidden). p: the layer's
    parameters by the suffix after "model.layers.<i>."."""
    rp = _cast(p, "retention.", dtype)
    x = _rms(h, rp["input_layernorm.weight"], cfg["rms_norm_eps"])
    return h + retention(x, rp, cfg)


def mlp_block(h, p, cfg, dtype=jnp.float32):
    """The layer's second residual sub-block: the gated-silu FFN."""
    mp = _cast(p, "mlp.", dtype)

    def rows(hb):
        x = _rms(hb, mp["post_attention_layernorm.weight"],
                 cfg["rms_norm_eps"])
        act = jax.nn.silu(x @ mp["gate_proj.weight"]) \
            * (x @ mp["up_proj.weight"])
        return hb + act @ mp["down_proj.weight"]

    return _row_blocks(rows, h)


def layer(h, p, cfg, dtype=jnp.float32):
    return mlp_block(retention_block(h, p, cfg, dtype), p, cfg, dtype)


def embed(table, ids, dtype=jnp.float32):
    return jnp.asarray(table, dtype)[ids]


def head_loss(h, norm_w, head_w, ids, cfg, dtype=jnp.float32):
    """Mean next-token cross entropy of one sequence from (T, hidden);
    the log-softmax in float32 whatever `dtype`."""
    norm_w, head_w = jnp.asarray(norm_w, dtype), jnp.asarray(head_w, dtype)
    targets = jnp.roll(ids, -1)

    def rows(hb, tgt):
        logits = (_rms(hb, norm_w, cfg["rms_norm_eps"]) @ head_w
                  ).astype(jnp.float32)
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.take_along_axis(logp, tgt[:, None], axis=-1)[:, 0]

    nll = _row_blocks(rows, h, targets)
    return jnp.mean(nll[:-1])           # the last position predicts nothing


def _layer_params(params, i):
    pre = f"model.layers.{i}."
    return {k[len(pre):]: v for k, v in params.items() if k.startswith(pre)}


def forward_loss(params, ids, cfg, dtype=jnp.float32):
    """Mean loss over a (B, T) batch as one pure function (differentiable:
    jax.grad gives the reference's gradients at a test size)."""
    total = 0.0
    for b in range(ids.shape[0]):
        h = embed(params["model.embed_tokens.weight"], ids[b], dtype)
        for i in range(cfg["num_hidden_layers"]):
            h = layer(h, _layer_params(params, i), cfg, dtype)
        total = total + head_loss(h, params["model.norm.weight"],
                                  params["lm_head.weight"], ids[b], cfg,
                                  dtype)
    return total / ids.shape[0]


def loss(params, ids, cfg, dtype=jnp.float32, on_block=None):
    """The same number, frugally: sequences and sub-blocks one at a time
    through jitted pieces, each layer's weights cast as they are used.
    `on_block(layer index, sub-layer name in BLOCKS, hidden in, hidden
    out)` is called after every sub-block of the first sequence, so that a
    caller can hold another implementation to the same sub-block on the
    same input."""
    frozen = tuple(sorted(cfg.items()))
    total = 0.0
    for b in range(ids.shape[0]):
        h = _embed_jit(params["model.embed_tokens.weight"], ids[b], dtype)
        for i in range(cfg["num_hidden_layers"]):
            p = _layer_params(params, i)
            mid = _retention_jit(h, p, frozen, dtype)
            out = _mlp_jit(mid, p, frozen, dtype)
            if on_block is not None and b == 0:
                on_block(i, "retention", h, mid)
                on_block(i, "mlp", mid, out)
            h = out
        total += float(_head_jit(h, params["model.norm.weight"],
                                 params["lm_head.weight"], ids[b], frozen,
                                 dtype))
    return total / ids.shape[0]


# "highest" is set inside each jitted piece, not around loss(): on_block
# runs the caller's code, which keeps its own matmul precision

@functools.partial(jax.jit, static_argnums=(2, 3))
def _retention_jit(h, p, frozen, dtype):
    with jax.default_matmul_precision("highest"):
        return retention_block(h, p, dict(frozen), dtype)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _mlp_jit(h, p, frozen, dtype):
    with jax.default_matmul_precision("highest"):
        return mlp_block(h, p, dict(frozen), dtype)


@functools.partial(jax.jit, static_argnums=(2,))
def _embed_jit(table, ids, dtype):
    return embed(table, ids, dtype)


@functools.partial(jax.jit, static_argnums=(4, 5))
def _head_jit(h, norm_w, head_w, ids, frozen, dtype):
    with jax.default_matmul_precision("highest"):
        return head_loss(h, norm_w, head_w, ids, dict(frozen), dtype)
