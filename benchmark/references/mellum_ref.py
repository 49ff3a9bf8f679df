"""Plain float32 jax.numpy Mellum 2 (HF `mellum`) forward, next-token loss
and, through jax.grad, gradients.

Written from the published config's keys and the formulas of the papers
they point to (RoFormer's rotation; YaRN, arXiv:2309.00071, as Hugging
Face's `_compute_yarn_parameters` evaluates it), not from the program
under test (paddle_tpu/models/mellum.py), of which it imports nothing:

    per layer:  h = h + Attn_kind(RMSNorm(h))
                h = h + MoE(RMSNorm(h))
    logits = RMSNorm(h) @ W_head;  mean cross entropy of token t + 1 given
    tokens <= t.

- attention: q/k/v/o without bias, 32 query heads over 4 key-value heads of
  128, RMSNorm over each head of q and of k, RoPE of the layer's kind,
  softmax of q k^T / sqrt(d), DENSE: every (query, key) score of a head,
  the mask written out. `sliding_attention` layers: default frequencies
  theta^(-2i/d); query t sees keys t - sliding_window + 1 .. t.
  `full_attention` layers: YaRN's blended frequencies, cos and sin times
  its attention factor; the whole causal triangle;
- experts: p = softmax over ALL the router's outputs in float32, the top-k
  of p, weights p / sum of the k (`norm_topk_prob`); expert e =
  (silu(x W1_e[:, :I]) * x W1_e[:, I:]) W2_e, as a plain loop over the
  experts that are held with a 0/1 choice mask: no sort, no gather, no
  kernel. No shared expert.

Departures from the published model, all of them:
1. `experts_held = (first, count)`: only those experts' terms of the
   routed sum are computed (the chip's share of an expert-parallel group;
   model-configs guide, section 4). With every expert held it is the whole
   layer.
2. no auxiliary (load-balancing) loss, no multi-token-prediction head:
   neither has a key in config.json.
3. the per-head RMSNorm on q and k is the Qwen3 lineage's, which the
   config's keys point to and never key.
4. the vocabulary is whatever `embed_tokens` has rows for (a slice).
5. float32 everywhere with `jax.default_matmul_precision("highest")`,
   where the released weights run in bf16. `dtype=jnp.bfloat16` computes
   everything in bf16 (the router's softmax alone stays float32, as
   published): the control the cell's limits are set against.
6. memory only: `loss()` runs sequences, sub-blocks, heads and blocks of
   ROW_BLOCK rows one at a time. The arithmetic is unchanged.

`attention_grads` differentiates the same masked softmax (`attend`) for the
query heads of one key-value head: what a caller holds an attention
kernel's BACKWARD to at a size where `jax.grad(forward_loss)` does not fit.

Faults a caller can plant (benchmark/families/mellum.py MELLUM_PLANT), by
keys of `cfg` that the published model does not have: `sliding_window`
None (window layers see the whole triangle), another `rope_parameters`
entry, `drop_first_held` (the first held expert of each token's choice is
left out).

Weights use the names of the model's state_dict ([in, out] matrices;
experts stacked [count, in, out], gate columns before up columns).
`cfg` is a plain dict of the config's keys in CFG_KEYS.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

CFG_KEYS = ("layer_types", "num_attention_heads", "num_key_value_heads",
            "head_dim", "sliding_window", "rope_parameters", "num_experts",
            "num_experts_per_tok", "rms_norm_eps", "experts_held",
            "differentiate_routing")
BLOCKS = ("self_attn", "mlp")                       # sub-layer names
ROW_BLOCK = 2048


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope_table(params, head_dim):
    """([head_dim / 2] inverse frequencies as Python floats, attention
    factor) of one `rope_parameters` entry, evaluated in double precision
    one dimension at a time."""
    theta, half = float(params["rope_theta"]), head_dim // 2
    extrap = [theta ** (-2.0 * i / head_dim) for i in range(half)]
    if params.get("rope_type", "default") == "default":
        return extrap, 1.0
    factor = float(params["factor"])
    original = float(params["original_max_position_embeddings"])

    def correction_dim(rotations):
        return (head_dim * math.log(original / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction_dim(params["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(params["beta_slow"])), head_dim - 1)
    if low == high:
        high += 0.001
    inv = []
    for i in range(half):
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        inv.append((extrap[i] / factor) * ramp + extrap[i] * (1.0 - ramp))
    return inv, float(params["attention_factor"])


def _rope(x, params):
    """(T, heads, d): entry i pairs with i + d/2; float32 angles whatever
    x's type, the tables then in x's type."""
    t, _, d = x.shape
    inv, factor = rope_table(params, d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] \
        * jnp.asarray(inv, jnp.float32)[None, :]
    cos = (jnp.cos(ang) * factor).astype(x.dtype)[:, None, :]
    sin = (jnp.sin(ang) * factor).astype(x.dtype)[:, None, :]
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


def attend(qi, ti, k, v, window):
    """(R, d) query rows at positions ti (R,) against every key of their
    head, k and v (T, d): softmax of q k^T / sqrt(d) over the keys the mask
    leaves, written out: key j for query t iff 0 <= t - j, and < window
    where one is given."""
    back = ti[:, None] - jnp.arange(k.shape[0])[None, :]
    keep = back >= 0
    if window is not None:
        keep = keep & (back < window)
    s = jnp.where(keep, (qi @ k.T) * (qi.shape[-1] ** -0.5), -jnp.inf)
    return jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(v.dtype) @ v


def attention(x, p, kind, cfg):
    """(T, hidden) -> (T, hidden): the mixer after its input norm, every
    score of one query head (a block of rows) at a time."""
    t = x.shape[0]
    nh, nkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    q = (x @ p["q_proj.weight"]).reshape(t, nh, d)
    k = (x @ p["k_proj.weight"]).reshape(t, nkv, d)
    v = (x @ p["v_proj.weight"]).reshape(t, nkv, d)
    rope = cfg["rope_parameters"][kind]
    q = _rope(_rms(q, p["q_norm.weight"], cfg["rms_norm_eps"]), rope)
    k = _rope(_rms(k, p["k_norm.weight"], cfg["rms_norm_eps"]), rope)
    window = cfg["sliding_window"] if kind == "sliding_attention" else None

    def head(i):
        j = i // (nh // nkv)
        return _row_blocks(
            lambda qi, ti: attend(qi, ti, k[:, j], v[:, j], window),
            q[:, i], jnp.arange(t))

    y = jax.lax.map(head, jnp.arange(nh))                # (nh, T, d)
    return jnp.moveaxis(y, 0, 1).reshape(t, nh * d) @ p["o_proj.weight"]


def attention_grads(q, k, v, do, window, dtype=jnp.float32):
    """(dq, dk, dv) of sum(do * o), o the causal attention of the query
    heads of ONE key-value head: q, do (T, G, d), k, v (T, d), `window`
    keys a query or None. jax.vjp of `attend`, one query head and one block
    of ROW_BLOCK rows at a time (memory only), the blocks' dk and dv summed
    in float32."""
    q, k, v, do = (jnp.asarray(x, dtype) for x in (q, k, v, do))
    t, g, _ = q.shape
    block = ROW_BLOCK if t % ROW_BLOCK == 0 else t
    dq, dk, dv = [], 0.0, 0.0
    for i in range(g):
        rows = []
        for r in range(0, t, block):
            gq, gk, gv = _attend_vjp(q[r:r + block, i],
                                     jnp.arange(r, r + block), k, v,
                                     do[r:r + block, i], window)
            rows.append(gq)
            dk = dk + gk.astype(jnp.float32)
            dv = dv + gv.astype(jnp.float32)
        dq.append(jnp.concatenate(rows))
    return jnp.stack(dq, axis=1), dk, dv


def routed(x, p, cfg):
    """(T, hidden) -> (T, hidden): the held experts' terms of the routed
    sum. With `differentiate_routing` false the weights are constants of
    the backward pass."""
    first, count = cfg["experts_held"]
    k = cfg["num_experts_per_tok"]
    probs = jax.nn.softmax((x @ p["gate.weight"]).astype(jnp.float32), -1)
    top, ids = jax.lax.top_k(probs, k)
    weights = top / jnp.sum(top, axis=-1, keepdims=True)
    if not cfg.get("differentiate_routing", True):
        weights = jax.lax.stop_gradient(weights)
    # [T, count]: a token's weight on each held expert, 0 where not chosen
    held = jnp.stack([jnp.sum(jnp.where(ids == first + j, weights, 0), -1)
                      for j in range(count)], axis=-1)
    if cfg.get("drop_first_held"):
        chosen = held > 0
        first_held = chosen & (jnp.cumsum(chosen, axis=-1) == 1)
        held = jnp.where(first_held, 0, held)
    inter = p["experts.down_proj"].shape[1]
    out = jnp.zeros_like(x)
    for j in range(count):
        h = x @ p["experts.gate_up_proj"][j]
        act = jax.nn.silu(h[:, :inter]) * h[:, inter:]
        out = out + held[:, j:j + 1].astype(x.dtype) \
            * (act @ p["experts.down_proj"][j])
    return out


def _cast(p, prefix, dtype):
    return {k[len(prefix):]: jnp.asarray(v, dtype) for k, v in p.items()
            if k.startswith(prefix)}


def attention_block(h, p, kind, cfg, dtype=jnp.float32):
    """The layer's first residual sub-block on (T, hidden). p: the layer's
    parameters by the suffix after "model.layers.<i>."."""
    ap = _cast(p, "self_attn.", dtype)
    x = _rms(h, ap["input_layernorm.weight"], cfg["rms_norm_eps"])
    return h + attention(x, ap, kind, cfg)


def moe_block(h, p, cfg, dtype=jnp.float32):
    """The layer's second residual sub-block: the routed experts."""
    mp = _cast(p, "mlp.", dtype)

    def rows(hb):
        x = _rms(hb, mp["post_attention_layernorm.weight"],
                 cfg["rms_norm_eps"])
        return hb + routed(x, mp, cfg)

    return _row_blocks(rows, h)


def layer(h, p, kind, cfg, dtype=jnp.float32):
    return moe_block(attention_block(h, p, kind, cfg, dtype), p, cfg, dtype)


def embed(table, ids, dtype=jnp.float32):
    return jnp.asarray(table, dtype)[ids]


def logits(h, norm_w, head_w, cfg, dtype=jnp.float32):
    """(T, hidden) -> (T, vocabulary) float32."""
    norm_w, head_w = jnp.asarray(norm_w, dtype), jnp.asarray(head_w, dtype)
    return (_rms(h, norm_w, cfg["rms_norm_eps"]) @ head_w
            ).astype(jnp.float32)


def head_loss(h, norm_w, head_w, ids, cfg, dtype=jnp.float32):
    """Mean next-token cross entropy of one sequence from (T, hidden);
    the log-softmax in float32 whatever `dtype`."""
    targets = jnp.roll(ids, -1)

    def rows(hb, tgt):
        logp = jax.nn.log_softmax(logits(hb, norm_w, head_w, cfg, dtype), -1)
        return -jnp.take_along_axis(logp, tgt[:, None], axis=-1)[:, 0]

    nll = _row_blocks(rows, h, targets)
    return jnp.mean(nll[:-1])           # the last position predicts nothing


def _layer_params(params, i):
    pre = f"model.layers.{i}."
    return {k[len(pre):]: v for k, v in params.items() if k.startswith(pre)}


def hidden_states(params, ids, cfg, dtype=jnp.float32):
    """(T,) ids of one sequence -> (T, hidden) before the final norm."""
    h = embed(params["model.embed_tokens.weight"], ids, dtype)
    for i, kind in enumerate(cfg["layer_types"]):
        h = layer(h, _layer_params(params, i), kind, cfg, dtype)
    return h


def forward_loss(params, ids, cfg, dtype=jnp.float32):
    """Mean loss over a (B, T) batch as one pure function (differentiable:
    jax.grad gives the reference's gradients at a test size)."""
    total = 0.0
    for b in range(ids.shape[0]):
        total = total + head_loss(
            hidden_states(params, ids[b], cfg, dtype),
            params["model.norm.weight"], params["lm_head.weight"], ids[b],
            cfg, dtype)
    return total / ids.shape[0]


def loss(params, ids, cfg, dtype=jnp.float32, on_block=None):
    """The same number, frugally: sequences and sub-blocks one at a time
    through jitted pieces, each layer's weights cast as they are used.
    `on_block(layer index, sub-layer name in BLOCKS, hidden in, hidden
    out)` is called after every sub-block of the first sequence, so that a
    caller can hold another implementation to the same sub-block on the
    same input."""
    frozen = _freeze(cfg)
    total = 0.0
    for b in range(ids.shape[0]):
        h = _embed_jit(params["model.embed_tokens.weight"], ids[b], dtype)
        for i, kind in enumerate(cfg["layer_types"]):
            p = _layer_params(params, i)
            mid = _attention_jit(h, p, kind, frozen, dtype)
            out = _moe_jit(mid, p, frozen, dtype)
            if on_block is not None and b == 0:
                on_block(i, "self_attn", h, mid)
                on_block(i, "mlp", mid, out)
            h = out
        total += float(_head_jit(h, params["model.norm.weight"],
                                 params["lm_head.weight"], ids[b], frozen,
                                 dtype))
    return total / ids.shape[0]


def _freeze(x):
    """A hashable copy of a config dict (nested dicts and lists)."""
    if isinstance(x, dict):
        return tuple(sorted((k, _freeze(v)) for k, v in x.items()))
    if isinstance(x, (list, tuple)):
        return tuple(_freeze(v) for v in x)
    return x


def _thaw(frozen):
    cfg = dict(frozen)
    cfg["rope_parameters"] = {k: dict(v) for k, v in cfg["rope_parameters"]}
    return cfg


# "highest" is set inside each jitted piece, not around loss(): on_block
# runs the caller's code, which keeps its own matmul precision

@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _attention_jit(h, p, kind, frozen, dtype):
    with jax.default_matmul_precision("highest"):
        return attention_block(h, p, kind, _thaw(frozen), dtype)


@functools.partial(jax.jit, static_argnums=(5,))
def _attend_vjp(qi, ti, k, v, do, window):
    with jax.default_matmul_precision("highest"):
        return jax.vjp(lambda qi, k, v: attend(qi, ti, k, v, window),
                       qi, k, v)[1](do)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _moe_jit(h, p, frozen, dtype):
    with jax.default_matmul_precision("highest"):
        return moe_block(h, p, _thaw(frozen), dtype)


@functools.partial(jax.jit, static_argnums=(2,))
def _embed_jit(table, ids, dtype):
    return embed(table, ids, dtype)


@functools.partial(jax.jit, static_argnums=(4, 5))
def _head_jit(h, norm_w, head_w, ids, frozen, dtype):
    with jax.default_matmul_precision("highest"):
        return head_loss(h, norm_w, head_w, ids, _thaw(frozen), dtype)
