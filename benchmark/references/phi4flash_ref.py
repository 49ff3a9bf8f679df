"""Plain float32 jax.numpy Phi-4-mini-flash (HF `phi4flash`; SambaY,
arXiv:2507.06607) forward, next-token loss and, through jax.vjp,
gradients.

Written from the published config's keys and the papers' formulas (Mamba,
arXiv:2312.00752, Algorithm 2; differential attention, arXiv:2410.05258;
the gated memory unit and the decoder-hybrid-decoder, arXiv:2507.06607),
not from the program under test (paddle_tpu/models/phi4flash.py), of which
it imports nothing:

    per layer:  h = h + Mixer_kind(LN(h)),  h = h + MLP(LN(h))
    logits = LN(h) @ E_embed^T;  mean cross entropy of token t + 1 given
    tokens <= t.

- mamba: [x, z] = u W_in; x = silu(b_conv + sum_i w_i x_{t-3+i}) (the taps
  written out); [delta, B, C] = x W_x; Delta = softplus(delta W_dt + b_dt);
  A = -exp(A_log); the recurrence h_t = exp(Delta_t A) h_{t-1} + Delta_t
  B_t u_t, y_t = h_t C_t + D u_t as a PLAIN SEQUENTIAL LOOP over every
  position (lax.scan, one step an iteration); out = (y silu(z)) W_out; the
  memory Mamba's y silu(z) is the memory m;
- attention, differential, DENSE: for each differential head i, q1 and q2
  its two query heads, k1, k2 and v (two value heads side by side) those
  of pair i // (heads / pairs); A1 = softmax(q1 k1^T / sqrt(d_h)) over the
  keys the mask leaves (key j for query t iff 0 <= t - j, and < window in
  a window layer), every score written out; o = RMSNorm(A1 v - lam A2 v)
  w_sub (1 - lam_init), lam = exp(lq1.lk1) - exp(lq2.lk2) + lam_init,
  lam_init = 0.8 - 0.6 exp(-0.3 l) at the layer's published index;
  cross layers project q alone and read the full layer's k and v;
- gmu: (silu(u W_1) m) W_2;  mlp: (u silu(g)) W_2 for [g, u] = x W_1.

Departures from the published model, all of them:
1. the vocabulary is whatever `embed_tokens` has rows for (a slice);
2. float32 everywhere with `jax.default_matmul_precision("highest")`,
   where the released weights run in bf16; `dtype=jnp.bfloat16` computes
   everything in bf16, the recurrent state too (the control the cell's
   limits are set against);
3. memory only: `loss_and_grads()` runs sequences, sub-blocks, heads
   and blocks of ROW_BLOCK rows one at a time, the recurrence in chunks of
   SCAN_CHUNK positions, and `scan_grads` blocks of channels; under a
   gradient each row block and chunk is made again rather than kept
   (jax.checkpoint). The arithmetic is unchanged.

Faults a caller can plant (benchmark/families/phi4flash.py PHI4FLASH_PLANT),
by keys of `cfg` the published model does not have: `reset_state` (the
state dropped every that many steps), `memory_zero` (the GMUs fed zeros),
`kv_own` (the cross layers apply the full layer's key and value
projection to their own input), `lambda_zero` (lam = 0), `sliding_window`
None (no band).

Weights use the names of the model's state_dict ([in, out] matrices).
`cfg` is a plain dict of the config's keys in CFG_KEYS.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

CFG_KEYS = ("layer_types", "layer_indices", "num_attention_heads",
            "num_key_value_heads", "head_dim", "sliding_window",
            "layer_norm_eps", "mamba_d_state", "mamba_dt_rank",
            "mamba_inner", "intermediate_size")
ROW_BLOCK = 2048
SCAN_CHANNELS = 512
SCAN_CHUNK = 256


def _ln(x, w, b, eps):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * w + b


def _silu(x):
    return x * jax.nn.sigmoid(x)


def _row_blocks(fn, *rows):
    """fn over the arrays' leading axis in blocks of ROW_BLOCK, one after
    the other (memory only): fn takes one block of each."""
    n = rows[0].shape[0]
    if n <= ROW_BLOCK or n % ROW_BLOCK:
        return fn(*rows)
    out = jax.lax.map(jax.checkpoint(lambda block: fn(*block)), tuple(
        r.reshape((n // ROW_BLOCK, ROW_BLOCK) + r.shape[1:]) for r in rows))
    return out.reshape((n,) + out.shape[2:])


def selective_scan(u, delta, a, b, c, d, z, delta_bias, reset_state=None):
    """(T, E) gated output of one sequence: Delta = softplus(delta +
    delta_bias), then one position at a time; `reset_state` drops the
    state before every that many positions (a planted fault)."""
    t = u.shape[0]
    dt = jax.nn.softplus(delta + delta_bias)
    every = reset_state or t + 1

    def step(h, xs):
        i, dt_t, u_t, b_t, c_t = xs
        h = jnp.where(i % every == 0, jnp.zeros_like(h), h)
        h = jnp.exp(dt_t[:, None] * a) * h + (dt_t * u_t)[:, None] * b_t
        return h, h @ c_t

    h0 = jnp.zeros(a.shape, u.dtype)
    xs = (jnp.arange(t), dt, u, b, c)
    if t <= SCAN_CHUNK or t % SCAN_CHUNK:
        _, y = jax.lax.scan(step, h0, xs)
    else:                   # the same steps, SCAN_CHUNK to a chunk
        _, y = jax.lax.scan(
            jax.checkpoint(lambda h, chunk: jax.lax.scan(step, h, chunk)),
            h0, tuple(x.reshape((t // SCAN_CHUNK, SCAN_CHUNK) + x.shape[1:])
                      for x in xs))
        y = y.reshape((t,) + y.shape[2:])
    return (y + d * u) * _silu(z)


def mamba(x, p, cfg):
    """(T, hidden) after the input norm -> (out, memory)."""
    e, n, r = cfg["mamba_inner"], cfg["mamba_d_state"], cfg["mamba_dt_rank"]
    xz = x @ p["in_proj.weight"]
    xs, z = xz[:, :e], xz[:, e:]
    w = p["conv1d.weight"]
    width = w.shape[0]
    padded = jnp.pad(xs, ((width - 1, 0), (0, 0)))
    conv = p["conv1d.bias"] + sum(w[i] * padded[i:i + xs.shape[0]]
                                  for i in range(width))
    u = _silu(conv)
    dbc = u @ p["x_proj.weight"]
    delta = dbc[:, :r] @ p["dt_proj.weight"]
    g = selective_scan(u, delta, -jnp.exp(p["A_log"]), dbc[:, r:r + n],
                       dbc[:, r + n:], p["D"], z, p["dt_proj.bias"],
                       cfg.get("reset_state"))
    return g @ p["out_proj.weight"], g


def lambda_init(index):
    return 0.8 - 0.6 * math.exp(-0.3 * index)


def _attend(qi, ti, k, v, window):
    """(R, d_h) query rows at positions ti against every key, k (T, d_h),
    v (T, dv): softmax over the keys the mask leaves, written out."""
    back = ti[:, None] - jnp.arange(k.shape[0])[None, :]
    keep = back >= 0
    if window is not None:
        keep = keep & (back < window)
    s = jnp.where(keep, (qi @ k.T) * (qi.shape[-1] ** -0.5), -jnp.inf)
    return jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(v.dtype) @ v


def differential(q, k, v, p, index, window, cfg):
    """q (T, 2 H, d_h), k, v (T, 2 K, d_h) -> (T, H * 2 d_h)."""
    t, nq, dh = q.shape
    pairs = k.shape[1] // 2
    heads = nq // 2
    init = lambda_init(index)
    lam = (jnp.exp(jnp.sum(p["lambda_q1"] * p["lambda_k1"]))
           - jnp.exp(jnp.sum(p["lambda_q2"] * p["lambda_k2"])) + init)
    if cfg.get("lambda_zero"):
        lam = 0.0 * lam
    eps = cfg["layer_norm_eps"]

    def head(i):
        j = i // (heads // pairs)
        k1, k2 = k[:, 2 * j], k[:, 2 * j + 1]
        vj = jnp.concatenate([v[:, 2 * j], v[:, 2 * j + 1]], -1)

        def rows(qb, tb):
            a1 = _attend(qb[:, 0], tb, k1, vj, window)
            a2 = _attend(qb[:, 1], tb, k2, vj, window)
            o = a1 - lam * a2
            o = o / jnp.sqrt(jnp.mean(o * o, -1, keepdims=True) + eps)
            return o * p["sub_norm.weight"] * (1.0 - init)

        return _row_blocks(rows, jnp.stack([q[:, 2 * i], q[:, 2 * i + 1]], 1),
                           jnp.arange(t))

    y = jax.lax.map(head, jnp.arange(heads))              # (H, T, 2 d_h)
    return jnp.moveaxis(y, 0, 1).reshape(t, heads * 2 * dh)


def _cast(p, prefix, dtype):
    return {k[len(prefix):]: jnp.asarray(v, dtype) for k, v in p.items()
            if k.startswith(prefix)}


def _split_qkv(qkv, cfg):
    t = qkv.shape[0]
    nq, nkv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    return (qkv[:, :nq * dh].reshape(t, nq, dh),
            qkv[:, nq * dh:(nq + nkv) * dh].reshape(t, nkv, dh),
            qkv[:, (nq + nkv) * dh:].reshape(t, nkv, dh))


def mixer_block(h, p, kind, index, cfg, extra=(), dtype=jnp.float32):
    """The layer's first residual sub-block on (T, hidden): (h out, what
    later layers read: the memory, or the full layer's (k, v, its key and
    value projection), or nothing). p: the layer's parameters by the
    suffix after "model.layers.<i>."; extra: the memory for a GMU, the
    full layer's (k, v, projection) for a cross layer."""
    mp = _cast(p, "mixer.", dtype)
    eps = cfg["layer_norm_eps"]
    x = _ln(h, mp["input_layernorm.weight"], mp["input_layernorm.bias"], eps)
    if kind in ("mamba", "memory_mamba"):
        out, memory = mamba(x, mp, cfg)
        return h + out, (memory,) if kind == "memory_mamba" else ()
    if kind == "gmu":
        memory = extra[0].astype(dtype)
        if cfg.get("memory_zero"):
            memory = jnp.zeros_like(memory)
        return h + (_silu(x @ mp["in_proj.weight"]) * memory) \
            @ mp["out_proj.weight"], ()
    nq, dh = cfg["num_attention_heads"], cfg["head_dim"]
    window = cfg["sliding_window"] if kind == "sliding_attention" else None
    if kind == "cross_attention":
        q = (x @ mp["q_proj.weight"]).reshape(-1, nq, dh)
        k, v, kv_weight = (e.astype(dtype) for e in extra)
        if cfg.get("kv_own"):
            _, k, v = _split_qkv(jnp.concatenate(
                [jnp.zeros((x.shape[0], nq * dh), dtype), x @ kv_weight], -1),
                cfg)
        later = ()
    else:
        q, k, v = _split_qkv(x @ mp["qkv_proj.weight"], cfg)
        later = (k, v, mp["qkv_proj.weight"][:, nq * dh:]) \
            if kind == "full_attention" else ()
    y = differential(q, k, v, mp, index, window, cfg)
    return h + y @ mp["o_proj.weight"], later


def mlp_block(h, p, cfg, dtype=jnp.float32):
    """The layer's second residual sub-block."""
    mp = _cast(p, "mlp.", dtype)
    inter = cfg["intermediate_size"]

    def rows(hb):
        x = _ln(hb, mp["post_attention_layernorm.weight"],
                mp["post_attention_layernorm.bias"], cfg["layer_norm_eps"])
        gu = x @ mp["fc1.weight"]
        return hb + (gu[:, inter:] * _silu(gu[:, :inter])) @ mp["fc2.weight"]

    return _row_blocks(rows, h)


def logits(h, params, cfg, dtype=jnp.float32):
    """(T, hidden) -> (T, vocabulary) float32, through the tied head."""
    x = _ln(h, jnp.asarray(params["model.final_layernorm.weight"], dtype),
            jnp.asarray(params["model.final_layernorm.bias"], dtype),
            cfg["layer_norm_eps"])
    return (x @ jnp.asarray(params["model.embed_tokens.weight"], dtype).T
            ).astype(jnp.float32)


def head_loss(h, params, ids, cfg, dtype=jnp.float32):
    """Mean next-token cross entropy of one sequence from (T, hidden)."""
    targets = jnp.roll(ids, -1)

    def rows(hb, tgt):
        logp = jax.nn.log_softmax(logits(hb, params, cfg, dtype), -1)
        return -jnp.take_along_axis(logp, tgt[:, None], axis=-1)[:, 0]

    nll = _row_blocks(rows, h, targets)
    return jnp.mean(nll[:-1])           # the last position predicts nothing


def _layer_params(params, i):
    pre = f"model.layers.{i}."
    return {k[len(pre):]: v for k, v in params.items() if k.startswith(pre)}


def _extra(kind, memory, shared):
    return {"gmu": memory, "cross_attention": shared}.get(kind, ())


def hidden_states(params, ids, cfg, dtype=jnp.float32):
    """(T,) ids of one sequence -> (T, hidden) before the final norm."""
    h = jnp.asarray(params["model.embed_tokens.weight"], dtype)[ids]
    memory = shared = ()
    for i, (kind, index) in enumerate(zip(cfg["layer_types"],
                                          cfg["layer_indices"])):
        p = _layer_params(params, i)
        h, later = mixer_block(h, p, kind, index, cfg,
                               _extra(kind, memory, shared), dtype)
        memory = later if kind == "memory_mamba" else memory
        shared = later if kind == "full_attention" else shared
        h = mlp_block(h, p, cfg, dtype)
    return h


def forward_loss(params, ids, cfg, dtype=jnp.float32):
    """Mean loss over a (B, T) batch as one pure function (differentiable:
    jax.grad gives the reference's gradients at a test size)."""
    total = 0.0
    for b in range(ids.shape[0]):
        total = total + head_loss(hidden_states(params, ids[b], cfg, dtype),
                                  params, ids[b], cfg, dtype)
    return total / ids.shape[0]


TOP = ("model.embed_tokens.weight", "model.final_layernorm.weight",
       "model.final_layernorm.bias")


def loss_and_grads(params, ids, cfg, dtype=jnp.float32, on_block=None):
    """(forward_loss's number, {name: float32 numpy gradient}), frugally:
    one sequence at a time, forward through the jitted sub-blocks keeping
    each layer's input on the host, then back through one layer at a time
    (one jitted vjp of its two sub-blocks, the weights cast to float32
    first), the memory's and the shared keys' and values' cotangents summed
    over the layers that read them. `on_block(layer index, "mixer" or
    "mlp", hidden in, hidden out, what the sub-block read beside the hidden
    states)` is called after every sub-block of the first sequence, so that
    a caller can hold another implementation to the same sub-block on the
    same input."""
    import numpy as np
    frozen = _freeze(cfg)
    kinds = list(zip(cfg["layer_types"], cfg["layer_indices"]))
    total, out = 0.0, {}

    def add(name, g):
        g = np.asarray(g, np.float32) / ids.shape[0]
        out[name] = out[name] + g if name in out else g

    top = {k: params[k] for k in TOP}
    for b in range(ids.shape[0]):
        seq = jnp.asarray(ids[b])
        h = jnp.asarray(top[TOP[0]], dtype)[seq]
        inputs, memory, shared = [], (), ()
        for i, (kind, index) in enumerate(kinds):
            inputs.append(jax.device_get(h))
            p = _layer_params(params, i)
            extra = _extra(kind, memory, shared)
            mid, later = _mixer_jit(h, p, extra, kind, index, frozen, dtype)
            out_h = _mlp_jit(mid, p, frozen, dtype)
            if on_block is not None and b == 0:
                on_block(i, "mixer", h, mid, extra)
                on_block(i, "mlp", mid, out_h, ())
            memory = later if kind == "memory_mamba" else memory
            shared = later if kind == "full_attention" else shared
            h = out_h
        loss, dh, d_top = _head_vjp_jit(h, top, seq, frozen, dtype)
        total += float(loss)
        d_read = {}
        for i in reversed(range(len(kinds))):
            kind, index = kinds[i]
            # every reader of this layer's memory or keys and values is
            # done: what it made is made again inside its own vjp
            memory = () if kind == "memory_mamba" else memory
            shared = () if kind == "full_attention" else shared
            dh, dp, d_extra = _layer_vjp_jit(
                jnp.asarray(inputs[i]), _layer_params(params, i),
                _extra(kind, memory, shared), dh, d_read.pop(kind, None),
                kind, index, frozen, dtype)
            for k, g in dp.items():
                add(f"model.layers.{i}.{k}", g)
            source = {"gmu": "memory_mamba",
                      "cross_attention": "full_attention"}.get(kind)
            if source is not None:
                seen = d_read.get(source)
                d_read[source] = d_extra if seen is None else \
                    jax.tree_util.tree_map(jnp.add, seen, d_extra)
        d_top[TOP[0]] = _embed_transpose_jit(d_top[TOP[0]], seq, dh)
        for k, g in d_top.items():
            add(k, g)
    return total / ids.shape[0], out


def scan_grads(u, delta, a, b, c, d, z, delta_bias, dg, dtype=jnp.float32,
               reset_state=None):
    """The cotangents (du, d delta, da, db, dc, dd, dz, d delta_bias) of
    sum(dg * selective_scan(...)) for one sequence (T, E) operands: jax.vjp
    of the sequential loop, SCAN_CHANNELS channels at a time (the channels
    are independent; db and dc summed over the blocks in float32)."""
    e = u.shape[1]
    step = SCAN_CHANNELS if e % SCAN_CHANNELS == 0 else e
    out = [[] for _ in range(8)]
    db = dc = 0.0
    for lo in range(0, e, step):
        cols = slice(lo, lo + step)
        g = _scan_vjp(*(jnp.asarray(x, dtype) for x in (
            u[:, cols], delta[:, cols], a[cols], b, c, d[cols], z[:, cols],
            delta_bias[cols], dg[:, cols])), reset_state)
        for j, x in enumerate(g):
            out[j].append(x)
        db = db + g[3].astype(jnp.float32)
        dc = dc + g[4].astype(jnp.float32)
    whole = [jnp.concatenate(x, axis=0 if j in (2, 5, 7) else 1)
             if j not in (3, 4) else None for j, x in enumerate(out)]
    whole[3], whole[4] = db, dc
    return tuple(whole)


def _freeze(x):
    """A hashable copy of a config dict (nested lists)."""
    if isinstance(x, dict):
        return tuple(sorted((k, _freeze(v)) for k, v in x.items()))
    if isinstance(x, (list, tuple)):
        return tuple(_freeze(v) for v in x)
    return x


# "highest" is set inside each jitted piece, not around loss_and_grads():
# on_block runs the caller's code, which keeps its own matmul precision

@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6))
def _mixer_jit(h, p, extra, kind, index, frozen, dtype):
    with jax.default_matmul_precision("highest"):
        return mixer_block(h, p, kind, index, dict(frozen), extra, dtype)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _mlp_jit(h, p, frozen, dtype):
    with jax.default_matmul_precision("highest"):
        return mlp_block(h, p, dict(frozen), dtype)


def _f32(tree):
    return jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), tree)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _head_vjp_jit(h, top, ids, frozen, dtype):
    """(loss, d hidden, {top weight: gradient}) of one sequence."""
    with jax.default_matmul_precision("highest"):
        loss, vjp = jax.vjp(lambda h, t: head_loss(h, t, ids, dict(frozen),
                                                   dtype), h, _f32(top))
        return (loss, *vjp(jnp.ones((), jnp.float32)))


@functools.partial(jax.jit, static_argnums=(5, 6, 7, 8))
def _layer_vjp_jit(h, p, extra, dh, d_later, kind, index, frozen, dtype):
    """(d input, {weight: gradient}, d extra) of one layer, given the
    cotangents of its output and of what later layers read of it (None:
    nothing reads it)."""
    cfg = dict(frozen)

    def layer(h, p, extra):
        mid, later = mixer_block(h, p, kind, index, cfg, extra, dtype)
        return mlp_block(mid, p, cfg, dtype), later

    with jax.default_matmul_precision("highest"):
        (out, later), vjp = jax.vjp(layer, h, _f32(p), extra)
        if d_later is None:
            d_later = jax.tree_util.tree_map(jnp.zeros_like, later)
        return vjp((dh.astype(out.dtype), jax.tree_util.tree_map(
            lambda d, x: d.astype(x.dtype), d_later, later)))


@jax.jit
def _embed_transpose_jit(d_embed, ids, dh):
    return d_embed + jnp.zeros_like(d_embed).at[ids].add(
        dh.astype(jnp.float32))


@functools.partial(jax.jit, static_argnums=(9,))
def _scan_vjp(u, delta, a, b, c, d, z, delta_bias, dg, reset_state):
    with jax.default_matmul_precision("highest"):
        return jax.vjp(lambda *x: selective_scan(*x, reset_state=reset_state),
                       u, delta, a, b, c, d, z, delta_bias)[1](dg)
