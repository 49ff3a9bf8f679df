"""Phi-4-mini-flash (HF `phi4flash`, Microsoft; the SambaY architecture,
arXiv:2507.06607): a decoder-hybrid-decoder. The self-decoder alternates
Mamba-1 layers with window attention and ends in a Mamba layer whose
gated output is kept as a memory, and one full attention layer whose keys
and values are kept too; the cross-decoder alternates gated memory units
(GMUs), which gate that memory by their own input, with cross-attention
layers, which project queries alone and attend to the full layer's keys
and values. Every attention is differential (arXiv:2410.05258). No
position encoding anywhere: the Mamba layers carry order.

Equations, for hidden states h (T x d) of one sequence, E = 2 d, N the
state size, R the rank of Delta, every layer pre-norm with LayerNorm
(weight and bias):

    per layer:  h = h + Mixer_kind(LN(h)),  h = h + MLP(LN(h))
    MLP:        [g, u] = x W_1;  (u * silu(g)) W_2
    mamba:      [x, z] = u W_in;  x = silu(conv_4(x) + b_conv)
                [delta, B, C] = x W_x;  delta = delta W_dt    (bias in the scan)
                g = selective_scan(x, delta, -exp(A_log), B, C, D, z, b_dt)
                out = g W_out                (ops/selective_scan.py)
    memory_mamba: the same; g is also the memory m
    sliding_attention / full_attention (differential):
                [q, k, v] = u W_qkv, heads of d_h = d / heads lanes
                query heads (2i, 2i+1) are q1_i, q2_i; key-value heads
                (2j, 2j+1) are k1_j, k2_j and, side by side, v_j (2 d_h
                lanes); j = i // (query heads / key-value heads)
                A1 = softmax(q1 k1^T / sqrt(d_h) + mask), A2 likewise
                lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam_init(l)
                o_i = RMSNorm_{2 d_h}(A1 v - lam A2 v) w_sub (1 - lam_init(l))
                out = concat_i(o_i) W_o;  the full layer keeps k and v
      sliding: query t sees keys t - sliding_window + 1 .. t; full: causal
    cross_attention: q = u W_q alone; k and v the full layer's; causal
    gmu:        out = (silu(u W_1) * m) W_2
    logits = LN(h) @ E_embed^T                (tied)

lam_init(l) = 0.8 - 0.6 exp(-0.3 l) at the layer's PUBLISHED index l
(`layer_indices`), so that a model cut in depth keeps each layer's own.

Not in the published config.json, and so this program's reading of the
published modelling code's defaults and of the paper: the Mamba sizes
(state 16, conv 4, expand 2, dt rank ceil(d / 16)), a conv bias and no
projection bias, no bias on the attention projections, the window's edge,
the memory taken after the z gate.

Memory: every mixer and every block of MLP_TOKEN_BLOCK tokens of each MLP
is rematerialised in the backward (models/sub_block.py); the full layer's
keys and values and the memory are a sub-block's outputs and the later
sub-blocks' inputs; the head and the loss run over blocks of
LOSS_TOKEN_BLOCK tokens.

Trained by parallel.SpmdTrainer: forward(ids, labels) returns the loss
alone; forward(ids) returns the logits.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .. import nn
from ..framework.core import execute
from ..framework.param_attr import ParamAttr
from ..framework.random import next_key
from ..nn import functional as F
from ..nn import initializer as I
from ..nn.functional.attention import attention_bshd
from ..ops.mamba2 import causal_conv1d_silu
from ..ops.selective_scan import selective_scan
from .sub_block import (Params, SubBlock, blocked_lm_loss, layer_norm,
                        over_token_blocks)

__all__ = ["Phi4FlashConfig", "Phi4FlashModel", "Phi4FlashForCausalLM",
           "phi4flash_tiny", "published_layer_types", "lambda_init",
           "mamba1_published_init", "differential_attention"]

KINDS = ("mamba", "memory_mamba", "sliding_attention", "full_attention",
         "gmu", "cross_attention")
MLP_TOKEN_BLOCK = 8192
LOSS_TOKEN_BLOCK = 2048


def published_layer_types(num_hidden_layers=32, mb_per_layer=2):
    """The published model's order: the first half a Mamba layer every
    `mb_per_layer` and window attention between; at the half the memory
    Mamba, then the full layer; after it a GMU every `mb_per_layer` and
    cross-attention between."""
    half = num_hidden_layers // 2
    kinds = []
    for i in range(num_hidden_layers):
        mamba = i % mb_per_layer == 0
        if i < half:
            kinds.append("mamba" if mamba else "sliding_attention")
        elif i == half:
            kinds.append("memory_mamba")
        elif i == half + 1:
            kinds.append("full_attention")
        else:
            kinds.append("gmu" if mamba else "cross_attention")
    return kinds


def lambda_init(index):
    """The differential weight's offset at published layer `index`."""
    return 0.8 - 0.6 * math.exp(-0.3 * index)


def mamba1_published_init(key, channels, d_state, dt_rank, conv_width):
    """Mamba-1's published initial values of the parameters a plain normal
    draw would get wrong, float32: A_log = log 1..N for every channel;
    D = 1; dt_proj's weight U(-dt_rank^-0.5, dt_rank^-0.5) and its bias the
    inverse softplus of a log-uniform step in [0.001, 0.1]; the conv's taps
    and bias U(-1/sqrt(width), 1/sqrt(width)) (torch's Conv1d default for
    a depthwise conv)."""
    kw, kd, kc, kb = jax.random.split(key, 4)
    dt = jnp.exp(jax.random.uniform(kd, (channels,), jnp.float32,
                                    math.log(1e-3), math.log(1e-1)))
    dt = jnp.maximum(dt, 1e-4)
    bound, std = 1.0 / math.sqrt(conv_width), dt_rank ** -0.5
    return {
        "A_log": jnp.log(jnp.broadcast_to(
            jnp.arange(1, d_state + 1, dtype=jnp.float32),
            (channels, d_state))),
        "D": jnp.ones((channels,), jnp.float32),
        "dt_proj.weight": jax.random.uniform(kw, (dt_rank, channels),
                                             jnp.float32, -std, std),
        "dt_proj.bias": dt + jnp.log(-jnp.expm1(-dt)),
        "conv1d.weight": jax.random.uniform(kc, (conv_width, channels),
                                            jnp.float32, -bound, bound),
        "conv1d.bias": jax.random.uniform(kb, (channels,), jnp.float32,
                                          -bound, bound)}


class Phi4FlashConfig:
    """The published config.json's keys; `layer_types` and `layer_indices`
    (each layer's kind and published index: a model cut in depth keeps
    both) default to the published order."""

    def __init__(self, vocab_size=200064, hidden_size=2560,
                 intermediate_size=10240, num_hidden_layers=32,
                 num_attention_heads=40, num_key_value_heads=20,
                 sliding_window=512, layer_norm_eps=1e-5, mb_per_layer=2,
                 mamba_d_state=16, mamba_d_conv=4, mamba_expand=2,
                 mamba_dt_rank="auto", layer_types=None, layer_indices=None,
                 tie_word_embeddings=True, mlp_bias=False,
                 lm_head_bias=False, max_position_embeddings=262144,
                 initializer_range=0.02, dtype="float32"):
        if not tie_word_embeddings or mlp_bias or lm_head_bias:
            raise NotImplementedError(
                "a tied head and an MLP without bias are what this model "
                "implements")
        if layer_types is None:
            layer_types = published_layer_types(num_hidden_layers,
                                                mb_per_layer)
        if layer_indices is None:
            layer_indices = list(range(num_hidden_layers))
        if not len(layer_types) == len(layer_indices) == num_hidden_layers:
            raise ValueError("layer_types and layer_indices must name every "
                             "layer")
        unknown = set(layer_types) - set(KINDS)
        if unknown:
            raise ValueError(f"unknown layer types {sorted(unknown)}")
        for kind, source in (("gmu", "memory_mamba"),
                             ("cross_attention", "full_attention")):
            if kind in layer_types and source not in \
                    layer_types[:layer_types.index(kind)]:
                raise ValueError(f"a {kind} layer reads a {source} layer "
                                 "before it")
        head_dim = hidden_size // num_attention_heads
        if (hidden_size % num_attention_heads or num_attention_heads % 2
                or num_key_value_heads % 2
                or num_attention_heads % num_key_value_heads):
            raise ValueError("differential attention pairs the heads: even "
                             "query and key-value heads, the first a "
                             "multiple of the second")
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.intermediate_size = intermediate_size
        self.num_hidden_layers = num_hidden_layers
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads
        self.head_dim = head_dim
        self.sliding_window = sliding_window
        self.layer_norm_eps = layer_norm_eps
        self.mb_per_layer = mb_per_layer
        self.mamba_d_state = mamba_d_state
        self.mamba_d_conv = mamba_d_conv
        self.mamba_expand = mamba_expand
        self.mamba_inner = mamba_expand * hidden_size
        self.mamba_dt_rank = (math.ceil(hidden_size / 16)
                              if mamba_dt_rank == "auto" else mamba_dt_rank)
        self.layer_types = list(layer_types)
        self.layer_indices = [int(i) for i in layer_indices]
        self.tie_word_embeddings = tie_word_embeddings
        self.max_position_embeddings = max_position_embeddings
        self.initializer_range = initializer_range
        self.dtype = dtype

    def window_of(self, layer_type):
        """Keys a query of this kind of layer sees, its own included (None:
        all before it)."""
        return self.sliding_window if layer_type == "sliding_attention" \
            else None


def _norm(c, width):
    return Params(c.dtype, weight=((width,), I.Constant(1.0)),
                  bias=((width,), I.Constant(0.0)))


def differential_attention(q, k, v, lambdas, sub_norm_weight, init, window,
                           eps):
    """The differential heads of q (batch, T, 2 H, d_h) against k, v
    (batch, T, 2 K, d_h) -> (batch, T, H * 2 d_h), q/k/v heads paired as
    the module's docstring says. Both maps are ONE call of the flash
    kernels (on a TPU): query heads [q1 of every pair, q2 of every pair]
    over key-value heads [k1_j beside v_j, k2_j beside v_j], q and k
    zero-padded to v's 2 d_h lanes (zeros add nothing to a score) at the
    scale of d_h."""
    b, t, _, dh = q.shape
    kvh = k.shape[2] // 2
    pad = ((0, 0), (0, 0), (0, 0), (0, dh))
    qq = jnp.pad(jnp.concatenate([q[:, :, 0::2], q[:, :, 1::2]], 2), pad)
    kk = jnp.pad(jnp.concatenate([k[:, :, 0::2], k[:, :, 1::2]], 2), pad)
    vj = v.reshape(b, t, kvh, 2 * dh)
    o = attention_bshd(qq, kk, jnp.concatenate([vj, vj], 2), is_causal=True,
                       scale=dh ** -0.5, window=window)
    with jax.named_scope("pt.attn.diff"):
        lq1, lk1, lq2, lk2 = (x.astype(jnp.float32) for x in lambdas)
        lam = (jnp.exp(jnp.sum(lq1 * lk1)) - jnp.exp(jnp.sum(lq2 * lk2))
               + init)
        heads = q.shape[2] // 2
        o = o.astype(jnp.float32)
        diff = o[:, :, :heads] - lam * o[:, :, heads:]
        diff = diff * jax.lax.rsqrt(jnp.mean(diff * diff, -1, keepdims=True)
                                    + eps)
        diff = diff * sub_norm_weight.astype(jnp.float32) * (1.0 - init)
        return diff.reshape(b, t, heads * 2 * dh).astype(q.dtype)


class _Differential(SubBlock):
    """What the three attention kinds share: the input norm, the four
    lambda vectors, the sub-norm and the output projection."""

    def __init__(self, config, layer_type, index):
        super().__init__()
        self.config = c = config
        self.layer_type = layer_type
        self.init = lambda_init(index)
        dh = c.head_dim
        self.input_layernorm = _norm(c, c.hidden_size)
        lam = I.Normal(std=0.1)
        for name in ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2"):
            setattr(self, name, self.create_parameter(
                (dh,), attr=ParamAttr(initializer=lam), dtype=c.dtype))
        self.sub_norm = Params(c.dtype, weight=((2 * dh,), I.Constant(1.0)))
        self.o_proj = Params(c.dtype, weight=(
            (c.num_attention_heads * dh, c.hidden_size),
            I.Normal(std=c.initializer_range)))

    def _attend(self, h, q, k, v, lambdas, sub_norm_weight, o_proj_weight):
        c = self.config
        y = differential_attention(q, k, v, lambdas, sub_norm_weight,
                                   self.init, c.window_of(self.layer_type),
                                   c.layer_norm_eps)
        with jax.named_scope("pt.attn.out"):
            return h + y @ o_proj_weight


class Phi4FlashSelfAttention(_Differential):
    """A self-decoder attention mixer (window or full): q, k, v from one
    projection. The full layer also returns its k and v."""

    def __init__(self, config, layer_type, index):
        super().__init__(config, layer_type, index)
        c = config
        width = (c.num_attention_heads + 2 * c.num_key_value_heads) \
            * c.head_dim
        self.qkv_proj = Params(c.dtype, weight=(
            (c.hidden_size, width), I.Normal(std=c.initializer_range)))

    def _pure(self, h, input_layernorm_weight, input_layernorm_bias,
              lambda_q1, lambda_k1, lambda_q2, lambda_k2, sub_norm_weight,
              o_proj_weight, qkv_proj_weight):
        c = self.config
        full = self.layer_type == "full_attention"
        with jax.named_scope("pt.attn"), jax.named_scope(
                "pt.attn.full" if full else "pt.attn.sliding"):
            b, s, _ = h.shape
            dh, nq, nkv = (c.head_dim, c.num_attention_heads,
                           c.num_key_value_heads)
            with jax.named_scope("pt.attn.in"):
                x = layer_norm(h, input_layernorm_weight,
                               input_layernorm_bias, c.layer_norm_eps)
                qkv = x @ qkv_proj_weight
                q = qkv[..., :nq * dh].reshape(b, s, nq, dh)
                k = qkv[..., nq * dh:(nq + nkv) * dh].reshape(b, s, nkv, dh)
                v = qkv[..., (nq + nkv) * dh:].reshape(b, s, nkv, dh)
            out = self._attend(h, q, k, v, (lambda_q1, lambda_k1,
                                               lambda_q2, lambda_k2),
                               sub_norm_weight, o_proj_weight)
            return (out, k, v) if full else out


class Phi4FlashCrossAttention(_Differential):
    """A cross-decoder attention mixer: queries from its own input, keys
    and values the full layer's."""

    def __init__(self, config, index):
        super().__init__(config, "cross_attention", index)
        c = config
        self.q_proj = Params(c.dtype, weight=(
            (c.hidden_size, c.num_attention_heads * c.head_dim),
            I.Normal(std=c.initializer_range)))

    def _pure(self, h, k, v, input_layernorm_weight, input_layernorm_bias,
              lambda_q1, lambda_k1, lambda_q2, lambda_k2, sub_norm_weight,
              o_proj_weight, q_proj_weight):
        c = self.config
        with jax.named_scope("pt.attn"), jax.named_scope("pt.attn.cross"):
            b, s, _ = h.shape
            with jax.named_scope("pt.attn.in"):
                x = layer_norm(h, input_layernorm_weight,
                               input_layernorm_bias, c.layer_norm_eps)
                q = (x @ q_proj_weight).reshape(
                    b, s, c.num_attention_heads, c.head_dim)
            return self._attend(h, q, k, v, (lambda_q1, lambda_k1,
                                                lambda_q2, lambda_k2),
                                sub_norm_weight, o_proj_weight)


class Phi4FlashMamba(SubBlock):
    """A Mamba-1 mixer; the memory Mamba also returns its gated output."""

    def __init__(self, config, memory=False):
        super().__init__()
        self.config = c = config
        self.memory = memory
        std = I.Normal(std=c.initializer_range)
        d, e, n, r = (c.hidden_size, c.mamba_inner, c.mamba_d_state,
                      c.mamba_dt_rank)
        pub = {k: I.Assign(v) for k, v in mamba1_published_init(
            next_key(), e, n, r, c.mamba_d_conv).items()}
        self.input_layernorm = _norm(c, d)
        self.in_proj = Params(c.dtype, weight=((d, 2 * e), std))
        self.conv1d = Params(
            c.dtype, weight=((c.mamba_d_conv, e), pub["conv1d.weight"]),
            bias=((e,), pub["conv1d.bias"]))
        self.x_proj = Params(c.dtype, weight=((e, r + 2 * n), std))
        self.dt_proj = Params(c.dtype, weight=((r, e), pub["dt_proj.weight"]),
                              bias=((e,), pub["dt_proj.bias"]))
        self.A_log = self.create_parameter(
            (e, n), attr=ParamAttr(initializer=pub["A_log"]), dtype=c.dtype)
        self.D = self.create_parameter(
            (e,), attr=ParamAttr(initializer=pub["D"]), dtype=c.dtype)
        self.out_proj = Params(c.dtype, weight=((e, d), std))

    def _pure(self, h, A_log, D, input_layernorm_weight, input_layernorm_bias,
              in_proj_weight, conv1d_weight, conv1d_bias, x_proj_weight,
              dt_proj_weight, dt_proj_bias, out_proj_weight):
        c = self.config
        e, n, r = c.mamba_inner, c.mamba_d_state, c.mamba_dt_rank
        with jax.named_scope("pt.ssm"):
            # the mixer's parts (catalog.py TRACE_SCOPES); the recurrence
            # enters pt.ssm.sel itself
            with jax.named_scope("pt.ssm.in"):
                x = layer_norm(h, input_layernorm_weight,
                               input_layernorm_bias, c.layer_norm_eps)
                xz = x @ in_proj_weight
                x, z = xz[..., :e], xz[..., e:]
            with jax.named_scope("pt.ssm.conv"):
                x = causal_conv1d_silu(x, conv1d_weight, conv1d_bias)
                dbc = x @ x_proj_weight
                delta = dbc[..., :r] @ dt_proj_weight
                bm, cm = dbc[..., r:r + n], dbc[..., r + n:]
            g = selective_scan(x, delta, -jnp.exp(A_log.astype(jnp.float32)),
                               bm, cm, D, z, dt_proj_bias)
            with jax.named_scope("pt.ssm.out"):
                out = h + g @ out_proj_weight
            return (out, g) if self.memory else out


class Phi4FlashGMU(SubBlock):
    """A gated memory unit: the memory Mamba's output gated by this
    layer's own input."""

    def __init__(self, config):
        super().__init__()
        self.config = c = config
        std = I.Normal(std=c.initializer_range)
        self.input_layernorm = _norm(c, c.hidden_size)
        self.in_proj = Params(c.dtype, weight=(
            (c.hidden_size, c.mamba_inner), std))
        self.out_proj = Params(c.dtype, weight=(
            (c.mamba_inner, c.hidden_size), std))

    def _pure(self, h, memory, input_layernorm_weight, input_layernorm_bias,
              in_proj_weight, out_proj_weight):
        c = self.config
        with jax.named_scope("pt.gmu"):
            x = layer_norm(h, input_layernorm_weight, input_layernorm_bias,
                           c.layer_norm_eps)
            return h + (jax.nn.silu(x @ in_proj_weight) * memory) \
                @ out_proj_weight


class Phi4FlashMLP(SubBlock):
    def __init__(self, config):
        super().__init__()
        self.config = c = config
        std = I.Normal(std=c.initializer_range)
        self.post_attention_layernorm = _norm(c, c.hidden_size)
        self.fc1 = Params(c.dtype, weight=(
            (c.hidden_size, 2 * c.intermediate_size), std))
        self.fc2 = Params(c.dtype, weight=(
            (c.intermediate_size, c.hidden_size), std))

    def _pure(self, h, post_attention_layernorm_weight,
              post_attention_layernorm_bias, fc1_weight, fc2_weight):
        c = self.config
        with jax.named_scope("pt.mlp"):
            x = layer_norm(h, post_attention_layernorm_weight,
                           post_attention_layernorm_bias, c.layer_norm_eps)
            gu = x @ fc1_weight
            inter = c.intermediate_size
            return h + (gu[..., inter:] * jax.nn.silu(gu[..., :inter])) \
                @ fc2_weight

    def _over(self, block, h):
        return over_token_blocks(block, h, MLP_TOKEN_BLOCK)


def _mixer(config, kind, index):
    if kind in ("mamba", "memory_mamba"):
        return Phi4FlashMamba(config, memory=kind == "memory_mamba")
    if kind == "gmu":
        return Phi4FlashGMU(config)
    if kind == "cross_attention":
        return Phi4FlashCrossAttention(config, index)
    return Phi4FlashSelfAttention(config, kind, index)


class Phi4FlashDecoderLayer(nn.Layer):
    def __init__(self, config, layer_type, index):
        super().__init__()
        self.layer_type = layer_type
        self.mixer = _mixer(config, layer_type, index)
        self.mlp = Phi4FlashMLP(config)


class Phi4FlashModel(nn.Layer):
    """Embedding and layers; the final norm's weights live here and are
    applied with the head (Phi4FlashForCausalLM)."""

    def __init__(self, config: Phi4FlashConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = Params(config.dtype, weight=(
            (config.vocab_size, config.hidden_size),
            I.Normal(std=config.initializer_range)))
        self.layers = nn.LayerList([
            Phi4FlashDecoderLayer(config, t, i) for t, i in
            zip(config.layer_types, config.layer_indices)])
        self.final_layernorm = _norm(config, config.hidden_size)

    def forward(self, input_ids):
        with jax.named_scope("pt.embed"):
            hidden = F.embedding(input_ids, self.embed_tokens.weight)
        memory = kv = None
        for layer in self.layers:
            kind = layer.layer_type
            if kind == "memory_mamba":
                hidden, memory = layer.mixer(hidden)
            elif kind == "full_attention":
                hidden, *kv = layer.mixer(hidden)
            elif kind == "gmu":
                hidden = layer.mixer(hidden, memory)
            elif kind == "cross_attention":
                hidden = layer.mixer(hidden, *kv)
            else:
                hidden = layer.mixer(hidden)
            hidden = layer.mlp(hidden)
        return hidden


class Phi4FlashForCausalLM(nn.Layer):
    def __init__(self, config: Phi4FlashConfig):
        super().__init__()
        self.config = config
        self.model = Phi4FlashModel(config)

    def forward(self, input_ids, labels=None):
        c = self.config
        hidden = self.model(input_ids)
        norm = self.model.final_layernorm
        embed = self.model.embed_tokens.weight
        if labels is not None:
            return execute(
                lambda h, nw, nb, ew, lab: blocked_lm_loss(
                    h, nw, ew.T, lab, c.layer_norm_eps, LOSS_TOKEN_BLOCK,
                    norm_b=nb),
                hidden, norm.weight, norm.bias, embed, labels,
                _name="Phi4FlashHeadLoss")
        with jax.named_scope("pt.head"):
            return execute(
                lambda h, nw, nb, ew: layer_norm(
                    h, nw, nb, c.layer_norm_eps) @ ew.T,
                hidden, norm.weight, norm.bias, embed, _name="Phi4FlashHead")

    def generate(self, input_ids, **kwargs):
        """No cache path: generation._generic_generate recomputes the
        prefix (a cache of Mamba states, one full layer's keys and values
        and a window ring is ROADMAP Reach)."""
        from ..generation import generate
        return generate(self, input_ids, **kwargs)


def phi4flash_tiny(**kw):
    """Every kind of layer at a test size: the cell's cut of the published
    order (two Mamba / window pairs, the memory Mamba, the full layer, two
    GMU / cross pairs) at their published indices, hidden 64, 8 query over
    4 key-value heads of 8 lanes, state 16, window 8."""
    kinds = published_layer_types(32)
    indices = [0, 1, 2, 3, 16, 17, 18, 19, 20, 21]
    cfg = dict(vocab_size=256, hidden_size=64, intermediate_size=96,
               num_hidden_layers=10, num_attention_heads=8,
               num_key_value_heads=4, sliding_window=8,
               layer_types=[kinds[i] for i in indices],
               layer_indices=indices, max_position_embeddings=256)
    cfg.update(kw)
    return Phi4FlashForCausalLM(Phi4FlashConfig(**cfg))
