"""Granite 4.0-H family (HF `granitemoehybrid`): Mamba-2 layers with one
NoPE GQA attention layer among them, and in every layer routed experts
beside a shared expert.

Equations, from the published config's keys:

    h = embedding_multiplier * E[ids]
    per layer:  h = h + residual_multiplier * Mixer(RMSNorm(h))
                x = RMSNorm(h)
                h = h + residual_multiplier * (Routed(x) + Shared(x))
    logits = RMSNorm(h) @ E^T / logits_scaling          (tied embedding)

- attention layer (`layer_types[i] == "attention"`): q/k/v/o without bias,
  GQA, causal softmax of q k^T * attention_multiplier, no rotary and no
  position embedding anywhere (`position_embedding_type: "nope"`);
- Mamba-2 layer: [z | xBC | dt] = x W_in; xBC = silu(conv1d(xBC))
  (depthwise, causal, with bias); x as heads, B and C of d_state shared by
  all heads (one group); dt = softplus(dt + dt_bias), A = -exp(A_log);
  S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T, y_t = S_t C_t + D x_t;
  y = RMSNorm(y * silu(z)) * w over all channels; out = y W_out
  (ops/mamba2.py, in chunks of `mamba_chunk_size`);
- routed experts: top-k of the router's logits over ALL experts, gates =
  softmax over the chosen logits, gated-silu experts; `experts_held =
  (first, count)` names the experts this program holds and the layer
  computes their part alone (parallel/moe.py dropless_moe). Shared: the
  same gated form at `shared_intermediate_size`. No auxiliary loss.

Memory: each mixer, and the FFN of each block of FFN_TOKEN_BLOCK tokens,
is rematerialised in the backward, always, so a step holds the sub-blocks'
inputs and one sub-block's internals. The layers are not
stacked and scanned: a scan's backward returns the stacked weight
gradients whole, and the trainer's fused update (6 bytes a parameter)
rests on each gradient dying where it is made.

Trained by parallel.SpmdTrainer like GPTForCausalLM: forward(ids, labels)
returns (loss, logits).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .. import nn
from ..framework.param_attr import ParamAttr
from ..framework.random import next_key
from ..generation import _rms
from ..nn import functional as F
from ..nn import initializer as I
from ..nn.functional.attention import attention_bshd
from ..ops.mamba2 import (causal_conv1d_silu, gated_rms_norm,
                          ssd_chunked_scan)
from ..parallel.moe import dropless_moe
from .sub_block import (Params as _Params, SubBlock as _SubBlock,
                        over_token_blocks)

__all__ = ["GraniteMoeHybridConfig", "GraniteMoeHybridModel",
           "GraniteMoeHybridForCausalLM", "granite_hybrid_tiny",
           "mamba2_published_init"]

_PERIOD = ("mamba",) * 5 + ("attention",) + ("mamba",) * 4

# The FFN runs over blocks of this many tokens, one after the other, where
# the tokens are a multiple of it: the sorted assignments' worst case
# (tokens x min(k, held) rows of hidden width) is then per block. 2048 is
# what lets the published widths' step at 8192 tokens fit a 16 GB chip.
FFN_TOKEN_BLOCK = 2048


class GraniteMoeHybridConfig:
    """The published config.json's keys, and one of this program's own:
    `experts_held` (first, count) of the `num_local_experts` routed
    experts (default: all)."""

    def __init__(self, vocab_size=100352, hidden_size=4096,
                 num_hidden_layers=40, layer_types=None,
                 num_attention_heads=32, num_key_value_heads=8,
                 attention_multiplier=0.0078125, embedding_multiplier=12.0,
                 residual_multiplier=0.22, logits_scaling=16.0,
                 num_local_experts=72, num_experts_per_tok=10,
                 intermediate_size=768, shared_intermediate_size=1536,
                 mamba_n_heads=128, mamba_d_head=64, mamba_d_state=128,
                 mamba_n_groups=1, mamba_d_conv=4, mamba_expand=2,
                 mamba_chunk_size=256, mamba_conv_bias=True,
                 mamba_proj_bias=False, rms_norm_eps=1e-5,
                 tie_word_embeddings=True, initializer_range=0.02,
                 experts_held=None, dtype="float32"):
        if layer_types is None:
            layer_types = [_PERIOD[i % len(_PERIOD)]
                           for i in range(num_hidden_layers)]
        if len(layer_types) != num_hidden_layers:
            raise ValueError("layer_types must name every layer")
        if mamba_n_groups != 1 or not mamba_conv_bias or mamba_proj_bias \
                or not tie_word_embeddings:
            raise NotImplementedError(
                "one B/C group, a conv bias, no projection bias and a tied "
                "embedding are what this model implements")
        if mamba_n_heads * mamba_d_head != mamba_expand * hidden_size:
            raise ValueError("mamba heads x head dim != expand x hidden")
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_hidden_layers = num_hidden_layers
        self.layer_types = list(layer_types)
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads
        self.attention_multiplier = attention_multiplier
        self.embedding_multiplier = embedding_multiplier
        self.residual_multiplier = residual_multiplier
        self.logits_scaling = logits_scaling
        self.num_local_experts = num_local_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.intermediate_size = intermediate_size
        self.shared_intermediate_size = shared_intermediate_size
        self.mamba_n_heads = mamba_n_heads
        self.mamba_d_head = mamba_d_head
        self.mamba_d_state = mamba_d_state
        self.mamba_n_groups = mamba_n_groups
        self.mamba_d_conv = mamba_d_conv
        self.mamba_expand = mamba_expand
        self.mamba_chunk_size = mamba_chunk_size
        self.rms_norm_eps = rms_norm_eps
        self.tie_word_embeddings = tie_word_embeddings
        self.initializer_range = initializer_range
        self.experts_held = tuple(experts_held or (0, num_local_experts))
        first, count = self.experts_held
        if first < 0 or count < 1 or first + count > num_local_experts:
            raise ValueError(f"experts_held {self.experts_held} is not a "
                             f"range of the {num_local_experts} experts")
        self.dtype = dtype

    @property
    def mamba_intermediate(self):
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def mamba_conv_dim(self):
        return self.mamba_intermediate + 2 * self.mamba_d_state


def mamba2_published_init(key, n_heads, conv_shape):
    """Mamba-2's published initial values of the parameters a plain normal
    draw would get wrong, float32: A_log = log U[1, 16]; dt_bias = the
    inverse softplus of a log-uniform step in [0.001, 0.1]; D = 1; the
    conv taps U(-1/sqrt(width), 1/sqrt(width)) (torch's Conv1d default for
    a depthwise conv). conv_shape = (width, channels)."""
    ka, kd, kc = jax.random.split(key, 3)
    a = jax.random.uniform(ka, (n_heads,), jnp.float32, 1.0, 16.0)
    dt = jnp.exp(jax.random.uniform(kd, (n_heads,), jnp.float32,
                                    math.log(1e-3), math.log(1e-1)))
    dt = jnp.maximum(dt, 1e-4)
    bound = 1.0 / math.sqrt(conv_shape[0])
    return {"A_log": jnp.log(a), "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "D": jnp.ones((n_heads,), jnp.float32),
            "conv1d.weight": jax.random.uniform(kc, conv_shape, jnp.float32,
                                                -bound, bound)}


def _gated_mlp(x, w_in, w_out):
    h = x @ w_in
    inter = w_out.shape[0]
    return (jax.nn.silu(h[..., :inter]) * h[..., inter:]) @ w_out


class GraniteMambaMixer(_SubBlock):
    def __init__(self, config):
        super().__init__()
        self.config = c = config
        std = I.Normal(std=c.initializer_range)
        inter, heads = c.mamba_intermediate, c.mamba_n_heads
        self.input_layernorm = _Params(
            c.dtype, weight=((c.hidden_size,), I.Constant(1.0)))
        self.in_proj = _Params(c.dtype, weight=(
            (c.hidden_size, inter + c.mamba_conv_dim + heads), std))
        pub = mamba2_published_init(
            next_key(), heads, (c.mamba_d_conv, c.mamba_conv_dim))
        fixed = {k: I.Assign(v) for k, v in pub.items()}
        self.conv1d = _Params(
            c.dtype,
            weight=((c.mamba_d_conv, c.mamba_conv_dim), fixed["conv1d.weight"]),
            bias=((c.mamba_conv_dim,), I.Constant(0.0)))
        for name in ("dt_bias", "A_log", "D"):
            setattr(self, name, self.create_parameter(
                (heads,), attr=ParamAttr(initializer=fixed[name]),
                dtype=c.dtype))
        self.norm = _Params(c.dtype, weight=((inter,), I.Constant(1.0)))
        self.out_proj = _Params(c.dtype, weight=((inter, c.hidden_size), std))

    def _pure(self, h, input_layernorm_weight, in_proj_weight, conv1d_weight,
              conv1d_bias, dt_bias, A_log, D, norm_weight, out_proj_weight):
        c = self.config
        f32 = jnp.float32
        with jax.named_scope("pt.ssm"):
            b, s, _ = h.shape
            inter, n, heads = (c.mamba_intermediate, c.mamba_d_state,
                               c.mamba_n_heads)
            # the mixer's parts, each a scope of its own inside pt.ssm
            # (catalog.py TRACE_SCOPES); the scan enters pt.ssm.scan itself
            with jax.named_scope("pt.ssm.in"):
                x = _rms(h, input_layernorm_weight, c.rms_norm_eps)
                zxbcdt = x @ in_proj_weight
                z = zxbcdt[..., :inter]
                xbc = zxbcdt[..., inter:inter + c.mamba_conv_dim]
                dt = zxbcdt[..., inter + c.mamba_conv_dim:]
            with jax.named_scope("pt.ssm.conv"):
                xbc = causal_conv1d_silu(xbc, conv1d_weight, conv1d_bias)
                dt = jax.nn.softplus(dt.astype(f32) + dt_bias.astype(f32))
            y = ssd_chunked_scan(
                xbc[..., :inter].reshape(b, s, heads, c.mamba_d_head), dt,
                -jnp.exp(A_log.astype(f32)),
                xbc[..., inter:inter + n], xbc[..., inter + n:],
                D, c.mamba_chunk_size)
            with jax.named_scope("pt.ssm.gate"):
                y = gated_rms_norm(y.reshape(b, s, inter), z, norm_weight,
                                   c.rms_norm_eps)
            with jax.named_scope("pt.ssm.out"):
                return h + c.residual_multiplier * (y @ out_proj_weight)


class GraniteAttentionMixer(_SubBlock):
    def __init__(self, config):
        super().__init__()
        self.config = c = config
        std = I.Normal(std=c.initializer_range)
        hd = c.hidden_size // c.num_attention_heads
        self.head_dim = hd
        self.input_layernorm = _Params(
            c.dtype, weight=((c.hidden_size,), I.Constant(1.0)))
        for name, width in (("q_proj", c.num_attention_heads * hd),
                            ("k_proj", c.num_key_value_heads * hd),
                            ("v_proj", c.num_key_value_heads * hd)):
            setattr(self, name, _Params(
                c.dtype, weight=((c.hidden_size, width), std)))
        self.o_proj = _Params(c.dtype, weight=(
            (c.num_attention_heads * hd, c.hidden_size), std))

    def _pure(self, h, input_layernorm_weight, q_proj_weight, k_proj_weight,
              v_proj_weight, o_proj_weight):
        c = self.config
        with jax.named_scope("pt.attn"):
            b, s, _ = h.shape
            with jax.named_scope("pt.attn.in"):
                x = _rms(h, input_layernorm_weight, c.rms_norm_eps)
                q = (x @ q_proj_weight).reshape(
                    b, s, c.num_attention_heads, self.head_dim)
                k = (x @ k_proj_weight).reshape(
                    b, s, c.num_key_value_heads, self.head_dim)
                v = (x @ v_proj_weight).reshape(
                    b, s, c.num_key_value_heads, self.head_dim)
            # NoPE: no pt.attn.pos here
            out = attention_bshd(q, k, v, is_causal=True,
                                 scale=c.attention_multiplier)
            with jax.named_scope("pt.attn.out"):
                return h + c.residual_multiplier * (
                    out.reshape(b, s, -1) @ o_proj_weight)


class GraniteMoeFFN(_SubBlock):
    """post-mixer norm, routed experts (the held ones) + shared expert."""

    def __init__(self, config):
        super().__init__()
        self.config = c = config
        std = I.Normal(std=c.initializer_range)
        count = c.experts_held[1]
        self.post_attention_layernorm = _Params(
            c.dtype, weight=((c.hidden_size,), I.Constant(1.0)))
        self.router = _Params(c.dtype, weight=(
            (c.hidden_size, c.num_local_experts), std))
        self.experts = _Params(
            c.dtype,
            input_linear=((count, c.hidden_size, 2 * c.intermediate_size),
                          std),
            output_linear=((count, c.intermediate_size, c.hidden_size), std))
        self.shared_mlp = _Params(
            c.dtype,
            input_linear=((c.hidden_size, 2 * c.shared_intermediate_size),
                          std),
            output_linear=((c.shared_intermediate_size, c.hidden_size), std))

    def _pure(self, h, post_attention_layernorm_weight, router_weight,
              experts_input_linear, experts_output_linear,
              shared_mlp_input_linear, shared_mlp_output_linear):
        c = self.config
        with jax.named_scope("pt.mlp"):
            x = _rms(h, post_attention_layernorm_weight, c.rms_norm_eps)
            shared = _gated_mlp(x, shared_mlp_input_linear,
                                shared_mlp_output_linear)
        with jax.named_scope("pt.moe"):
            routed = dropless_moe(
                x.reshape(-1, x.shape[-1]), router_weight,
                experts_input_linear, experts_output_linear,
                c.num_experts_per_tok, c.experts_held).reshape(x.shape)
        with jax.named_scope("pt.mlp"):
            return h + c.residual_multiplier * (routed + shared)

    def _over(self, block, h):
        """Blocks of FFN_TOKEN_BLOCK tokens one after the other, each
        rematerialised on its own."""
        return over_token_blocks(block, h, FFN_TOKEN_BLOCK)


class GraniteDecoderLayer(nn.Layer):
    def __init__(self, config, layer_type):
        super().__init__()
        if layer_type == "mamba":
            self.mamba = GraniteMambaMixer(config)
        elif layer_type == "attention":
            self.self_attn = GraniteAttentionMixer(config)
        else:
            raise ValueError(f"layer type {layer_type!r}")
        self.block_sparse_moe = GraniteMoeFFN(config)

    def forward(self, hidden):
        mixer = self._sub_layers.get("mamba") or self._sub_layers["self_attn"]
        return self.block_sparse_moe(mixer(hidden))


class GraniteMoeHybridModel(nn.Layer):
    def __init__(self, config: GraniteMoeHybridConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = _Params(config.dtype, weight=(
            (config.vocab_size, config.hidden_size),
            I.Normal(std=config.initializer_range)))
        self.layers = nn.LayerList([GraniteDecoderLayer(config, t)
                                    for t in config.layer_types])
        self.norm = _Params(config.dtype, weight=(
            (config.hidden_size,), I.Constant(1.0)))

    def forward(self, input_ids):
        c = self.config
        with jax.named_scope("pt.embed"):
            hidden = F.embedding(input_ids, self.embed_tokens.weight) \
                * c.embedding_multiplier
        for layer in self.layers:
            hidden = layer(hidden)
        with jax.named_scope("pt.head"):
            return F.rms_norm(hidden, self.norm.weight, c.rms_norm_eps)


class GraniteMoeHybridForCausalLM(nn.Layer):
    def __init__(self, config: GraniteMoeHybridConfig):
        super().__init__()
        self.config = config
        self.model = GraniteMoeHybridModel(config)

    def forward(self, input_ids, labels=None):
        hidden = self.model(input_ids)
        with jax.named_scope("pt.head"):
            logits = F.linear(hidden * (1.0 / self.config.logits_scaling),
                              self.model.embed_tokens.weight.T)
        if labels is not None:
            with jax.named_scope("pt.loss"):
                loss = F.cross_entropy(logits[:, :-1], labels[:, 1:])
            return loss, logits
        return logits


def granite_hybrid_tiny(**kw):
    """All three layer kinds at a test size: hidden 64, a shortened period
    (mamba, attention, mamba), 8 experts with top-3."""
    cfg = dict(vocab_size=256, hidden_size=64, num_hidden_layers=3,
               layer_types=["mamba", "attention", "mamba"],
               num_attention_heads=4, num_key_value_heads=2,
               attention_multiplier=1.0 / 16, num_local_experts=8,
               num_experts_per_tok=3, intermediate_size=32,
               shared_intermediate_size=48, mamba_n_heads=8, mamba_d_head=16,
               mamba_d_state=16, mamba_chunk_size=8)
    cfg.update(kw)
    return GraniteMoeHybridForCausalLM(GraniteMoeHybridConfig(**cfg))
