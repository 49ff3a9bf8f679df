"""Brumby (HF `brumby`, Manifest AI): a Qwen3-shaped decoder whose every
attention layer is a power-retention layer (degree-2 gated linear
attention with a carried state, ops/power_retention.py; "Scaling Context
Requires Rethinking Attention", arXiv:2507.04239).

Pre-norm residual blocks, RMSNorm, untied embedding and head, no bias but
the gate's. For hidden states h (T x hidden) of one sequence:

    x   = RMSNorm(h; w_in)
    q   = x W_q -> (T, heads, d)    k = x W_k, v = x W_v -> (T, kv_heads, d)
    gam = x W_g + b_g -> (T, kv_heads)          one gate logit a state head
    q   = RMSNorm_d(q; w_qn)   k = RMSNorm_d(k; w_kn)   per head (Qwen3's)
    q,k = RoPE(q, k; rope_theta, rotate-half pairing, positions 0..T-1)
    y   = power_retention(q, k, v, log sigmoid(gam))    normalised, p = 2
    h   = h + concat_heads(y) W_o
    h   = h + W_down(silu(W_gate x') * (W_up x')),  x' = RMSNorm(h; w_post)

Not in the published config.json and so this program's reading of the
family's description: the degree (2), the gate (one sigmoid per key/value
head from a linear map of the layer's normed input, with a bias), the
1 / sqrt(d) scale inside the power, eps in the normaliser. The chunk size
is the program's choice, not the model's.

RoPE is the default kind of models/rope.py: sin and cos are float32 and
the rotation is made in float32, then rounded once (tests/test_brumby.py
holds it to a float64 rotation).

Memory: each mixer, the FFN of each block of FFN_TOKEN_BLOCK tokens and
the head with its loss over each block of LOSS_TOKEN_BLOCK tokens are
rematerialised in the backward (models/sub_block.py), so a step holds the
sub-blocks' inputs and one sub-block's internals, and no (tokens x
vocabulary) array is ever alive whole.

Trained by parallel.SpmdTrainer: forward(ids, labels) returns the loss
alone (the logits of 16,384 positions are what the blocks exist to
avoid); forward(ids) returns the logits.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .. import nn
from ..framework.core import execute
from ..generation import _rms
from ..nn import functional as F
from ..nn import initializer as I
from ..ops.power_retention import power_retention
from .rope import apply_rope, norm_rope, rope_frequencies
from .sub_block import Params, SubBlock, blocked_lm_loss, over_token_blocks

__all__ = ["BrumbyConfig", "BrumbyModel", "BrumbyForCausalLM", "brumby_tiny",
           "retention_log_gate"]

# The FFN runs over blocks of this many tokens, one after the other, where
# the tokens are a multiple of it; the head and the loss over blocks of
# theirs (2,048 x 18,992 logits in float32 are 148 MiB).
FFN_TOKEN_BLOCK = 4096
LOSS_TOKEN_BLOCK = 2048


class BrumbyConfig:
    """The published config.json's keys, and two of this program's own:
    `retention_chunk` and `retention_eps` (the module's docstring)."""

    def __init__(self, vocab_size=151936, hidden_size=5120,
                 intermediate_size=17408, num_hidden_layers=40,
                 num_attention_heads=40, num_key_value_heads=8, head_dim=128,
                 rms_norm_eps=1e-6, rope_theta=1000000.0,
                 max_position_embeddings=32768, tie_word_embeddings=False,
                 attention_bias=False, initializer_range=0.02,
                 retention_chunk=1024, retention_eps=1e-6, dtype="float32"):
        if tie_word_embeddings or attention_bias:
            raise NotImplementedError(
                "an untied head and projections without bias are what this "
                "model implements")
        if num_attention_heads % num_key_value_heads or head_dim % 2:
            raise ValueError("query heads must be a multiple of the state "
                             "heads, and head_dim even")
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.intermediate_size = intermediate_size
        self.num_hidden_layers = num_hidden_layers
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads
        self.head_dim = head_dim
        self.rms_norm_eps = rms_norm_eps
        self.rope_theta = rope_theta
        self.max_position_embeddings = max_position_embeddings
        self.tie_word_embeddings = tie_word_embeddings
        self.initializer_range = initializer_range
        self.retention_chunk = retention_chunk
        self.retention_eps = retention_eps
        self.dtype = dtype


def _frequencies(theta, head_dim):
    """The default kind's (inv_freq, attention_factor) (models/rope.py)."""
    return rope_frequencies({"rope_type": "default", "rope_theta": theta},
                            head_dim)


def _rope(x, theta):
    """Default-kind rotate-half RoPE at positions 0..T-1 (models/rope.py)."""
    return apply_rope(x, *_frequencies(theta, x.shape[-1]))


def retention_log_gate(h, norm_weight, gate_weight, gate_bias, eps):
    """log g, float32, (..., state heads): the log-sigmoid of the gate's
    linear map of the layer's normed input. What the mixer decays its
    states by; the benchmark's loader reads the horizons off it."""
    x = _rms(h, norm_weight, eps)
    gam = (x @ gate_weight).astype(jnp.float32) + gate_bias.astype(jnp.float32)
    return jax.nn.log_sigmoid(gam)


class BrumbyRetention(SubBlock):
    def __init__(self, config):
        super().__init__()
        self.config = c = config
        std = I.Normal(std=c.initializer_range)
        one = I.Constant(1.0)
        h, d = c.hidden_size, c.head_dim
        self.input_layernorm = Params(c.dtype, weight=((h,), one))
        for name, width in (("q_proj", c.num_attention_heads * d),
                            ("k_proj", c.num_key_value_heads * d),
                            ("v_proj", c.num_key_value_heads * d)):
            setattr(self, name, Params(c.dtype, weight=((h, width), std)))
        self.g_proj = Params(
            c.dtype, weight=((h, c.num_key_value_heads), std),
            bias=((c.num_key_value_heads,), I.Constant(0.0)))
        self.q_norm = Params(c.dtype, weight=((d,), one))
        self.k_norm = Params(c.dtype, weight=((d,), one))
        self.o_proj = Params(c.dtype, weight=(
            (c.num_attention_heads * d, h), std))

    def _pure(self, h, input_layernorm_weight, q_proj_weight, k_proj_weight,
              v_proj_weight, g_proj_weight, g_proj_bias, q_norm_weight,
              k_norm_weight, o_proj_weight):
        c = self.config
        with jax.named_scope("pt.retn"):
            b, s, _ = h.shape
            d = c.head_dim
            # the mixer's parts, each a scope of its own inside pt.retn
            # (catalog.py TRACE_SCOPES); the retention enters pt.retn.scan
            with jax.named_scope("pt.retn.in"):
                x = _rms(h, input_layernorm_weight, c.rms_norm_eps)
                q = (x @ q_proj_weight).reshape(
                    b, s, c.num_attention_heads, d)
                k = (x @ k_proj_weight).reshape(
                    b, s, c.num_key_value_heads, d)
                v = (x @ v_proj_weight).reshape(
                    b, s, c.num_key_value_heads, d)
                log_g = retention_log_gate(h, input_layernorm_weight,
                                           g_proj_weight, g_proj_bias,
                                           c.rms_norm_eps)
            with jax.named_scope("pt.retn.pos"):
                rope = _frequencies(c.rope_theta, d)
                q = norm_rope(q, q_norm_weight, c.rms_norm_eps, *rope)
                k = norm_rope(k, k_norm_weight, c.rms_norm_eps, *rope)
            y = power_retention(q, k, v, log_g, c.retention_chunk,
                                c.retention_eps)
            with jax.named_scope("pt.retn.out"):
                return h + y.reshape(b, s, -1) @ o_proj_weight


class BrumbyMLP(SubBlock):
    """post-mixer norm and the gated-silu FFN."""

    def __init__(self, config):
        super().__init__()
        self.config = c = config
        std = I.Normal(std=c.initializer_range)
        self.post_attention_layernorm = Params(
            c.dtype, weight=((c.hidden_size,), I.Constant(1.0)))
        self.gate_proj = Params(c.dtype, weight=(
            (c.hidden_size, c.intermediate_size), std))
        self.up_proj = Params(c.dtype, weight=(
            (c.hidden_size, c.intermediate_size), std))
        self.down_proj = Params(c.dtype, weight=(
            (c.intermediate_size, c.hidden_size), std))

    def _pure(self, h, post_attention_layernorm_weight, gate_proj_weight,
              up_proj_weight, down_proj_weight):
        with jax.named_scope("pt.mlp"):
            x = _rms(h, post_attention_layernorm_weight,
                     self.config.rms_norm_eps)
            act = jax.nn.silu(x @ gate_proj_weight) * (x @ up_proj_weight)
            return h + act @ down_proj_weight

    def _over(self, block, h):
        return over_token_blocks(block, h, FFN_TOKEN_BLOCK)


class BrumbyDecoderLayer(nn.Layer):
    def __init__(self, config):
        super().__init__()
        self.retention = BrumbyRetention(config)
        self.mlp = BrumbyMLP(config)

    def forward(self, hidden):
        return self.mlp(self.retention(hidden))


class BrumbyModel(nn.Layer):
    """Embedding and layers; the final norm's weight lives here and is
    applied with the head (BrumbyForCausalLM), a block of tokens at a time
    when there is a loss to take."""

    def __init__(self, config: BrumbyConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = Params(config.dtype, weight=(
            (config.vocab_size, config.hidden_size),
            I.Normal(std=config.initializer_range)))
        self.layers = nn.LayerList([BrumbyDecoderLayer(config)
                                    for _ in range(config.num_hidden_layers)])
        self.norm = Params(config.dtype, weight=(
            (config.hidden_size,), I.Constant(1.0)))

    def forward(self, input_ids):
        with jax.named_scope("pt.embed"):
            hidden = F.embedding(input_ids, self.embed_tokens.weight)
        for layer in self.layers:
            hidden = layer(hidden)
        return hidden


class BrumbyForCausalLM(nn.Layer):
    def __init__(self, config: BrumbyConfig):
        super().__init__()
        self.config = config
        self.model = BrumbyModel(config)
        self.lm_head = Params(config.dtype, weight=(
            (config.hidden_size, config.vocab_size),
            I.Normal(std=config.initializer_range)))

    def forward(self, input_ids, labels=None):
        c = self.config
        hidden = self.model(input_ids)
        if labels is not None:
            return execute(
                lambda h, nw, hw, lab: blocked_lm_loss(
                    h, nw, hw, lab, c.rms_norm_eps, LOSS_TOKEN_BLOCK),
                hidden, self.model.norm.weight, self.lm_head.weight, labels,
                _name="BrumbyHeadLoss")
        with jax.named_scope("pt.head"):
            return F.linear(F.rms_norm(hidden, self.model.norm.weight,
                                       c.rms_norm_eps), self.lm_head.weight)

    def generate(self, input_ids, **kwargs):
        """No cache path: generation._generic_generate recomputes the
        prefix (a carried state per lane is ROADMAP "Reach")."""
        from ..generation import generate
        return generate(self, input_ids, **kwargs)


def brumby_tiny(**kw):
    """A test size with the published shape's ratios: 10 query heads over
    2 state heads (5 a state), head_dim 8, three chunks in 24 tokens."""
    cfg = dict(vocab_size=256, hidden_size=64, intermediate_size=96,
               num_hidden_layers=2, num_attention_heads=10,
               num_key_value_heads=2, head_dim=8, retention_chunk=8,
               max_position_embeddings=256)
    cfg.update(kw)
    return BrumbyForCausalLM(BrumbyConfig(**cfg))
