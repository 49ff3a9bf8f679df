"""Mellum 2 (HF `mellum`, JetBrains): a pre-norm decoder whose layers mix
two kinds of attention, three window layers to one full layer, each with
its own rotary frequencies, and whose every feed-forward is a routed
mixture of experts with no shared expert.

Equations, from the published config's keys. For hidden states h
(T x hidden) of one sequence:

    per layer:  h = h + Attn_kind(RMSNorm(h; w_in))
                h = h + MoE(RMSNorm(h; w_post))
    logits = RMSNorm(h; w) @ W_head                       (untied head)

    Attn_kind:  q = x W_q -> (T, heads, d)   k = x W_k, v = x W_v -> (T, kv, d)
                q = RMSNorm_d(q; w_qn)   k = RMSNorm_d(k; w_kn)      per head
                q, k = RoPE_kind(q, k)   rotate-half, positions 0..T-1
                y = softmax(q k^T / sqrt(d) under the kind's mask) v
                out = concat_heads(y) W_o
      `sliding_attention`: default RoPE (theta^(-2i/d)); query t sees keys
          t - sliding_window + 1 .. t (the Hugging Face sliding mask);
      `full_attention`: YaRN frequencies and attention factor
          (models/rope.py), the whole causal triangle.
    MoE:        p = softmax_f32(x W_r) over all num_experts; the top-k, their
                weights renormalised over the k (`norm_topk_prob`), which is
                the softmax over the k chosen logits that
                parallel/moe.py route_top_k computes;
                out = sum_e w_e (silu(x W_gate[e]) * x W_up[e]) W_down[e]
                over the chosen experts that are held here (`experts_held
                = (first, count)`; dropless_moe).

Not in the published config.json and so this program's reading of the
lineages its keys point to (Qwen3-MoE): the per-head RMSNorm on q and k,
pre-norm placement, SwiGLU experts, softmax routing. No auxiliary loss.
The card's multi-token-prediction head has no key in the config and is not
here.

Memory: each mixer, each block of MOE_TOKEN_BLOCK tokens of each MoE and
the head with its loss over each block of LOSS_TOKEN_BLOCK tokens are
rematerialised in the backward (models/sub_block.py).

Trained by parallel.SpmdTrainer: forward(ids, labels) returns the loss
alone; forward(ids) returns the logits.
"""

from __future__ import annotations

import jax

from .. import nn
from ..framework.core import execute
from ..generation import _rms
from ..nn import functional as F
from ..nn import initializer as I
from ..nn.functional.attention import attention_bshd
from ..parallel.moe import dropless_moe
from .rope import norm_rope, rope_frequencies
from .sub_block import Params, SubBlock, blocked_lm_loss, over_token_blocks

__all__ = ["MellumConfig", "MellumModel", "MellumForCausalLM", "mellum_tiny"]

_PERIOD = ("sliding_attention",) * 3 + ("full_attention",)
_ROPE = {
    "full_attention": {
        "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
        "original_max_position_embeddings": 8192, "beta_fast": 32,
        "beta_slow": 1, "attention_factor": 1.2772588722239782},
    "sliding_attention": {"rope_type": "default", "rope_theta": 500000},
}

# The experts run over blocks of this many tokens, one after the other,
# where the tokens are a multiple of it (the sorted assignments' worst case,
# tokens x min(k, held) rows of hidden width, is then per block); the head
# and the loss over blocks of theirs.
MOE_TOKEN_BLOCK = 4096
LOSS_TOKEN_BLOCK = 2048


class MellumConfig:
    """The published config.json's keys, and two of this program's own:
    `experts_held` (first, count) of the `num_experts` routed experts
    (default: all), and `differentiate_routing` (default True; False
    takes the routing out of the backward pass, which a share of the
    experts needs: parallel/moe.py dropless_moe)."""

    def __init__(self, vocab_size=98304, hidden_size=2304,
                 num_hidden_layers=28, layer_types=None,
                 num_attention_heads=32, num_key_value_heads=4, head_dim=128,
                 sliding_window=1024, rope_parameters=None, num_experts=64,
                 num_experts_per_tok=8, moe_intermediate_size=896,
                 norm_topk_prob=True, rms_norm_eps=1e-6,
                 max_position_embeddings=131072, tie_word_embeddings=False,
                 attention_bias=False, initializer_range=0.02,
                 experts_held=None, differentiate_routing=True,
                 dtype="float32"):
        if layer_types is None:
            layer_types = [_PERIOD[i % len(_PERIOD)]
                           for i in range(num_hidden_layers)]
        if len(layer_types) != num_hidden_layers:
            raise ValueError("layer_types must name every layer")
        if tie_word_embeddings or attention_bias or not norm_topk_prob:
            raise NotImplementedError(
                "an untied head, projections without bias and top-k weights "
                "renormalised over the k are what this model implements")
        if num_attention_heads % num_key_value_heads or head_dim % 2:
            raise ValueError("query heads must be a multiple of the "
                             "key-value heads, and head_dim even")
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_hidden_layers = num_hidden_layers
        self.layer_types = list(layer_types)
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads
        self.head_dim = head_dim
        self.sliding_window = sliding_window
        self.rope_parameters = {k: dict(v) for k, v in
                                (rope_parameters or _ROPE).items()}
        unknown = set(self.layer_types) - set(self.rope_parameters)
        if unknown:
            raise ValueError(f"layer types {sorted(unknown)} have no "
                             "rope_parameters entry")
        self.num_experts = num_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.moe_intermediate_size = moe_intermediate_size
        self.norm_topk_prob = norm_topk_prob
        self.rms_norm_eps = rms_norm_eps
        self.max_position_embeddings = max_position_embeddings
        self.tie_word_embeddings = tie_word_embeddings
        self.initializer_range = initializer_range
        self.experts_held = tuple(experts_held or (0, num_experts))
        first, count = self.experts_held
        if first < 0 or count < 1 or first + count > num_experts:
            raise ValueError(f"experts_held {self.experts_held} is not a "
                             f"range of the {num_experts} experts")
        self.differentiate_routing = bool(differentiate_routing)
        self.dtype = dtype

    def window_of(self, layer_type):
        """Keys a query of this kind of layer sees, its own included (None:
        all before it)."""
        return self.sliding_window if layer_type == "sliding_attention" \
            else None


class MellumAttention(SubBlock):
    """One kind's attention mixer with its input norm."""

    def __init__(self, config, layer_type):
        super().__init__()
        self.config = c = config
        self.layer_type = layer_type
        std = I.Normal(std=c.initializer_range)
        one = I.Constant(1.0)
        h, d = c.hidden_size, c.head_dim
        self.input_layernorm = Params(c.dtype, weight=((h,), one))
        for name, width in (("q_proj", c.num_attention_heads * d),
                            ("k_proj", c.num_key_value_heads * d),
                            ("v_proj", c.num_key_value_heads * d)):
            setattr(self, name, Params(c.dtype, weight=((h, width), std)))
        self.q_norm = Params(c.dtype, weight=((d,), one))
        self.k_norm = Params(c.dtype, weight=((d,), one))
        self.o_proj = Params(c.dtype, weight=(
            (c.num_attention_heads * d, h), std))

    def _pure(self, h, input_layernorm_weight, q_proj_weight, k_proj_weight,
              v_proj_weight, q_norm_weight, k_norm_weight, o_proj_weight):
        c = self.config
        sliding = self.layer_type == "sliding_attention"
        with jax.named_scope("pt.attn"), jax.named_scope(
                "pt.attn.sliding" if sliding else "pt.attn.full"):
            b, s, _ = h.shape
            d = c.head_dim
            # the mixer's parts, each a scope inside the kind's
            # (catalog.py TRACE_SCOPES)
            with jax.named_scope("pt.attn.in"):
                x = _rms(h, input_layernorm_weight, c.rms_norm_eps)
                q = (x @ q_proj_weight).reshape(
                    b, s, c.num_attention_heads, d)
                k = (x @ k_proj_weight).reshape(
                    b, s, c.num_key_value_heads, d)
                v = (x @ v_proj_weight).reshape(
                    b, s, c.num_key_value_heads, d)
            with jax.named_scope("pt.attn.pos"):
                rope = rope_frequencies(
                    c.rope_parameters[self.layer_type], d)
                q = norm_rope(q, q_norm_weight, c.rms_norm_eps, *rope)
                k = norm_rope(k, k_norm_weight, c.rms_norm_eps, *rope)
            y = attention_bshd(q, k, v, is_causal=True, scale=d ** -0.5,
                               window=c.window_of(self.layer_type))
            with jax.named_scope("pt.attn.out"):
                return h + y.reshape(b, s, -1) @ o_proj_weight


class MellumSparseMoe(SubBlock):
    """post-attention norm and the routed experts held here; no shared
    expert beside them."""

    def __init__(self, config):
        super().__init__()
        self.config = c = config
        std = I.Normal(std=c.initializer_range)
        count, inter = c.experts_held[1], c.moe_intermediate_size
        self.post_attention_layernorm = Params(
            c.dtype, weight=((c.hidden_size,), I.Constant(1.0)))
        self.gate = Params(c.dtype, weight=(
            (c.hidden_size, c.num_experts), std))
        self.experts = Params(
            c.dtype,
            gate_up_proj=((count, c.hidden_size, 2 * inter), std),
            down_proj=((count, inter, c.hidden_size), std))

    def _pure(self, h, post_attention_layernorm_weight, gate_weight,
              experts_gate_up_proj, experts_down_proj):
        c = self.config
        with jax.named_scope("pt.moe"):
            x = _rms(h, post_attention_layernorm_weight, c.rms_norm_eps)
            routed = dropless_moe(
                x.reshape(-1, x.shape[-1]), gate_weight,
                experts_gate_up_proj, experts_down_proj,
                c.num_experts_per_tok, c.experts_held,
                c.differentiate_routing)
            return h + routed.reshape(x.shape)

    def _over(self, block, h):
        return over_token_blocks(block, h, MOE_TOKEN_BLOCK)


class MellumDecoderLayer(nn.Layer):
    def __init__(self, config, layer_type):
        super().__init__()
        self.self_attn = MellumAttention(config, layer_type)
        self.mlp = MellumSparseMoe(config)

    def forward(self, hidden):
        return self.mlp(self.self_attn(hidden))


class MellumModel(nn.Layer):
    """Embedding and layers; the final norm's weight lives here and is
    applied with the head (MellumForCausalLM), a block of tokens at a time
    when there is a loss to take."""

    def __init__(self, config: MellumConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = Params(config.dtype, weight=(
            (config.vocab_size, config.hidden_size),
            I.Normal(std=config.initializer_range)))
        self.layers = nn.LayerList([MellumDecoderLayer(config, t)
                                    for t in config.layer_types])
        self.norm = Params(config.dtype, weight=(
            (config.hidden_size,), I.Constant(1.0)))

    def forward(self, input_ids):
        with jax.named_scope("pt.embed"):
            hidden = F.embedding(input_ids, self.embed_tokens.weight)
        for layer in self.layers:
            hidden = layer(hidden)
        return hidden


class MellumForCausalLM(nn.Layer):
    def __init__(self, config: MellumConfig):
        super().__init__()
        self.config = config
        self.model = MellumModel(config)
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
                _name="MellumHeadLoss")
        with jax.named_scope("pt.head"):
            return F.linear(F.rms_norm(hidden, self.model.norm.weight,
                                       c.rms_norm_eps), self.lm_head.weight)

    def generate(self, input_ids, **kwargs):
        """No cache path: generation._generic_generate recomputes the
        prefix (a windowed paged cache is ROADMAP M3)."""
        from ..generation import generate
        return generate(self, input_ids, **kwargs)


def mellum_tiny(**kw):
    """Both layer kinds at a test size with the published shape's ratios:
    one period (three window layers of 8 keys, one full layer whose YaRN
    ramp runs inside its 8 rotary pairs), 8 query heads over 1 key-value
    head, 16 experts with top-4."""
    rope = {
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 10000, "factor": 4,
            "original_max_position_embeddings": 16, "beta_fast": 2,
            "beta_slow": 0.25, "attention_factor": 1.1386294361119891},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000},
    }
    cfg = dict(vocab_size=256, hidden_size=64, num_hidden_layers=4,
               num_attention_heads=8, num_key_value_heads=1, head_dim=16,
               sliding_window=8, rope_parameters=rope, num_experts=16,
               num_experts_per_tok=4, moe_intermediate_size=24,
               max_position_embeddings=256)
    cfg.update(kw)
    return MellumForCausalLM(MellumConfig(**cfg))
