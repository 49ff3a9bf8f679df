"""Llama model family — the flagship for the BASELINE ladder (Llama-2-7B).

reference capability: PaddleNLP llama (the reference repo's llm recipe target,
BASELINE.json config 4) built on paddle.incubate fused ops
(fused_rms_norm, fused_rotary_position_embedding, swiglu, flash_attention —
python/paddle/incubate/nn/functional/).

TPU-first design decisions:
- bf16 parameters by default (MXU native), fp32 RMSNorm accumulation.
- Attention through nn.functional.scaled_dot_product_attention: the
  Pallas flash kernels, forward and backward, or dense XLA, by the rule
  on the shape in ops/pallas/attention_router.
- GQA (num_key_value_heads < num_attention_heads) via jnp broadcast —
  no repeat_interleave materialization.
- Shapes arranged (batch, seq, heads, head_dim) so GSPMD shards cleanly:
  dp on batch, mp on heads/ffn, sep on seq (ring attention path).
- paddle_tpu.parallel.SHARDING_RULES_LLAMA maps parameter names to
  PartitionSpecs for the mesh trainer.
"""

from __future__ import annotations

import jax

from .. import nn
from ..incubate.nn.functional import fused_rotary_position_embedding, swiglu
from ..nn import functional as F
from ..tensor.manipulation import reshape

__all__ = ["LlamaConfig", "LlamaModel", "LlamaForCausalLM", "llama_tiny",
           "llama_7b", "llama_13b"]


class LlamaConfig:
    def __init__(self, vocab_size=32000, hidden_size=4096,
                 intermediate_size=11008, num_hidden_layers=32,
                 num_attention_heads=32, num_key_value_heads=None,
                 max_position_embeddings=4096, rms_norm_eps=1e-5,
                 rope_theta=10000.0, tie_word_embeddings=False,
                 dtype="float32", use_flash_attention=True):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.intermediate_size = intermediate_size
        self.num_hidden_layers = num_hidden_layers
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads or num_attention_heads
        self.max_position_embeddings = max_position_embeddings
        self.rms_norm_eps = rms_norm_eps
        self.rope_theta = rope_theta
        self.tie_word_embeddings = tie_word_embeddings
        self.dtype = dtype
        self.use_flash_attention = use_flash_attention


class LlamaAttention(nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        h = config.hidden_size
        self.num_heads = config.num_attention_heads
        self.num_kv_heads = config.num_key_value_heads
        self.head_dim = h // self.num_heads
        self.q_proj = nn.Linear(h, self.num_heads * self.head_dim, bias_attr=False)
        self.k_proj = nn.Linear(h, self.num_kv_heads * self.head_dim, bias_attr=False)
        self.v_proj = nn.Linear(h, self.num_kv_heads * self.head_dim, bias_attr=False)
        self.o_proj = nn.Linear(self.num_heads * self.head_dim, h, bias_attr=False)

    def forward(self, hidden, position_ids=None, attn_mask=None, cache=None):
        b, s = hidden.shape[0], hidden.shape[1]
        q = reshape(self.q_proj(hidden), [b, s, self.num_heads, self.head_dim])
        k = reshape(self.k_proj(hidden), [b, s, self.num_kv_heads, self.head_dim])
        v = reshape(self.v_proj(hidden), [b, s, self.num_kv_heads, self.head_dim])
        q, k, _ = fused_rotary_position_embedding(
            q, k, None, position_ids=position_ids,
            rotary_emb_base=self.config.rope_theta)
        if cache is not None:
            from ..tensor.manipulation import concat
            k = concat([cache[0], k], axis=1)
            v = concat([cache[1], v], axis=1)
            cache = (k, v)
        # GQA kv stays UNEXPANDED: scaled_dot_product_attention groups
        # query heads onto shared KV natively (Pallas BlockSpec index map;
        # the dense path expands inside its traced fn) — so the KV cache
        # above also stays at num_kv_heads, cutting decode cache memory by
        # num_heads/num_kv_heads.
        # always causal (decoder LM): a user-supplied mask (e.g. padding) is
        # combined with, not substituted for, the causal structure
        out = F.scaled_dot_product_attention(
            q, k, v, attn_mask=attn_mask, is_causal=True,
            training=self.training)
        out = self.o_proj(reshape(out, [b, s, self.num_heads * self.head_dim]))
        if cache is not None:
            return out, cache
        return out


class LlamaMLP(nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        h, i = config.hidden_size, config.intermediate_size
        self.gate_proj = nn.Linear(h, i, bias_attr=False)
        self.up_proj = nn.Linear(h, i, bias_attr=False)
        self.down_proj = nn.Linear(i, h, bias_attr=False)

    def forward(self, x):
        return self.down_proj(swiglu(self.gate_proj(x), self.up_proj(x)))


class LlamaDecoderLayer(nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.self_attn = LlamaAttention(config)
        self.mlp = LlamaMLP(config)
        self.input_layernorm = nn.RMSNorm(config.hidden_size, config.rms_norm_eps)
        self.post_attention_layernorm = nn.RMSNorm(config.hidden_size,
                                                   config.rms_norm_eps)

    def forward(self, hidden, position_ids=None, attn_mask=None, cache=None):
        residual = hidden
        with jax.named_scope("pt.attn"):
            h = self.input_layernorm(hidden)
            attn = self.self_attn(h, position_ids, attn_mask, cache)
            if cache is not None:
                attn, cache = attn
            hidden = residual + attn
        residual = hidden
        with jax.named_scope("pt.mlp"):
            hidden = residual + self.mlp(
                self.post_attention_layernorm(hidden))
        if cache is not None:
            return hidden, cache
        return hidden


class LlamaModel(nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = nn.Embedding(config.vocab_size, config.hidden_size)
        self.layers = nn.LayerList(
            [LlamaDecoderLayer(config) for _ in range(config.num_hidden_layers)])
        self.norm = nn.RMSNorm(config.hidden_size, config.rms_norm_eps)

    def forward(self, input_ids, position_ids=None, attn_mask=None):
        with jax.named_scope("pt.embed"):
            hidden = self.embed_tokens(input_ids)
        for layer in self.layers:
            hidden = layer(hidden, position_ids, attn_mask)
        with jax.named_scope("pt.head"):
            return self.norm(hidden)


class LlamaForCausalLM(nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        self.llama = LlamaModel(config)
        if config.tie_word_embeddings:
            self.lm_head = None
        else:
            self.lm_head = nn.Linear(config.hidden_size, config.vocab_size,
                                     bias_attr=False)

    def forward(self, input_ids, position_ids=None, labels=None):
        hidden = self.llama(input_ids, position_ids)
        with jax.named_scope("pt.head"):
            if self.lm_head is not None:
                logits = self.lm_head(hidden)
            else:
                logits = F.linear(hidden, self.llama.embed_tokens.weight.T)
        if labels is not None:
            # next-token LM loss: predict labels[t+1] from logits[t]
            with jax.named_scope("pt.loss"):
                loss = F.cross_entropy(logits[:, :-1], labels[:, 1:],
                                       reduction="mean")
            return loss, logits
        return logits

    def num_params(self):
        import numpy as np
        return sum(int(np.prod(p.shape)) for p in self.parameters())

    def generate(self, input_ids, **kwargs):
        """Compiled KV-cache decoding (see paddle_tpu.generation)."""
        from ..generation import generate
        return generate(self, input_ids, **kwargs)


def llama_tiny(**kw):
    """Small config for tests/dry runs."""
    cfg = dict(vocab_size=512, hidden_size=128, intermediate_size=256,
               num_hidden_layers=2, num_attention_heads=4,
               num_key_value_heads=2, max_position_embeddings=256)
    cfg.update(kw)
    return LlamaForCausalLM(LlamaConfig(**cfg))


def llama_7b(**kw):
    cfg = dict(vocab_size=32000, hidden_size=4096, intermediate_size=11008,
               num_hidden_layers=32, num_attention_heads=32)
    cfg.update(kw)
    return LlamaForCausalLM(LlamaConfig(**cfg))


def llama_13b(**kw):
    cfg = dict(vocab_size=32000, hidden_size=5120, intermediate_size=13824,
               num_hidden_layers=40, num_attention_heads=40)
    cfg.update(kw)
    return LlamaForCausalLM(LlamaConfig(**cfg))
