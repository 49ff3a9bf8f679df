"""GPT model family (GPT-3 style) — BASELINE ladder config 5 (1.3B 4D hybrid).

reference capability: PaddleNLP gpt-3 recipe (fleet hybrid-parallel target).
TPU-first: learned positions + pre-LN transformer; attention via the shared
scaled_dot_product_attention path (Pallas on TPU).
"""

from __future__ import annotations

import jax

from .. import nn
from ..framework.core import execute
from ..nn import functional as F
from ..ops.gelu_once import gelu_once
from ..tensor.manipulation import reshape
from ._init import transformer_init_attr


def _init_attr(config):
    # GPT-2 init scheme: every weight matrix N(0, initializer_range),
    # biases zero — nn.Embedding's N(0, 1) default would blow up the
    # tied-softmax logits (init CE ~10x ln(V))
    return transformer_init_attr(config.initializer_range)

__all__ = ["GPTConfig", "GPTModel", "GPTForCausalLM", "gpt_tiny", "gpt3_1p3b"]


class GPTConfig:
    def __init__(self, vocab_size=50304, hidden_size=2048, num_hidden_layers=24,
                 num_attention_heads=16, intermediate_size=None,
                 max_position_embeddings=2048, layer_norm_eps=1e-5,
                 dropout=0.0, tie_word_embeddings=True,
                 initializer_range=0.02):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_hidden_layers = num_hidden_layers
        self.num_attention_heads = num_attention_heads
        self.intermediate_size = intermediate_size or 4 * hidden_size
        self.max_position_embeddings = max_position_embeddings
        self.layer_norm_eps = layer_norm_eps
        self.dropout = dropout
        self.tie_word_embeddings = tie_word_embeddings
        self.initializer_range = initializer_range


class GPTAttention(nn.Layer):
    def __init__(self, config):
        super().__init__()
        h = config.hidden_size
        wa = _init_attr(config)
        self.num_heads = config.num_attention_heads
        self.head_dim = h // self.num_heads
        self.qkv_proj = nn.Linear(h, 3 * h, weight_attr=wa)
        self.out_proj = nn.Linear(h, h, weight_attr=wa)
        self.dropout = config.dropout

    def forward(self, x):
        b, s = x.shape[0], x.shape[1]
        with jax.named_scope("pt.attn.in"):
            qkv = reshape(self.qkv_proj(x),
                          [b, s, 3, self.num_heads, self.head_dim])
            q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        out = F.scaled_dot_product_attention(q, k, v, is_causal=True,
                                             dropout_p=self.dropout,
                                             training=self.training)
        with jax.named_scope("pt.attn.out"):
            return self.out_proj(
                reshape(out, [b, s, self.num_heads * self.head_dim]))


class GPTBlock(nn.Layer):
    def __init__(self, config):
        super().__init__()
        wa = _init_attr(config)
        self.ln_1 = nn.LayerNorm(config.hidden_size, config.layer_norm_eps)
        self.attn = GPTAttention(config)
        self.ln_2 = nn.LayerNorm(config.hidden_size, config.layer_norm_eps)
        self.fc1 = nn.Linear(config.hidden_size, config.intermediate_size,
                             weight_attr=wa)
        self.fc2 = nn.Linear(config.intermediate_size, config.hidden_size,
                             weight_attr=wa)
        self.dropout = nn.Dropout(config.dropout)

    def forward(self, x):
        # component scopes (observability/catalog.py TRACE_SCOPES): the
        # names a device trace attributes this block's operations to; the
        # mixer's parts (pt.attn.in: norm and qkv_proj; pt.attn.out:
        # out_proj and the residual) nest in its scope
        with jax.named_scope("pt.attn"):
            with jax.named_scope("pt.attn.in"):
                normed = self.ln_1(x)
            mixed = self.attn(normed)
            with jax.named_scope("pt.attn.out"):
                x = x + mixed
        with jax.named_scope("pt.mlp"):
            # the activation sits between two matmuls and a GPT step has
            # memory to spare: evaluate it once a layer (ops/gelu_once.py)
            h = execute(gelu_once, self.fc1(self.ln_2(x)), _name="gelu_once")
            h = self.fc2(h)
            return x + self.dropout(h)


class GPTModel(nn.Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        self.config = config
        wa = _init_attr(config)
        self.wte = nn.Embedding(config.vocab_size, config.hidden_size,
                                weight_attr=wa)
        self.wpe = nn.Embedding(config.max_position_embeddings,
                                config.hidden_size, weight_attr=wa)
        self.h = nn.LayerList([GPTBlock(config)
                               for _ in range(config.num_hidden_layers)])
        self.ln_f = nn.LayerNorm(config.hidden_size, config.layer_norm_eps)

    def forward(self, input_ids, position_ids=None):
        if position_ids is None:
            import jax.numpy as jnp
            from ..framework.core import Tensor
            position_ids = Tensor(jnp.arange(input_ids.shape[1])[None, :])
        with jax.named_scope("pt.embed"):
            x = self.wte(input_ids) + self.wpe(position_ids)
        for block in self.h:
            x = block(x)
        with jax.named_scope("pt.head"):
            return self.ln_f(x)


class GPTForCausalLM(nn.Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        self.config = config
        self.gpt = GPTModel(config)
        if not config.tie_word_embeddings:
            self.lm_head = nn.Linear(config.hidden_size, config.vocab_size,
                                     weight_attr=_init_attr(config),
                                     bias_attr=False)
        else:
            self.lm_head = None

    def forward(self, input_ids, position_ids=None, labels=None):
        hidden = self.gpt(input_ids, position_ids)
        with jax.named_scope("pt.head"):
            if self.lm_head is not None:
                logits = self.lm_head(hidden)
            else:
                logits = F.linear(hidden, self.gpt.wte.weight.T)
        if labels is not None:
            # next-token LM loss: predict labels[t+1] from logits[t]
            with jax.named_scope("pt.loss"):
                loss = F.cross_entropy(logits[:, :-1], labels[:, 1:])
            return loss, logits
        return logits

    def generate(self, input_ids, **kwargs):
        """Autoregressive decoding (recompute path; see
        paddle_tpu.generation)."""
        from ..generation import generate
        return generate(self, input_ids, **kwargs)


def gpt_tiny(**kw):
    cfg = dict(vocab_size=512, hidden_size=128, num_hidden_layers=2,
               num_attention_heads=4, max_position_embeddings=256)
    cfg.update(kw)
    return GPTForCausalLM(GPTConfig(**cfg))


def gpt3_1p3b(**kw):
    cfg = dict(vocab_size=50304, hidden_size=2048, num_hidden_layers=24,
               num_attention_heads=16, max_position_embeddings=2048)
    cfg.update(kw)
    return GPTForCausalLM(GPTConfig(**cfg))
