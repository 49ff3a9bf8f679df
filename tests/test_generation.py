"""Compiled KV-cache generation vs. full-recompute reference.

reference capability: the decode loop the reference serves through
masked_multihead_attention / block_multihead_attention fused kernels +
top_p_sampling. The KV-cache scan must reproduce the model's own forward
exactly (greedy), and sampling knobs must restrict the support.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import generation


def _model():
    paddle.seed(0)
    return paddle.models.llama_tiny(num_hidden_layers=2)


def _greedy_recompute(model, ids, n):
    """Reference: argmax over the model's own (cache-free) forward."""
    ids = jnp.asarray(ids, jnp.int32)
    for _ in range(n):
        logits = model(paddle.Tensor(ids))._data
        nxt = jnp.argmax(logits[:, -1].astype(jnp.float32), axis=-1)
        ids = jnp.concatenate([ids, nxt[:, None]], axis=1)
    return np.asarray(ids)


class TestGenerate:
    def test_kv_cache_matches_recompute_greedy(self):
        model = _model()
        rs = np.random.RandomState(0)
        ids = rs.randint(0, model.config.vocab_size, (2, 7))
        ref = _greedy_recompute(model, ids, 6)
        out = generation.generate(model, jnp.asarray(ids, jnp.int32),
                                  max_new_tokens=6)
        np.testing.assert_array_equal(np.asarray(out._data), ref)

    def test_gqa_and_tied_embeddings(self):
        paddle.seed(1)
        model = paddle.models.llama_tiny(
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, tie_word_embeddings=True)
        rs = np.random.RandomState(1)
        ids = rs.randint(0, model.config.vocab_size, (1, 5))
        ref = _greedy_recompute(model, ids, 4)
        out = generation.generate(model, jnp.asarray(ids, jnp.int32),
                                  max_new_tokens=4)
        np.testing.assert_array_equal(np.asarray(out._data), ref)

    def test_sampling_deterministic_with_seed(self):
        model = _model()
        ids = jnp.ones((2, 4), jnp.int32)
        a = generation.generate(model, ids, max_new_tokens=5, do_sample=True,
                                temperature=0.8, top_p=0.9, seed=7)
        b = generation.generate(model, ids, max_new_tokens=5, do_sample=True,
                                temperature=0.8, top_p=0.9, seed=7)
        np.testing.assert_array_equal(np.asarray(a._data),
                                      np.asarray(b._data))

    def test_top_k_restricts_support(self):
        model = _model()
        ids = jnp.zeros((1, 3), jnp.int32)
        # top_k=1 sampling must equal greedy regardless of temperature
        greedy = generation.generate(model, ids, max_new_tokens=4)
        k1 = generation.generate(model, ids, max_new_tokens=4,
                                 do_sample=True, top_k=1, temperature=5.0,
                                 seed=3)
        np.testing.assert_array_equal(np.asarray(greedy._data),
                                      np.asarray(k1._data))

    def test_eos_padding(self):
        model = _model()
        ids = jnp.ones((1, 3), jnp.int32)
        ref = _greedy_recompute(model, np.asarray(ids), 8)
        eos = int(ref[0, 5])  # force the 3rd generated token to act as EOS
        out = np.asarray(generation.generate(
            model, ids, max_new_tokens=8, eos_token_id=eos)._data)
        # once eos appears, everything after is eos
        after = out[0, 6:]
        assert (after == eos).all()

    def test_zero_max_new_tokens_returns_prompt(self):
        """Both paths must agree: max_new_tokens=0 yields the prompt
        unchanged (the compiled llama path used to emit one token —
        ADVICE r3)."""
        ids = jnp.ones((2, 5), jnp.int32)
        out = generation.generate(_model(), ids, max_new_tokens=0)
        np.testing.assert_array_equal(np.asarray(out._data),
                                      np.asarray(ids))
        out2 = generation.generate(paddle.models.gpt_tiny(), ids,
                                   max_new_tokens=0)
        np.testing.assert_array_equal(np.asarray(out2._data),
                                      np.asarray(ids))

    def test_generic_fallback_gpt(self):
        paddle.seed(2)
        model = paddle.models.gpt_tiny()
        ids = jnp.ones((1, 4), jnp.int32)
        out = generation.generate(model, ids, max_new_tokens=3)
        assert np.asarray(out._data).shape == (1, 7)

    def test_generic_fallback_records_no_tape_for_any_model(self):
        """The recompute path runs every model without a cache path under
        `no_grad`: a causal LM that is neither llama nor GPT sees grad mode
        off in its forward, its logits carry no backward, the caller's
        grad mode comes back, and the tokens are the greedy recompute's."""
        from paddle_tpu import nn

        class BagOfPrefix(nn.Layer):
            def __init__(self):
                super().__init__()
                self.embed = nn.Embedding(32, 16)
                self.head = nn.Linear(16, 32)
                self.seen = []

            def forward(self, ids):
                h = paddle.cumsum(self.embed(ids), axis=1)
                logits = self.head(paddle.nn.functional.gelu(h))
                self.seen.append((paddle.is_grad_enabled(),
                                  logits.stop_gradient))
                return logits

        paddle.seed(3)
        model = BagOfPrefix()
        ids = np.random.RandomState(3).randint(0, 32, (2, 5))
        out = generation.generate(model, jnp.asarray(ids, jnp.int32),
                                  max_new_tokens=3)
        assert model.seen == [(False, True)] * 3
        assert paddle.is_grad_enabled()
        np.testing.assert_array_equal(np.asarray(out._data),
                                      _greedy_recompute(model, ids, 3))
        assert model.seen[-1] == (True, False)   # outside, the tape is on


    def test_generation_tracks_weight_updates(self):
        """The compiled program must take weights as arguments — after an
        optimizer step the same-shape generate call must reflect the new
        parameters (no stale weight constants in the jit cache)."""
        from paddle_tpu import optimizer
        model = _model()
        ids = jnp.ones((1, 4), jnp.int32)
        a = np.asarray(generation.generate(model, ids, max_new_tokens=4)._data)
        opt = optimizer.SGD(0.5, parameters=model.parameters())
        loss, _ = model(paddle.Tensor(ids), labels=paddle.Tensor(ids))
        loss.backward()
        opt.step()
        b = np.asarray(generation.generate(model, ids, max_new_tokens=4)._data)
        ref = _greedy_recompute(model, np.asarray(ids), 4)
        np.testing.assert_array_equal(b, ref)  # matches CURRENT weights


class TestWeightOnlyGenerator:
    """Weight-only int8 serving path (generation.WeightOnlyGenerator):
    int8 quant error must not change the GREEDY argmax on a tiny model,
    and shared-weight rebuilds must not requantize."""

    def test_int8_greedy_parity(self):
        model = _model()
        ids = jnp.ones((2, 4), jnp.int32)
        ref = np.asarray(
            generation.generate(model, ids, max_new_tokens=6)._data)
        wog = generation.WeightOnlyGenerator(model, max_new_tokens=6)
        out = np.asarray(wog.generate(ids)._data)
        np.testing.assert_array_equal(out, ref)
        # int8 + scales + fp leftovers must undercut the f32 state dict
        f32_bytes = sum(int(np.prod(t.shape)) * 4
                        for t in model.state_dict().values())
        assert wog.quantized_bytes() < f32_bytes

    def test_untied_head_and_gqa(self):
        """With an UNTIED head the head weight itself is quantized, so the
        exact reference is generate() on a model whose weights were passed
        through the same quant->dequant — identical math, bit-equal ids."""
        paddle.seed(3)
        model = paddle.models.llama_tiny(
            num_hidden_layers=2, num_key_value_heads=2,
            tie_word_embeddings=False)
        ids = jnp.ones((1, 3), jnp.int32)
        wog = generation.WeightOnlyGenerator(model, max_new_tokens=4)
        out = np.asarray(wog.generate(ids)._data)

        def qdq(v):
            v32 = np.asarray(v, np.float32)
            scale = np.maximum(
                np.max(np.abs(v32), axis=-2, keepdims=True) / 127.0, 1e-8)
            return (np.clip(np.round(v32 / scale), -127, 127)
                    * scale).astype(np.asarray(v).dtype)

        state = model.state_dict()
        saved = {k: t._data for k, t in state.items()}
        for k, t in state.items():
            is_layer_mat = ".layers." in k and np.asarray(t._data).ndim >= 2 \
                and "norm" not in k
            if is_layer_mat or k == "lm_head.weight":
                t._data = jnp.asarray(qdq(t._data))
        try:
            ref = np.asarray(
                generation.generate(model, ids, max_new_tokens=4)._data)
        finally:
            for k, t in state.items():
                t._data = saved[k]
        np.testing.assert_array_equal(out, ref)

    def test_share_weights_from_skips_requantize(self):
        model = _model()
        ids = jnp.ones((1, 4), jnp.int32)
        wog1 = generation.WeightOnlyGenerator(model, max_new_tokens=1)
        wog2 = generation.WeightOnlyGenerator(model, max_new_tokens=5,
                                              share_weights_from=wog1)
        for k in wog1._q:
            assert wog2._q[k] is wog1._q[k]  # same buffers, no requantize
        out = np.asarray(wog2.generate(ids)._data)
        ref = np.asarray(
            generation.generate(model, ids, max_new_tokens=5)._data)
        np.testing.assert_array_equal(out, ref)
