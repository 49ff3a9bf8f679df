"""Loader of the Llama/Mistral decoder family: models/llama.py through
inference.ContinuousBatchingEngine."""

from __future__ import annotations

MODEL_KEYS = ("vocab_size", "hidden_size", "intermediate_size",
              "num_hidden_layers", "num_attention_heads",
              "num_key_value_heads", "max_position_embeddings",
              "rms_norm_eps", "rope_theta", "tie_word_embeddings")
ENGINE_KEYS = ("num_blocks", "block_size", "max_batch", "max_blocks_per_seq",
               "prefill_buckets", "decode_steps", "prefill_chunk",
               "prefill_chunks_per_step", "kv_cache_dtype", "prefix_cache")


def build_engine(config, seed):
    """(engine, model config, parameter count, weights()). The model's own
    constructor fixes names and shapes (its Xavier draws are skipped with
    the framework's global initializer; the embedding's are overwritten);
    the weights come from the seed in one jitted call, in the served type.

    The engine stacks its own copy of the layer weights, so the module's
    copy is dropped once the engine stands (a server has no use for it, and
    on a 16 GB chip it is 3.5 GB). `weights()` makes the same {name: array}
    again from the seed, for the reference, after the engine is gone."""
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu import nn
    from paddle_tpu.inference import ContinuousBatchingEngine
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from harness import weights

    class Placeholder(nn.initializer.Initializer):
        """Zeros in the served type: the constructor's own draw would be
        float32, four times the bytes, and is thrown away."""

        def _init(self, shape, dtype):
            return jnp.zeros(shape, jnp.dtype(config["dtype"]))

    cfg = LlamaConfig(**{k: config[k] for k in MODEL_KEYS if k in config})
    paddle.seed(seed % (2 ** 31 - 1))
    nn.initializer.set_global_initializer(Placeholder())
    try:
        model = LlamaForCausalLM(cfg)
    finally:
        nn.initializer.set_global_initializer(None)
    n_params = weights.install(model, seed, config["dtype"])
    state = model.state_dict()
    shapes = {k: tuple(t.shape) for k, t in state.items()}
    opts = {k: config["engine"][k] for k in ENGINE_KEYS
            if k in config["engine"]}
    if "prefill_buckets" in opts:
        opts["prefill_buckets"] = tuple(opts["prefill_buckets"])
    engine = ContinuousBatchingEngine(model, **opts)
    for name, t in state.items():
        if name.startswith("llama.layers."):
            t._data = jnp.zeros((0,), t._data.dtype)
    del model, state
    return engine, cfg, n_params, (
        lambda: weights.make(shapes, seed, config["dtype"]))


def check_request(state, cfg, prompt, generated, tol):
    """One served request against the float32 reference, judged as
    chip_smoke.py judges: teacher-forced over prompt + generated tokens, at
    every generated position the reference logit of the engine's token must
    lie within `tol` reference-logit standard deviations of the reference
    maximum (random weights flip an argmax on rounding; a lower precision
    than bf16 moves a logit by several times more than 1% of that spread).
    Returns {"argmax_equal", "tokens", "max_gap_in_logit_std", "ok"}."""
    import jax.numpy as jnp
    import numpy as np
    from references import llama_ref

    layers = []
    for i in range(cfg.num_hidden_layers):
        pre = f"llama.layers.{i}."
        layers.append({k[len(pre):]: v for k, v in state.items()
                       if k.startswith(pre)})
    head_w = (state["lm_head.weight"] if "lm_head.weight" in state
              else state["llama.embed_tokens.weight"].T)
    gen = np.asarray(generated, np.int32)
    ids = jnp.asarray(np.concatenate([prompt, gen[:-1]]), jnp.int32)
    rows = np.asarray(llama_ref.logits(
        layers, state["llama.embed_tokens.weight"], state["llama.norm.weight"],
        head_w, ids, int(prompt.size) - 1, nh=cfg.num_attention_heads,
        nkv=cfg.num_key_value_heads, theta=cfg.rope_theta,
        eps=cfg.rms_norm_eps))
    top = rows.max(-1)
    picked = rows[np.arange(gen.size), gen]
    gap = float(((top - picked) / rows.std(-1)).max())
    return {"prompt": int(prompt.size), "tokens": int(gen.size),
            "argmax_equal": int((rows.argmax(-1) == gen).sum()),
            "max_gap_in_logit_std": gap, "ok": bool(gap <= tol)}


def shapes(cfg):
    hd = cfg.hidden_size // cfg.num_attention_heads
    return {"layers": cfg.num_hidden_layers, "hidden": cfg.hidden_size,
            "heads": cfg.num_attention_heads,
            "kv_heads": cfg.num_key_value_heads, "head_dim": hd,
            "ffn": cfg.intermediate_size, "vocab": cfg.vocab_size,
            "matmul_params_per_layer": (
                cfg.hidden_size * hd * (2 * cfg.num_attention_heads
                                        + 2 * cfg.num_key_value_heads)
                + 3 * cfg.hidden_size * cfg.intermediate_size),
            "head_params": cfg.vocab_size * cfg.hidden_size}
