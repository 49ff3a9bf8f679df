"""Loader of the GPT family: models/gpt.py through parallel.SpmdTrainer."""

from __future__ import annotations

MODEL_KEYS = ("vocab_size", "hidden_size", "num_hidden_layers",
              "num_attention_heads", "intermediate_size",
              "max_position_embeddings", "layer_norm_eps",
              "tie_word_embeddings", "initializer_range")


def build_trainer(config, traffic, seed):
    """(trainer, model config, parameter count): the model from the
    program's constructor, weights from the seed, AdamW at the traffic's
    fixed learning rate, the mesh the configuration's deployment states."""
    import jax
    import numpy as np
    from jax.sharding import PartitionSpec as P
    import paddle_tpu as paddle
    from paddle_tpu import optimizer
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    from paddle_tpu.parallel import (GPT_SHARDING_RULES, SpmdTrainer,
                                     create_mesh)
    from harness import weights

    dep = config["deployment"]
    mesh_axes = dict(dep.get("mesh") or {})
    need = int(np.prod(list(mesh_axes.values()) or [1]))
    devices = list(jax.devices())[:need]
    mesh = create_mesh(devices=devices, **mesh_axes)
    cfg = GPTConfig(**{k: config[k] for k in MODEL_KEYS if k in config})
    paddle.seed(seed % (2 ** 31 - 1))
    model = GPTForCausalLM(cfg)
    n_params = weights.install(model, seed, config["dtype"])
    opt = optimizer.AdamW(float(traffic["learning_rate"]),
                          parameters=model.parameters())
    # the ZeRO axis is a data-parallel axis: the batch is split over it
    data_axes = tuple(a for a in ("sharding", "dp") if mesh_axes.get(a, 1) > 1)
    trainer = SpmdTrainer(model, opt, mesh, GPT_SHARDING_RULES,
                          dtype=config["dtype"],
                          batch_spec=P(data_axes or None),
                          sharding_stage=int(dep.get("sharding_stage", 0)))
    return trainer, cfg, n_params


def reference_loss(trainer, cfg, ids):
    """First-step loss of the float32 reference on the trainer's current
    weights (call before the step that donates them)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from references import gpt_ref
    # the weights live on the trainer's mesh; the batch joins them there
    ids = jax.device_put(ids, NamedSharding(trainer.mesh, P()))
    return gpt_ref.loss(dict(trainer.params), ids,
                        cfg.num_hidden_layers, cfg.num_attention_heads,
                        cfg.layer_norm_eps)


def shapes(cfg):
    """What the operation counts need (harness/flops.py)."""
    return {"layers": cfg.num_hidden_layers, "hidden": cfg.hidden_size,
            "heads": cfg.num_attention_heads,
            "head_dim": cfg.hidden_size // cfg.num_attention_heads,
            "ffn": cfg.intermediate_size, "vocab": cfg.vocab_size,
            "matmul_params_per_layer": (
                cfg.hidden_size * 3 * cfg.hidden_size
                + cfg.hidden_size * cfg.hidden_size
                + 2 * cfg.hidden_size * cfg.intermediate_size),
            "head_params": cfg.vocab_size * cfg.hidden_size}
