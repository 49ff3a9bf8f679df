"""Loader of the Granite 4.0-H family (HF `granitemoehybrid`):
models/granite_moe_hybrid.py through parallel.SpmdTrainer.

What the configuration file's keys become:
- the model has the first `num_hidden_layers` entries of `layer_types`
  (the file keeps the published list whole), `vocab_rows` rows of
  vocabulary, a router of `router_outputs` outputs and the experts
  `experts_held = [first, count]` of them (count = `num_local_experts`,
  the experts held here);
- recomputation sits in the model (per mixer, per block of FFN tokens),
  not in the trainer: SpmdTrainer(remat=True) wraps the whole loss in one
  jax.checkpoint, which lowers no peak;
- the model is built under paddle.LazyGuard (weights.install replaces
  every value, so the constructor draws none); after weights.install
  (matrices N(0, 0.02), norms 1, other vectors 0) the Mamba-2 parameters a
  plain draw gets wrong are re-drawn from the seed with the published
  initialisation (the configuration's `assumed`);
- `correct`: reference_loss() holds the loss AND every sub-block of the
  program to the reference (see there; GRANITE_PLANT plants a fault).

Operation count (harness/flops.py is fixed: 6 x (layers x
matmul_params_per_layer + head_params) + 3 x layers x causal attention of
`heads` heads): `shapes()` gives the PERIOD'S MEAN of what one token
multiplies in a layer: the mixer's projections (Mamba in/out, or q/k/v/o),
the shared expert, the router's full width, and top_k x held / routed
experts, the expected number of a token's experts that are held here under
even routing. `heads` is the attention heads times the share of layers
that attend (32 x 1/10 = 3.2), so that the attention term is the one
layer's. Left out, so that the count may fall short and never over: the
state-space scan's own products (about 3% more), the conv, every
elementwise operation, and whatever routing actually sends here beyond
the even share.
"""

from __future__ import annotations

MODEL_KEYS = ("hidden_size", "num_attention_heads", "num_key_value_heads",
              "attention_multiplier", "embedding_multiplier",
              "residual_multiplier", "logits_scaling", "num_experts_per_tok",
              "intermediate_size", "shared_intermediate_size",
              "mamba_n_heads", "mamba_d_head", "mamba_d_state",
              "mamba_n_groups", "mamba_d_conv", "mamba_expand",
              "mamba_chunk_size", "mamba_conv_bias", "mamba_proj_bias",
              "rms_norm_eps", "tie_word_embeddings", "initializer_range")
GAUGES = ("moe_held_assignment_share", "moe_expert_load_max_over_mean")


def model_config(config):
    from paddle_tpu.models.granite_moe_hybrid import GraniteMoeHybridConfig
    layers = int(config["num_hidden_layers"])
    return GraniteMoeHybridConfig(
        vocab_size=int(config["vocab_rows"]), num_hidden_layers=layers,
        layer_types=list(config["layer_types"])[:layers],
        num_local_experts=int(config["router_outputs"]),
        experts_held=tuple(config["experts_held"]), dtype=config["dtype"],
        **{k: config[k] for k in MODEL_KEYS if k in config})


def _redraw_mamba(model, seed):
    """Mamba-2's published initial values for A_log, dt_bias, D and the
    conv taps of every Mamba layer, one key a layer folded from the seed."""
    import jax
    from paddle_tpu.models.granite_moe_hybrid import mamba2_published_init
    key = jax.random.key(seed % (2 ** 31 - 1))
    state = model.state_dict()
    for i, kind in enumerate(model.config.layer_types):
        if kind != "mamba":
            continue
        pre = f"model.layers.{i}.mamba."
        taps = state[pre + "conv1d.weight"]
        drawn = mamba2_published_init(jax.random.fold_in(key, i),
                                      model.config.mamba_n_heads,
                                      tuple(taps.shape))
        for name, value in drawn.items():
            t = state[pre + name]
            t._data = value.astype(t._data.dtype)


def build_trainer(config, traffic, seed):
    """(trainer, model config, parameter count), as families/gpt.py: the
    model from the program's constructor, weights from the seed, AdamW at
    the traffic's fixed learning rate, one chip, no clipping."""
    import jax
    import numpy as np
    from jax.sharding import PartitionSpec as P
    import paddle_tpu as paddle
    from paddle_tpu import optimizer
    from paddle_tpu.models.granite_moe_hybrid import \
        GraniteMoeHybridForCausalLM
    from paddle_tpu.parallel import DP_ONLY_RULES, SpmdTrainer, create_mesh
    from harness import weights

    mesh_axes = dict(config["deployment"].get("mesh") or {})
    need = int(np.prod(list(mesh_axes.values()) or [1]))
    mesh = create_mesh(devices=list(jax.devices())[:need], **mesh_axes)
    cfg = model_config(config)
    paddle.seed(seed % (2 ** 31 - 1))
    with paddle.LazyGuard():        # install follows: nothing is drawn
        model = GraniteMoeHybridForCausalLM(cfg)
    n_params = weights.install(model, seed, config["dtype"])
    _redraw_mamba(model, seed)
    opt = optimizer.AdamW(float(traffic["learning_rate"]),
                          parameters=model.parameters())
    trainer = SpmdTrainer(model, opt, mesh, DP_ONLY_RULES,
                          dtype=config["dtype"], batch_spec=P(None))
    trainer.block_tolerance = {k: float(v) for k, v
                               in traffic["block_tolerance"].items()}
    return trainer, cfg, n_params


def _planted(params, ref_cfg, plant):
    """(parameters, config, dtype) the reference is computed from. With
    GRANITE_PLANT unset: the trainer's own, float32. Otherwise a fault is
    planted in what the UNCHANGED reference is given, so that a run shows
    the comparison failing (`correct` false):
      bf16       everything, the recurrent state too, in bf16: the nearest
                 precision below the program's bf16 operands with float32
                 accumulation
      bf16_scan  the recurrence alone, its state too, in bf16 (what a scan
                 that does not accumulate in float32 would give)
      no_state   dt_bias = -30 in every Mamba layer: the step is 0, the
                 state stays empty, y = D x (a dropped state term)
      no_routed  the experts' output matrices zeroed (a missing routed sum)
      residual   residual_multiplier 0.2 for 0.22 (a wrong multiplier)"""
    import jax.numpy as jnp
    if plant in ("", "bf16"):
        return params, ref_cfg, jnp.bfloat16 if plant else jnp.float32
    params, ref_cfg = dict(params), dict(ref_cfg)
    if plant == "no_state":
        for k in [k for k in params if k.endswith(".mamba.dt_bias")]:
            params[k] = jnp.full_like(params[k], -30.0)
    elif plant == "no_routed":
        for k in [k for k in params if k.endswith("experts.output_linear")]:
            params[k] = jnp.zeros_like(params[k])
    elif plant == "residual":
        ref_cfg["residual_multiplier"] = 0.2
    elif plant == "bf16_scan":
        ref_cfg["scan_dtype"] = "bfloat16"
    else:
        raise SystemExit(f"GRANITE_PLANT={plant!r}: one of bf16, bf16_scan, "
                         "no_state, no_routed, residual")
    return params, ref_cfg, jnp.float32


class _BlockCheck:
    """The program's own sub-blocks (each kind's forward, jitted once, the
    layer's arrays passed in) on the input the reference's sub-block had,
    rounded to the program's type: for each sub-block the error of the
    residual update, |(program out - in) - (reference out - in)| over
    |reference out - in|. Judging each on the reference's input keeps one
    sub-block's error out of the next one's reading. The routing gauges
    are taken here too, where every layer's real input passes by."""

    def __init__(self, trainer, cfg):
        import jax
        import jax.numpy as jnp
        from paddle_tpu.parallel.functional import functional_call
        from paddle_tpu.parallel.moe import route_top_k, sorted_assignments

        self.params, self.cfg = trainer.params, cfg
        self.errors, self.sizes = {}, []
        first = {}
        for layer in trainer.model.model.layers:
            for name, sub in layer._sub_layers.items():
                first.setdefault(name, sub)

        def runner(sub):
            def run(h, arrays):
                x = h.astype(jnp.dtype(cfg.dtype))[None]
                got = functional_call(sub, arrays, x)
                return (got - x)[0].astype(jnp.float32)
            return jax.jit(run)

        self._run = {name: runner(sub) for name, sub in first.items()}

        @jax.jit
        def error(update, h_in, h_out):
            want = (h_out - h_in).astype(jnp.float32)
            return jnp.linalg.norm(update - want) / jnp.linalg.norm(want)

        @jax.jit
        def held_sizes(h, norm_w, router_w):
            # the router's input as the layer makes it: RMSNorm(h) * w
            x = h.astype(jnp.float32)
            x = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                                  + cfg.rms_norm_eps)
            x = (x * norm_w.astype(jnp.float32)).astype(router_w.dtype)
            top_ids, _ = route_top_k(x, router_w, cfg.num_experts_per_tok)
            return sorted_assignments(top_ids, cfg.experts_held)[2]

        self._error, self._held_sizes = error, held_sizes

    def __call__(self, i, name, h_in, h_out):
        pre = f"model.layers.{i}.{name}."
        arrays = {k[len(pre):]: v for k, v in self.params.items()
                  if k.startswith(pre)}
        self.errors[f"{i}.{name}"] = float(self._error(
            self._run[name](h_in, arrays), h_in, h_out))
        if name == "block_sparse_moe":
            self.sizes.append([int(n) for n in self._held_sizes(
                h_in, arrays["post_attention_layernorm.weight"],
                arrays["router.weight"])])

    def set_gauges(self, tokens):
        """moe_held_assignment_share: assignments that landed on held
        experts over tokens x top-k, all layers; moe_expert_load_max_over_
        mean: the fullest held expert over the mean one, averaged over the
        layers. Statistics of the seed's initial weights on the first
        sequence, set once: nothing in the timed window changes them."""
        from paddle_tpu.observability import metrics
        from paddle_tpu.observability.catalog import metric
        per_layer = float(tokens * self.cfg.num_experts_per_tok)
        share = sum(map(sum, self.sizes)) / (per_layer * len(self.sizes))
        load = sum(max(s) * len(s) / float(sum(s) or 1)
                   for s in self.sizes) / len(self.sizes)
        registry = metrics.get_registry()
        was_on = registry.enabled
        registry.enable()      # a gauge of a registry that is off keeps 0
        try:
            metric(GAUGES[0]).set(share)
            metric(GAUGES[1]).set(load)
        finally:
            if not was_on:
                registry.disable()


def reference_loss(trainer, cfg, ids):
    """First-step loss of the float32 reference on the trainer's current
    weights (call before the step that donates them), or NaN.

    harness/runners/train.py compares one number, and at seeded weights the
    loss hardly moves with anything the layers do (ln of the vocabulary
    plus little). So the layers are held here: every sub-block of the
    program against the reference's on the same input (_BlockCheck), each
    within the traffic's `block_tolerance` for its kind. Where one is not,
    the number returned is NaN, which the runner's comparison cannot pass:
    `correct` comes out false. The errors of every sub-block, and which
    were over, go to standard error in any case, as one JSON line."""
    import json
    import os
    import sys
    import jax.numpy as jnp
    from references import granite_hybrid_ref

    ref_cfg = {k: getattr(cfg, k) for k in granite_hybrid_ref.CFG_KEYS}
    params, ref_cfg, dtype = _planted(
        dict(trainer.params), ref_cfg, os.environ.get("GRANITE_PLANT", ""))
    check = _BlockCheck(trainer, cfg)
    loss = granite_hybrid_ref.loss(params, jnp.asarray(ids), ref_cfg, dtype,
                                   on_block=check)
    check.set_gauges(ids.shape[1])
    limits = trainer.block_tolerance            # {sub-layer name: limit}
    over = {k: v for k, v in check.errors.items()
            if not v <= limits[k.split(".", 1)[1]]}
    print("granite_hybrid blocks " + json.dumps(
        {"block_tolerance": limits, "errors": check.errors, "over": over}),
        file=sys.stderr, flush=True)
    return float("nan") if over else loss


def shapes(cfg):
    """What the operation counts need (harness/flops.py); see the module's
    docstring for what is counted and what is left out."""
    h = cfg.hidden_size
    hd = h // cfg.num_attention_heads
    mamba = (h * (2 * cfg.mamba_intermediate + 2 * cfg.mamba_d_state
                  + cfg.mamba_n_heads) + cfg.mamba_intermediate * h)
    attn = (2 * h * cfg.num_attention_heads * hd
            + 2 * h * cfg.num_key_value_heads * hd)
    kinds = cfg.layer_types
    attending = sum(k == "attention" for k in kinds) / float(len(kinds))
    mixer = attending * attn + (1.0 - attending) * mamba
    shared = 3 * h * cfg.shared_intermediate_size
    router = h * cfg.num_local_experts
    routed = (cfg.num_experts_per_tok * cfg.experts_held[1]
              / float(cfg.num_local_experts)) * 3 * h * cfg.intermediate_size
    return {"layers": len(kinds), "hidden": h,
            "heads": cfg.num_attention_heads * attending,
            "kv_heads": cfg.num_key_value_heads * attending,
            "head_dim": hd, "ffn": cfg.shared_intermediate_size,
            "vocab": cfg.vocab_size,
            "matmul_params_per_layer": mixer + shared + router + routed,
            "head_params": cfg.vocab_size * h,
            # for harness/moe_flops.py (the grouped matmuls' roofline)
            "expert_ffn": cfg.intermediate_size,
            "experts_held": cfg.experts_held[1],
            "top_k": cfg.num_experts_per_tok}
