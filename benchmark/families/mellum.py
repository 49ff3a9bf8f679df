"""Loader of the Mellum 2 family (HF `mellum`, JetBrains):
models/mellum.py through parallel.SpmdTrainer.

What the configuration file's keys become:
- the model has the first `num_hidden_layers` entries of `layer_types`
  (the file keeps the published list whole), `vocab_rows` rows of
  vocabulary, a router of `router_outputs` outputs and the experts
  `experts_held = [first, count]` of them (count = `num_experts`, the
  experts held here), every width as published; `differentiate_routing`
  false takes the routing out of the backward pass, as a share of the
  experts needs (parallel/moe.py dropless_moe);
- recomputation sits in the model (per mixer, per block of MoE tokens, per
  block of head-and-loss tokens), not in the trainer;
- the model is built under paddle.LazyGuard; weights.install then draws
  matrices N(0, 0.02) and sets the norms to 1, and `_redraw` draws two
  kinds again from the seed, so that a token's own embedding row is what
  every router sees, as in a trained model (see there);
- `correct`: reference_loss() holds the loss AND every sub-block of the
  program to the reference, and the window kernels' BACKWARD to the
  reference's gradients (see there; MELLUM_PLANT plants a fault).

Operation count (harness/flops.py is fixed: 6 x (layers x
matmul_params_per_layer + head_params) + 3 x layers x causal attention of
`heads` heads): `shapes()` gives, under `matmul_params_per_layer`, what one
token multiplies in a layer: q, k, v and o, the router's full width, and
top_k x held / routed experts (8 x 16 / 64 = 2), the expected number of a
token's experts that are held here under even routing. `heads` is the
query heads times the share of the causal triangle the layers attend at
the cell's sequence length, the layers' mean: a full layer its whole
triangle, a window layer its band (harness/window_flops.py), so that the
attention term is the pairs the masks leave and never what a kernel
visits. Left out, so that the count may fall short and never over: norms,
RoPE, the softmaxes, every elementwise operation, and whatever routing
actually sends here beyond the even share.
"""

from __future__ import annotations

MODEL_KEYS = ("hidden_size", "num_attention_heads", "num_key_value_heads",
              "head_dim", "sliding_window", "rope_parameters",
              "num_experts_per_tok", "moe_intermediate_size",
              "norm_topk_prob", "rms_norm_eps", "max_position_embeddings",
              "tie_word_embeddings", "attention_bias", "initializer_range",
              "differentiate_routing")
GAUGES = ("moe_held_assignment_share", "moe_expert_load_max_over_mean",
          "attn_window_visited_pair_share")
PLANTS = ("window", "rope", "routed", "bf16")


def model_config(config):
    from paddle_tpu.models.mellum import MellumConfig
    layers = int(config["num_hidden_layers"])
    return MellumConfig(
        vocab_size=int(config["vocab_rows"]), num_hidden_layers=layers,
        layer_types=list(config["layer_types"])[:layers],
        num_experts=int(config["router_outputs"]),
        experts_held=tuple(config["experts_held"]), dtype=config["dtype"],
        **{k: config[k] for k in MODEL_KEYS if k in config})


def _redraw(model, seed):
    """The embedding's rows N(0, 1) and every attention output projection
    N(0, initializer_range / sqrt(2 x layers)) (the residual-projection
    rule of GPT-2's recipe), one key each folded from the seed.

    At N(0, 0.02) everywhere an attention layer's output, nearly the same
    vector at neighbouring positions, is two to three times a token's own
    embedding row and each layer adds to it: every router then sees one
    common vector, sends one expert 3-7 times the mean load, and which of
    its eight choices are among the 16 held is a lottery of the seed (the
    held share of a layer's assignments 0.15-0.36, a run's rate 15,252-
    15,743 tokens/s by seed; PERF.md section 6, PR 34). With these two
    kinds re-drawn a token's row dominates the stream at every depth, as
    it does in a trained model: a held expert sees its 4,096 assignments a
    step (largest / mean load 1.04-1.13 in every layer) and every seed
    does the same work."""
    import jax
    import jax.numpy as jnp
    c = model.config
    key = jax.random.key(seed % (2 ** 31 - 1))
    state = model.state_dict()
    kinds = [("model.embed_tokens.weight", 1.0)] + [
        (f"model.layers.{i}.self_attn.o_proj.weight",
         c.initializer_range / (2 * c.num_hidden_layers) ** 0.5)
        for i in range(c.num_hidden_layers)]
    for i, (name, std) in enumerate(kinds):
        t = state[name]
        t._data = (jax.random.normal(jax.random.fold_in(key, i),
                                     tuple(t.shape), jnp.float32)
                   * std).astype(t._data.dtype)


def build_trainer(config, traffic, seed):
    """(trainer, model config, parameter count), as families/gpt.py: the
    model from the program's constructor, weights from the seed, AdamW at
    the traffic's fixed learning rate, one chip, no clipping."""
    import jax
    import numpy as np
    from jax.sharding import PartitionSpec as P
    import paddle_tpu as paddle
    from paddle_tpu import optimizer
    from paddle_tpu.models.mellum import MellumForCausalLM
    from paddle_tpu.parallel import DP_ONLY_RULES, SpmdTrainer, create_mesh
    from harness import weights

    mesh_axes = dict(config["deployment"].get("mesh") or {})
    need = int(np.prod(list(mesh_axes.values()) or [1]))
    mesh = create_mesh(devices=list(jax.devices())[:need], **mesh_axes)
    cfg = model_config(config)
    # what shapes() counts the attention's pairs at
    cfg.counted_seq = int(traffic["seq"])
    paddle.seed(seed % (2 ** 31 - 1))
    with paddle.LazyGuard():        # install follows: nothing is drawn
        model = MellumForCausalLM(cfg)
    n_params = weights.install(model, seed, config["dtype"])
    _redraw(model, seed)
    opt = optimizer.AdamW(float(traffic["learning_rate"]),
                          parameters=model.parameters())
    trainer = SpmdTrainer(model, opt, mesh, DP_ONLY_RULES,
                          dtype=config["dtype"], batch_spec=P(None))
    trainer.block_tolerance = {k: float(v) for k, v
                               in traffic["block_tolerance"].items()}
    trainer.seed = seed             # window_backward draws its operands
    return trainer, cfg, n_params


def _planted(ref_cfg, plant):
    """(config, dtype) the reference is computed from. With MELLUM_PLANT
    unset: the trainer's own, float32. Otherwise a fault is planted in
    what the UNCHANGED reference is given, so that a run shows the
    comparison failing (`correct` false):
      window  the window layers see the whole causal triangle (a mask, or
              a kernel's band, that is not applied)
      rope    the full layers rotate with the default frequencies and
              factor 1 (YaRN's blend and attention factor left off)
      routed  the first held expert of each token's eight is left out of
              the routed sum (a dropped assignment)
      bf16    everything in bf16: the nearest precision below the
              program's bf16 operands with float32 accumulation"""
    import jax.numpy as jnp
    if plant in ("", "bf16"):
        return ref_cfg, jnp.bfloat16 if plant else jnp.float32
    ref_cfg = dict(ref_cfg)
    if plant == "window":
        ref_cfg["sliding_window"] = None
    elif plant == "rope":
        rope = ref_cfg["rope_parameters"]
        ref_cfg["rope_parameters"] = dict(
            rope, full_attention={
                "rope_type": "default",
                "rope_theta": rope["full_attention"]["rope_theta"]})
    elif plant == "routed":
        ref_cfg["drop_first_held"] = True
    else:
        raise SystemExit(f"MELLUM_PLANT={plant!r}: one of "
                         + ", ".join(PLANTS))
    return ref_cfg, jnp.float32


def block_kind(cfg, i, name):
    """The kind a sub-block's limit is keyed by: the layer's attention
    kind, or `sparse_moe`."""
    return cfg.layer_types[i] if name == "self_attn" else "sparse_moe"


class _BlockCheck:
    """The program's own sub-blocks (each KIND's forward, jitted once, the
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
        for i, layer in enumerate(trainer.model.model.layers):
            for name, sub in layer._sub_layers.items():
                first.setdefault(block_kind(cfg, i, name), sub)

        def runner(sub):
            def run(h, arrays):
                x = h.astype(jnp.dtype(cfg.dtype))[None]
                got = functional_call(sub, arrays, x)
                return (got - x)[0].astype(jnp.float32)
            return jax.jit(run)

        self._run = {kind: runner(sub) for kind, sub in first.items()}

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
        kind = block_kind(self.cfg, i, name)
        self.errors[f"{i}.{kind}"] = float(self._error(
            self._run[kind](h_in, arrays), h_in, h_out))
        if name == "mlp":
            self.sizes.append([int(n) for n in self._held_sizes(
                h_in, arrays["post_attention_layernorm.weight"],
                arrays["gate.weight"])])

    def set_gauges(self, batch, tokens):
        """moe_held_assignment_share and moe_expert_load_max_over_mean as
        families/granite_hybrid.py sets them (statistics of the seed's
        initial weights on the first sequence); attn_window_visited_pair_
        share from the rule's Decision at the window layers' shape. Set
        once: nothing in the timed window changes them."""
        from paddle_tpu.observability import metrics
        from paddle_tpu.observability.catalog import metric
        from paddle_tpu.ops.pallas.attention_router import route
        c = self.cfg
        per_layer = float(tokens * c.num_experts_per_tok)
        share = sum(map(sum, self.sizes)) / (per_layer * len(self.sizes))
        load = sum(max(s) * len(s) / float(sum(s) or 1)
                   for s in self.sizes) / len(self.sizes)
        visited = route(batch * c.num_attention_heads, tokens, tokens,
                        c.head_dim, c.dtype, True,
                        window=c.sliding_window).visited_pair_share
        registry = metrics.get_registry()
        was_on = registry.enabled
        registry.enable()      # a gauge of a registry that is off keeps 0
        try:
            for name, value in zip(GAUGES, (share, load, visited)):
                metric(name).set(value)
        finally:
            if not was_on:
                registry.disable()


def window_backward(cfg, tokens, seed, ref_window, ref_dtype):
    """{dq|dk|dv.window_backward: error}: the gradients of the window
    layers' attention as the program's mixers call it (`attention_bshd`: on
    a TPU the `faw_*` kernels at the tiles the rule hands this length,
    forward and backward) against the reference's dense band-mask gradients
    (`mellum_ref.attention_grads`), |got - want| / |want| of each. The
    sub-block comparison runs forwards only; this is what holds
    `faw_bwd_dq` and `faw_bwd_dkv` on the chip. One key-value head with its
    query heads at the cell's sequence length; operands and cotangent
    N(0, 1) from the seed in the model's type, which is what the q/k norms
    and N(0, 0.02) projections of a normed input give the kernels (scores
    of spread near 1)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.nn.functional.attention import attention_bshd
    from references import mellum_ref
    group = cfg.num_attention_heads // cfg.num_key_value_heads
    d, dtype = cfg.head_dim, jnp.dtype(cfg.dtype)
    keys = jax.random.split(jax.random.key(seed % (2 ** 31 - 1)), 4)
    q, do = (jax.random.normal(k, (1, tokens, group, d), jnp.float32
                               ).astype(dtype) for k in keys[:2])
    k, v = (jax.random.normal(k, (1, tokens, 1, d), jnp.float32
                              ).astype(dtype) for k in keys[2:])

    @jax.jit
    def program(q, k, v, do):
        return jax.vjp(lambda q, k, v: attention_bshd(
            q, k, v, is_causal=True, scale=d ** -0.5,
            window=cfg.sliding_window), q, k, v)[1](do)

    got = program(q, k, v, do)
    want = mellum_ref.attention_grads(q[0], k[0, :, 0], v[0, :, 0], do[0],
                                      ref_window, ref_dtype)
    return {f"{name}.window_backward": float(
        jnp.linalg.norm(g.astype(jnp.float32).reshape(w.shape)
                        - w.astype(jnp.float32))
        / jnp.linalg.norm(w.astype(jnp.float32)))
        for name, g, w in zip(("dq", "dk", "dv"), got, want)}


def reference_loss(trainer, cfg, ids):
    """First-step loss of the float32 reference on the trainer's current
    weights (call before the step that donates them), or NaN.

    harness/runners/train.py compares one number, and at seeded weights the
    loss hardly moves with anything the layers do (ln of the vocabulary
    plus little). So the layers are held here, as families/
    granite_hybrid.py holds its own: every sub-block of the program
    against the reference's on the same input (_BlockCheck), each within
    the traffic's `block_tolerance` for its kind (`sliding_attention`,
    `full_attention`, `sparse_moe`), and the window attention's gradients
    with them (`window_backward`, limit of that name). Where one is not,
    the number returned is NaN, which the runner's comparison cannot pass:
    `correct` comes out false. The errors of every sub-block, and which were over, and the
    reference's loss as it was computed go to standard error in any case,
    as one JSON line."""
    import json
    import os
    import sys
    import jax.numpy as jnp
    from references import mellum_ref

    ref_cfg = {k: getattr(cfg, k) for k in mellum_ref.CFG_KEYS}
    ref_cfg, dtype = _planted(ref_cfg, os.environ.get("MELLUM_PLANT", ""))
    check = _BlockCheck(trainer, cfg)
    loss = mellum_ref.loss(dict(trainer.params), jnp.asarray(ids), ref_cfg,
                           dtype, on_block=check)
    check.set_gauges(*ids.shape)
    errors = dict(check.errors, **window_backward(
        cfg, ids.shape[1], trainer.seed, ref_cfg["sliding_window"], dtype))
    limits = trainer.block_tolerance            # {kind: limit}
    over = {k: v for k, v in errors.items()
            if not v <= limits[k.split(".", 1)[1]]}
    print("mellum blocks " + json.dumps(
        {"block_tolerance": limits, "errors": errors, "over": over,
         "loss": loss, "held_sizes": check.sizes}), file=sys.stderr,
        flush=True)
    return float("nan") if over else loss


def shapes(cfg):
    """What the operation counts need (harness/flops.py, harness/
    window_flops.py, harness/moe_flops.py); see the module's docstring for
    what is counted and what is left out."""
    from harness import window_flops
    h, d = cfg.hidden_size, cfg.head_dim
    nh, nkv = cfg.num_attention_heads, cfg.num_key_value_heads
    kinds = cfg.layer_types
    seq = cfg.counted_seq
    window_layers = sum(k == "sliding_attention" for k in kinds)
    # the layers' mean share of the causal triangle that their masks leave
    triangle = window_flops.band_pairs(seq)
    attended = sum(window_flops.band_pairs(seq, cfg.window_of(k))
                   for k in kinds) / (len(kinds) * triangle)
    mixer = 2 * h * nh * d + 2 * h * nkv * d                # q, o; k, v
    router = h * cfg.num_experts
    routed = (cfg.num_experts_per_tok * cfg.experts_held[1]
              / float(cfg.num_experts)) * 3 * h * cfg.moe_intermediate_size
    return {"layers": len(kinds), "hidden": h,
            "heads": nh * attended, "kv_heads": nkv * attended,
            "head_dim": d, "ffn": 0, "vocab": cfg.vocab_size,
            "matmul_params_per_layer": mixer + router + routed,
            "head_params": cfg.vocab_size * h,
            # for readers/attn_window_roofline.py
            "window_layers": window_layers, "window": cfg.sliding_window,
            "window_heads": nh,
            # for harness/moe_flops.py (the grouped matmuls' roofline)
            "expert_ffn": cfg.moe_intermediate_size,
            "experts_held": cfg.experts_held[1],
            "top_k": cfg.num_experts_per_tok}
