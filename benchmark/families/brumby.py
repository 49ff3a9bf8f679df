"""Loader of the Brumby family (HF `brumby`, Manifest AI):
models/brumby.py through parallel.SpmdTrainer.

What the configuration file's keys become:
- the model has `num_hidden_layers` layers (every layer is a power-
  retention layer and a gated FFN) and `vocab_rows` rows of vocabulary,
  every width as published;
- recomputation sits in the model (per mixer, per block of FFN tokens, per
  block of head-and-loss tokens), not in the trainer;
- the model is built under paddle.LazyGuard; after weights.install
  (matrices N(0, 0.02), norms 1, other vectors 0) every gate's bias is
  re-drawn from the seed so that a state head's horizon 1 / (1 - g) is
  log-uniform in [64, 8192] tokens: at the zeros install leaves, every
  gate is 0.5, a memory of one token, and a program that dropped the
  state it carries between chunks would pass every comparison (the
  configuration's `assumed`);
- `correct`: reference_loss() holds the loss AND every sub-block of the
  program to the reference (see there; BRUMBY_PLANT plants a fault).

Operation count (harness/flops.py is fixed: 6 x (layers x
matmul_params_per_layer + head_params) + 3 x layers x causal SOFTMAX
attention of `heads` heads): `shapes()` gives `heads` and `kv_heads` 0,
so that the attention term is 0, and the five projections and the FFN
under `matmul_params_per_layer`: `train_mfu` then counts 6 x (4 x 330.3M +
97.2M) = 8.51 GFLOP a token and LEAVES OUT the retention's own products
(harness/retention_flops.py: 3 x 1.66e12 a layer and 16k sequence, 1.22
GFLOP a token, about 14% more), the norms, RoPE and every elementwise
operation: short, never over. The real head counts are under
`retention_heads`, `retention_state_heads`, `retention_features`.
"""

from __future__ import annotations

import math

MODEL_KEYS = ("hidden_size", "intermediate_size", "num_attention_heads",
              "num_key_value_heads", "head_dim", "rms_norm_eps", "rope_theta",
              "max_position_embeddings", "tie_word_embeddings",
              "attention_bias", "initializer_range", "retention_chunk",
              "retention_eps")
HORIZON = (64.0, 8192.0)                # tokens, of the re-drawn gate biases
GAUGE = "retention_mean_horizon_tokens"


def model_config(config):
    from paddle_tpu.models.brumby import BrumbyConfig
    return BrumbyConfig(
        vocab_size=int(config["vocab_rows"]),
        num_hidden_layers=int(config["num_hidden_layers"]),
        dtype=config["dtype"],
        **{k: config[k] for k in MODEL_KEYS if k in config})


def gate_bias(key, shape):
    """b_g = logit(1 - 1 / horizon), float32, the horizon log-uniform in
    HORIZON: sigmoid(b_g) = 1 - 1 / horizon, so b_g = log(horizon - 1)."""
    import jax
    import jax.numpy as jnp
    horizon = jnp.exp(jax.random.uniform(
        key, shape, jnp.float32, math.log(HORIZON[0]), math.log(HORIZON[1])))
    return jnp.log(horizon - 1.0)


def _redraw_gates(model, seed):
    """One key a layer folded from the seed, as the granite loader's."""
    import jax
    key = jax.random.key(seed % (2 ** 31 - 1))
    state = model.state_dict()
    for i in range(model.config.num_hidden_layers):
        t = state[f"model.layers.{i}.retention.g_proj.bias"]
        t._data = gate_bias(jax.random.fold_in(key, i),
                            tuple(t.shape)).astype(t._data.dtype)


def build_trainer(config, traffic, seed):
    """(trainer, model config, parameter count), as families/gpt.py: the
    model from the program's constructor, weights from the seed, AdamW at
    the traffic's fixed learning rate, one chip, no clipping."""
    import jax
    import numpy as np
    from jax.sharding import PartitionSpec as P
    import paddle_tpu as paddle
    from paddle_tpu import optimizer
    from paddle_tpu.models.brumby import BrumbyForCausalLM
    from paddle_tpu.parallel import DP_ONLY_RULES, SpmdTrainer, create_mesh
    from harness import weights

    mesh_axes = dict(config["deployment"].get("mesh") or {})
    need = int(np.prod(list(mesh_axes.values()) or [1]))
    mesh = create_mesh(devices=list(jax.devices())[:need], **mesh_axes)
    cfg = model_config(config)
    paddle.seed(seed % (2 ** 31 - 1))
    with paddle.LazyGuard():        # install follows: nothing is drawn
        model = BrumbyForCausalLM(cfg)
    n_params = weights.install(model, seed, config["dtype"])
    _redraw_gates(model, seed)
    opt = optimizer.AdamW(float(traffic["learning_rate"]),
                          parameters=model.parameters())
    trainer = SpmdTrainer(model, opt, mesh, DP_ONLY_RULES,
                          dtype=config["dtype"], batch_spec=P(None))
    trainer.block_tolerance = {k: float(v) for k, v
                               in traffic["block_tolerance"].items()}
    return trainer, cfg, n_params


def _planted(params, ref_cfg, plant, chunk):
    """(parameters, config, dtype) the reference is computed from. With
    BRUMBY_PLANT unset: the trainer's own, float32. Otherwise a fault is
    planted in what the UNCHANGED reference is given, so that a run shows
    the comparison failing (`correct` false):
      bf16   everything, the running sum of the log-decays too, in bf16:
             the nearest precision below the program's bf16 operands with
             float32 sums and decays
      state  the carried part of the sum dropped: a_ts = 0 for s more than
             the program's chunk (1,024 in the cell) behind t
      gate   every gate 1 (log-decay 0): W_g = 0, b_g = 30"""
    import jax.numpy as jnp
    if plant in ("", "bf16"):
        return params, ref_cfg, jnp.bfloat16 if plant else jnp.float32
    params, ref_cfg = dict(params), dict(ref_cfg)
    if plant == "state":
        ref_cfg["window"] = chunk
    elif plant == "gate":
        for k in [k for k in params if k.endswith(".g_proj.weight")]:
            params[k] = jnp.zeros_like(params[k])
        for k in [k for k in params if k.endswith(".g_proj.bias")]:
            params[k] = jnp.full_like(params[k], 30.0)
    else:
        raise SystemExit(f"BRUMBY_PLANT={plant!r}: one of bf16, state, gate")
    return params, ref_cfg, jnp.float32


class _BlockCheck:
    """The program's own sub-blocks (each kind's forward, jitted once, the
    layer's arrays passed in) on the input the reference's sub-block had,
    rounded to the program's type: for each sub-block the error of the
    residual update, |(program out - in) - (reference out - in)| over
    |reference out - in|. Judging each on the reference's input keeps one
    sub-block's error out of the next one's reading. The gates' horizons
    are taken here too, where every layer's real input passes by."""

    def __init__(self, trainer, cfg):
        import jax
        import jax.numpy as jnp
        from paddle_tpu.models.brumby import retention_log_gate
        from paddle_tpu.parallel.functional import functional_call

        self.params, self.cfg = trainer.params, cfg
        self.errors, self.horizons = {}, []
        first = dict(trainer.model.model.layers[0]._sub_layers)

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
        def horizons(h, norm_w, gate_w, gate_b):
            # 1 / (1 - mean_t g_t) per state head, from the model's own gate
            g = jnp.exp(retention_log_gate(
                h.astype(jnp.dtype(cfg.dtype)), norm_w, gate_w, gate_b,
                cfg.rms_norm_eps))
            return 1.0 / (1.0 - jnp.mean(g, axis=0))

        self._error, self._horizons = error, horizons

    def __call__(self, i, name, h_in, h_out):
        pre = f"model.layers.{i}.{name}."
        arrays = {k[len(pre):]: v for k, v in self.params.items()
                  if k.startswith(pre)}
        self.errors[f"{i}.{name}"] = float(self._error(
            self._run[name](h_in, arrays), h_in, h_out))
        if name == "retention":
            self.horizons.extend(float(x) for x in self._horizons(
                h_in, arrays["input_layernorm.weight"],
                arrays["g_proj.weight"], arrays["g_proj.bias"]))

    def set_gauge(self):
        """retention_mean_horizon_tokens: the mean over layers and state
        heads of 1 / (1 - mean_t g_t). A statistic of the seed's initial
        weights on the first sequence, set once: nothing in the timed
        window reads it."""
        from paddle_tpu.observability import metrics
        from paddle_tpu.observability.catalog import metric
        registry = metrics.get_registry()
        was_on = registry.enabled
        registry.enable()      # a gauge of a registry that is off keeps 0
        try:
            metric(GAUGE).set(sum(self.horizons) / len(self.horizons))
        finally:
            if not was_on:
                registry.disable()


def reference_loss(trainer, cfg, ids):
    """First-step loss of the float32 reference on the trainer's current
    weights (call before the step that donates them), or NaN.

    harness/runners/train.py compares one number, and at seeded weights the
    loss hardly moves with anything the layers do (ln of the vocabulary
    plus little). So the layers are held here, as families/
    granite_hybrid.py holds its own: every sub-block of the program
    against the reference's on the same input (_BlockCheck), each within
    the traffic's `block_tolerance` for its kind. Where one is not, the
    number returned is NaN, which the runner's comparison cannot pass:
    `correct` comes out false. The errors of every sub-block, and which
    were over, the reference's loss as it was computed and the gates'
    horizons go to standard error in any case, as one JSON line."""
    import json
    import os
    import sys
    import jax.numpy as jnp
    from references import brumby_ref

    ref_cfg = {k: getattr(cfg, k) for k in brumby_ref.CFG_KEYS}
    params, ref_cfg, dtype = _planted(
        dict(trainer.params), ref_cfg, os.environ.get("BRUMBY_PLANT", ""),
        cfg.retention_chunk)
    check = _BlockCheck(trainer, cfg)
    loss = brumby_ref.loss(params, jnp.asarray(ids), ref_cfg, dtype,
                           on_block=check)
    check.set_gauge()
    limits = trainer.block_tolerance            # {sub-layer name: limit}
    over = {k: v for k, v in check.errors.items()
            if not v <= limits[k.split(".", 1)[1]]}
    print("brumby blocks " + json.dumps(
        {"block_tolerance": limits, "errors": check.errors, "over": over,
         "loss": loss, "horizons": check.horizons}), file=sys.stderr,
        flush=True)
    return float("nan") if over else loss


def shapes(cfg):
    """What the operation counts need (harness/flops.py, harness/
    retention_flops.py); see the module's docstring for what is counted
    and what is left out."""
    from paddle_tpu.ops.power_retention import retention_features
    h, d = cfg.hidden_size, cfg.head_dim
    nh, nkv = cfg.num_attention_heads, cfg.num_key_value_heads
    mixer = 2 * h * nh * d + 2 * h * nkv * d + h * nkv      # q, o; k, v; gate
    return {"layers": cfg.num_hidden_layers, "hidden": h,
            "heads": 0, "kv_heads": 0,          # no softmax attention
            "head_dim": d, "ffn": cfg.intermediate_size,
            "vocab": cfg.vocab_size,
            "matmul_params_per_layer": mixer + 3 * h * cfg.intermediate_size,
            "head_params": cfg.vocab_size * h,
            # for harness/retention_flops.py (the scan's roofline)
            "retention_heads": nh, "retention_state_heads": nkv,
            "retention_features": retention_features(d)}
