"""Loader of the Phi-4-mini-flash family (HF `phi4flash`, Microsoft; SambaY):
models/phi4flash.py through parallel.SpmdTrainer.

What the configuration file's keys become:
- the model has the `num_hidden_layers` layers that `layer_types` names,
  each at its published index (`layer_indices`, which the differential
  weight's offset reads), `vocab_rows` rows of vocabulary (embedding and
  tied head alike), every width as published;
- recomputation sits in the model (per mixer, per block of MLP tokens, per
  block of head-and-loss tokens), not in the trainer;
- the model is built under paddle.LazyGuard; weights.install then draws
  matrices N(0, 0.02), sets the norms' weights to 1 and every other vector
  to 0, and `_redraw` draws again from the seed what that gets wrong: the
  Mamba layers' published initialisation and the lambda vectors N(0, 0.1);
- `correct`: reference_loss() holds the loss AND every sub-block of the
  program to the reference, the selective scan's BACKWARD to the
  reference's gradients, and the whole first step's gradients and update
  to the reference's (see there; PHI4FLASH_PLANT plants a fault).

Operation count: `shapes()` gives the layers' MEAN of what one token
multiplies (every projection of every mixer, the MLP, the tied head once)
for harness/flops.py's 6 per matmul parameter, and counts the
differential layers' flash work ONCE, in harness/diff_attn_flops.py (the
pairs the masks leave, 2 x query heads x (q/k width + v width) a pair);
flops.py's attention term (4 x heads x head_dim a causal pair a layer) is
given that count through `heads`, the number of 64-lane heads that makes
it so. Left out, so that the count may fall short and never over: the
selective scan and the conv (elementwise work), norms, softmaxes, the zero
lanes q and k are padded with.
"""

from __future__ import annotations

MODEL_KEYS = ("hidden_size", "intermediate_size", "num_attention_heads",
              "num_key_value_heads", "sliding_window", "layer_norm_eps",
              "mb_per_layer", "mamba_d_state", "mamba_d_conv",
              "mamba_expand", "mamba_dt_rank", "tie_word_embeddings",
              "mlp_bias", "lm_head_bias", "max_position_embeddings",
              "initializer_range")
PLANTS = ("state", "memory", "kv", "lambda", "window", "bf16", "frozen")
# the recurrence's state is dropped every this many steps under `state`
# (the kernels' chunk; a quarter of a shorter sequence)
STATE_CHUNK = 128


def model_config(config):
    from paddle_tpu.models.phi4flash import Phi4FlashConfig
    return Phi4FlashConfig(
        vocab_size=int(config["vocab_rows"]),
        num_hidden_layers=int(config["num_hidden_layers"]),
        layer_types=list(config["layer_types"]),
        layer_indices=list(config["layer_indices"]), dtype=config["dtype"],
        **{k: config[k] for k in MODEL_KEYS if k in config})


def _redraw(model, seed):
    """Mamba-1's published initial values (A_log, D, dt_proj, the conv) of
    every Mamba layer and the four lambda vectors of every attention layer
    N(0, 0.1), one key a layer folded from the seed."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.models.phi4flash import mamba1_published_init
    c = model.config
    key = jax.random.key(seed % (2 ** 31 - 1))
    state = model.state_dict()
    for i, kind in enumerate(c.layer_types):
        pre = f"model.layers.{i}.mixer."
        k = jax.random.fold_in(key, i)
        if kind in ("mamba", "memory_mamba"):
            drawn = mamba1_published_init(k, c.mamba_inner, c.mamba_d_state,
                                          c.mamba_dt_rank, c.mamba_d_conv)
        elif kind.endswith("attention"):
            drawn = {f"lambda_{n}": 0.1 * jax.random.normal(
                jax.random.fold_in(k, j), (c.head_dim,), jnp.float32)
                for j, n in enumerate(("q1", "k1", "q2", "k2"))}
        else:
            continue
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
    from paddle_tpu.models.phi4flash import Phi4FlashForCausalLM
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
        model = Phi4FlashForCausalLM(cfg)
    n_params = weights.install(model, seed, config["dtype"])
    _redraw(model, seed)
    opt = optimizer.AdamW(float(traffic["learning_rate"]),
                          parameters=model.parameters())
    trainer = SpmdTrainer(model, opt, mesh, DP_ONLY_RULES,
                          dtype=config["dtype"], batch_spec=P(None))
    trainer.block_tolerance = {k: None if v is None else float(v)
                               for k, v in traffic["block_tolerance"].items()}
    trainer.seed = seed             # scan_backward draws its operands
    return trainer, cfg, n_params


def _planted(ref_cfg, plant, tokens):
    """(config, dtype) the reference is computed from. With PHI4FLASH_PLANT
    unset: the trainer's own, float32. Otherwise a fault is planted in
    what the UNCHANGED reference is given, so that a run shows the
    comparison failing (`correct` false):
      state   the recurrence's state dropped every STATE_CHUNK steps (a
              scan that does not carry it across chunk edges; a quarter of
              a shorter sequence), in the sub-blocks and in the scan's
              gradients
      memory  the GMUs fed zeros for the memory
      kv      the cross layers apply the full layer's key and value
              projection to their own input (no shared keys and values)
      lambda  the differential weight lam = 0 (one softmax map alone)
      window  the window layers see the whole causal triangle
      bf16    everything in bf16, the recurrent state too: the nearest
              precision below the program's bf16 operands with float32
              accumulation
      frozen  (the reference unchanged) the program's first step is held
              to it as if the step had left the state as it was
              (first_step)"""
    import jax.numpy as jnp
    if plant in ("", "bf16", "frozen"):
        return ref_cfg, jnp.bfloat16 if plant == "bf16" else jnp.float32
    ref_cfg = dict(ref_cfg)
    if plant == "state":
        ref_cfg["reset_state"] = min(STATE_CHUNK, tokens // 4)
    elif plant == "memory":
        ref_cfg["memory_zero"] = True
    elif plant == "kv":
        ref_cfg["kv_own"] = True
    elif plant == "lambda":
        ref_cfg["lambda_zero"] = True
    elif plant == "window":
        ref_cfg["sliding_window"] = None
    else:
        raise SystemExit(f"PHI4FLASH_PLANT={plant!r}: one of "
                         + ", ".join(PLANTS))
    return ref_cfg, jnp.float32


def block_kind(cfg, i, name):
    """The kind a sub-block's limit is keyed by: the mixer's layer kind,
    or `mlp`."""
    return cfg.layer_types[i] if name == "mixer" else "mlp"


def scan_backward(cfg, tokens, seed, reset_state, ref_dtype):
    """{du|ddelta|dA|dB|dC|dD|dz|ddelta_bias.selscan_backward: error}: the
    gradients of the selective scan as the program's Mamba mixers call it
    (ops/selective_scan.py: on a TPU the `selscan_fwd` and `selscan_bwd`
    kernels) against the reference's sequential loop's
    (`phi4flash_ref.scan_grads`), |got - want| / |want| of each: the scan
    kernels' backward alone, where `first_step` holds the whole step's
    gradients, which the scan's are a part of. One layer's shape at the
    cell's length;
    operands and cotangent N(0, 1) from the seed in the model's type
    (delta N(0, 0.25)), A, D and delta's bias the published
    initialisation's, whatever the model's weights."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.models.phi4flash import mamba1_published_init
    from paddle_tpu.ops.selective_scan import selective_scan
    from references import phi4flash_ref
    e, n = cfg.mamba_inner, cfg.mamba_d_state
    dtype = jnp.dtype(cfg.dtype)
    keys = jax.random.split(jax.random.key(seed % (2 ** 31 - 1)), 7)
    u, z, dg = (jax.random.normal(k, (1, tokens, e), jnp.float32
                                  ).astype(dtype) for k in keys[:3])
    delta = (0.5 * jax.random.normal(keys[3], (1, tokens, e), jnp.float32)
             ).astype(dtype)
    b, c = (jax.random.normal(k, (1, tokens, n), jnp.float32).astype(dtype)
            for k in keys[4:6])
    pub = mamba1_published_init(keys[6], e, n, cfg.mamba_dt_rank,
                                cfg.mamba_d_conv)
    a = -jnp.exp(pub["A_log"].astype(dtype).astype(jnp.float32))
    d, bias = pub["D"].astype(dtype), pub["dt_proj.bias"].astype(dtype)

    @jax.jit
    def program(u, delta, a, b, c, d, z, bias, dg):
        return jax.vjp(selective_scan, u, delta, a, b, c, d, z, bias)[1](dg)

    got = program(u, delta, a, b, c, d, z, bias, dg)
    want = phi4flash_ref.scan_grads(u[0], delta[0], a, b[0], c[0], d, z[0],
                                    bias, dg[0], ref_dtype, reset_state)
    names = ("du", "ddelta", "dA", "dB", "dC", "dD", "dz", "ddelta_bias")
    out = {}
    for name, g, w in zip(names, got, want):
        g = g.astype(jnp.float32).reshape(w.shape)
        w = w.astype(jnp.float32)
        out[f"{name}.selscan_backward"] = float(
            jnp.linalg.norm(g - w) / jnp.linalg.norm(w))
    return out


def _relative(got, want):
    """|got - want| / |want| in float64; 0 where both are 0."""
    import numpy as np
    gap = float(np.linalg.norm((got - want).astype(np.float64)))
    scale = float(np.linalg.norm(want.astype(np.float64)))
    return gap / scale if scale else (float("inf") if gap else 0.0)


def program_step(trainer, ids):
    """The program's first step on `ids` (the compiled step the timed
    window runs: forward, backward, AdamW update) as host copies of (the
    weights before, the weights after, the first moments after). The
    trainer's weights are put back as they were and its moments deleted:
    call `restore_moments` once the memory is free again."""
    import jax
    import numpy as np
    shardings = {k: a.sharding for k, a in trainer.params.items()}
    # copies: the step donates the arrays, which a view would share
    before = {k: np.array(a, copy=True) for k, a in trainer.params.items()}
    trainer.step((ids, ids))
    after = {k: np.array(a, copy=True) for k, a in trainer.params.items()}
    moment = {k: np.array(s["moment1"], copy=True)
              for k, s in trainer.opt_state.items()}
    for a in jax.tree_util.tree_leaves((trainer.params, trainer.opt_state)):
        a.delete()                  # room for the reference's backward
    trainer.params = jax.device_put(before, shardings)
    trainer.opt_state = None
    return before, after, moment


def restore_moments(trainer):
    """Zero moments and step 0, as the trainer was built."""
    import jax
    opt = trainer.optimizer
    trainer.opt_state = {k: {n: jax.device_put(v, p.sharding)
                             for n, v in opt.init_state(p).items()}
                         for k, p in trainer.params.items()}
    trainer.step_count = 0


def first_step(opt, before, after, moment, want):
    """({first_step.<reading>: the worst parameter's error}, {the same: its
    three worst parameters}): the program's first step (`program_step`)
    against the reference's gradients `want` and AdamW's first step
    written from its formula, parameter by parameter. `grad`: the
    program's first moment over (1 - beta1), which is its gradient, against
    the reference's; `update`: what the step did to the parameter against
    what the reference's step does to it, rounded to the parameter's type;
    both |got - want| / |want| (a state left unchanged reads 1). The lambda
    vectors are read apart (`grad_lambda`, `update_lambda`): each one's
    gradient is ONE sum over a layer's positions and heads times a fixed
    vector, a sum that bf16 leaves undetermined (traffic's
    block_tolerance_why)."""
    import numpy as np
    b1, b2, eps = opt._beta1, opt._beta2, opt._eps
    lr, decay = float(opt.get_lr()), float(opt._weight_decay or 0.0)
    errors = {}
    for name, p0 in before.items():
        p, g = np.asarray(p0, np.float32), want[name]
        m, v = (1 - b1) * g, (1 - b2) * g * g         # from zero moments
        new = p - lr * (m / (1 - b1) / (np.sqrt(v / (1 - b2)) + eps)
                        + decay * p)
        moved = new.astype(p0.dtype).astype(np.float32) - p
        part = "_lambda" if ".lambda_" in name else ""
        errors.setdefault("grad" + part, {})[name] = _relative(
            np.asarray(moment[name], np.float32) / (1 - b1), g)
        errors.setdefault("update" + part, {})[name] = _relative(
            np.asarray(after[name], np.float32) - p, moved)
    worst = {r: sorted(e, key=e.get)[:-4:-1] for r, e in errors.items()}
    return ({f"first_step.{r}": max(e.values()) for r, e in errors.items()},
            {f"first_step.{r}": {k: errors[r][k] for k in names}
             for r, names in worst.items()})


def limit_of(limits, reading):
    """A reading's limit: its own name's, else its kind's (after the first
    dot); None: reported, not judged."""
    return limits[reading] if reading in limits \
        else limits[reading.split(".", 1)[1]]


def reference_loss(trainer, cfg, ids):
    """First-step loss of the float32 reference on the trainer's current
    weights, or NaN; the trainer is left as it was found.

    harness/runners/train.py compares one number, and at seeded weights the
    loss hardly moves with anything the layers do. So the layers are held
    here, as families/mellum.py holds its own: every sub-block of the
    program against the reference's on the same input (harness/
    block_check.py), each within the traffic's `block_tolerance` for its
    kind (`mamba`, `memory_mamba`, `sliding_attention`, `full_attention`,
    `cross_attention`, `gmu`; `mlp` reported, not judged), the selective
    scan's gradients (`scan_backward`, limit `selscan_backward`; dD
    reported, not judged), and the whole first step, backward and update
    (`first_step`, limits `grad` and `update`). Where one is over, the
    number returned is NaN, which the runner's comparison cannot pass:
    `correct` comes out false. Every reading, which were over, the worst
    parameters of the first step and the reference's loss go to standard
    error in any case, as one JSON line."""
    import json
    import os
    import sys
    import numpy as np
    from harness.block_check import BlockCheck
    from references import phi4flash_ref

    plant = os.environ.get("PHI4FLASH_PLANT", "")
    ref_cfg = {k: getattr(cfg, k) for k in phi4flash_ref.CFG_KEYS}
    ref_cfg, dtype = _planted(ref_cfg, plant, ids.shape[1])
    before, after, moment = program_step(trainer, ids)
    if plant == "frozen":
        after = before
        moment = {k: np.zeros(p.shape, np.float32) for k, p in before.items()}
    check = BlockCheck(
        trainer, cfg.dtype, kind=lambda i, name: block_kind(cfg, i, name),
        # an attention layer's lambda_init (from its published index) is a
        # constant of its own runner
        key=lambda i, name, sub: (block_kind(cfg, i, name),
                                  getattr(sub, "init", None)),
        # a cross layer reads the full layer's keys and values, not the
        # projection the reference passes beside them
        extra=lambda i, name, extra: extra[:2]
        if block_kind(cfg, i, name) == "cross_attention" else extra)
    loss, want = phi4flash_ref.loss_and_grads(
        dict(trainer.params), ids, ref_cfg, dtype, on_block=check)
    restore_moments(trainer)
    step, worst = first_step(trainer.optimizer, before, after, moment, want)
    errors = dict(check.errors, **step, **scan_backward(
        cfg, ids.shape[1], trainer.seed, ref_cfg.get("reset_state"), dtype))
    limits = trainer.block_tolerance
    over = {k: v for k, v in errors.items()
            if limit_of(limits, k) is not None
            and not v <= limit_of(limits, k)}
    print("phi4flash blocks " + json.dumps(
        {"block_tolerance": limits, "errors": errors, "over": over,
         "first_step_worst": worst, "loss": loss}), file=sys.stderr,
        flush=True)
    return float("nan") if over else loss


def shapes(cfg):
    """What the operation counts need (harness/flops.py, harness/
    diff_attn_flops.py, harness/selective_scan_bytes.py); see the module's
    docstring for what is counted and what is left out."""
    from harness import diff_attn_flops, flops
    h, dh = cfg.hidden_size, cfg.head_dim
    nq, nkv = cfg.num_attention_heads, cfg.num_key_value_heads
    e, n, r = cfg.mamba_inner, cfg.mamba_d_state, cfg.mamba_dt_rank
    kinds = cfg.layer_types
    seq = cfg.counted_seq
    mixer = {
        "mamba": h * 2 * e + e * (r + 2 * n) + r * e + e * h,
        "sliding_attention": h * (nq + 2 * nkv) * dh + nq * dh * h,
        "cross_attention": 2 * h * nq * dh,
        "gmu": 2 * h * e}
    mixer["memory_mamba"] = mixer["mamba"]
    mixer["full_attention"] = mixer["sliding_attention"]
    mlp = 3 * h * cfg.intermediate_size
    out = {"layers": len(kinds), "hidden": h, "kv_heads": nkv,
           "head_dim": dh, "ffn": 0, "vocab": cfg.vocab_size,
           "matmul_params_per_layer": sum(mixer[k] for k in kinds)
           / len(kinds) + mlp,
           "head_params": cfg.vocab_size * h,
           # for harness/diff_attn_flops.py
           "diff_windows": [cfg.window_of(k) for k in kinds
                            if k.endswith("attention")],
           "diff_query_heads": nq, "diff_qk_dim": dh, "diff_v_dim": 2 * dh,
           # for harness/selective_scan_bytes.py
           "sel_layers": sum(k.endswith("mamba") for k in kinds),
           "sel_channels": e, "sel_state": n,
           "sel_itemsize": {"bfloat16": 2, "float32": 4}[cfg.dtype]}
    out["heads"] = diff_attn_flops.diff_fwd_flops(out, seq) / (
        4.0 * len(kinds) * dh * flops.attention_pairs_causal(seq))
    return out
