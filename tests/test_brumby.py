"""Brumby (models/brumby.py) against its plain reference
(benchmark/references/brumby_ref.py) at a tiny size on the CPU: hidden 64,
two layers, 10 query heads over 2 state heads of 8 (5 a state, as
published), three chunks of 8 in 29 tokens (a ragged tail), seeded
weights, gate biases drawn for horizons of 2-64 tokens.

Each tolerance has its reason beside it. Program and reference both run in
float32 here unless a test says otherwise, so what separates them is the
order of the sums alone.
"""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import optimizer
from paddle_tpu.models import brumby
from paddle_tpu.models.brumby import BrumbyConfig, brumby_tiny
from paddle_tpu.parallel import DP_ONLY_RULES, SpmdTrainer, create_mesh
from paddle_tpu.parallel.functional import functional_call, make_loss_fn

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_reference():
    path = os.path.join(REPO, "benchmark", "references", "brumby_ref.py")
    spec = importlib.util.spec_from_file_location("brumby_ref", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load_reference()


def _ref_cfg(c: BrumbyConfig, **extra):
    return dict({k: getattr(c, k) for k in ref.CFG_KEYS}, **extra)


def _gate_biases(model, seed, low=2.0, high=64.0):
    """b_g = logit(1 - 1 / horizon), horizons log-uniform in [low, high]:
    at the constructor's zeros every gate is 0.5, a memory of one token,
    and a dropped state would change nothing."""
    rs = np.random.RandomState(seed)
    for name, t in model.state_dict().items():
        if name.endswith("g_proj.bias"):
            hz = np.exp(rs.uniform(np.log(low), np.log(high), t.shape))
            t._data = jnp.asarray(np.log(hz - 1.0), t._data.dtype)


@pytest.fixture(scope="module")
def tiny():
    paddle.seed(11)
    model = brumby_tiny()
    _gate_biases(model, 11)
    params = {k: v._data for k, v in model.state_dict().items()}
    # two sequences; 29 tokens: no multiple of the chunk (8)
    ids = np.random.RandomState(3).randint(0, 256, (2, 29)).astype(np.int32)
    return model, params, ids


def test_parameters_carry_the_published_names_and_shapes(tiny):
    model, params, _ = tiny
    shapes = {k: tuple(v.shape) for k, v in params.items()}
    pre = "model.layers.1."
    assert shapes[pre + "retention.q_proj.weight"] == (64, 80)
    assert shapes[pre + "retention.k_proj.weight"] == (64, 16)
    assert shapes[pre + "retention.g_proj.weight"] == (64, 2)
    assert shapes[pre + "retention.g_proj.bias"] == (2,)
    assert shapes[pre + "retention.q_norm.weight"] == (8,)
    assert shapes[pre + "retention.o_proj.weight"] == (80, 64)
    assert shapes[pre + "mlp.down_proj.weight"] == (96, 64)
    assert shapes["lm_head.weight"] == (64, 256)        # untied
    assert shapes["model.embed_tokens.weight"] == (256, 64)
    # no bias anywhere but the gate's
    assert [k for k in shapes if k.endswith(".bias")] == [
        f"model.layers.{i}.retention.g_proj.bias" for i in range(2)]
    with pytest.raises(NotImplementedError):
        BrumbyConfig(tie_word_embeddings=True)
    with pytest.raises(ValueError):
        BrumbyConfig(num_attention_heads=10, num_key_value_heads=4)


def test_loss_and_gradients_match_the_reference(tiny):
    model, params, ids = tiny
    cfg = _ref_cfg(model.config)
    loss_fn = make_loss_fn(model)
    got, grads = jax.jit(jax.value_and_grad(loss_fn))(params, (ids, ids),
                                                      None)
    with jax.default_matmul_precision("highest"):
        want, ref_grads = jax.jit(jax.value_and_grad(
            lambda p, i: ref.forward_loss(p, i, cfg)))(params,
                                                       jnp.asarray(ids))
    # float32 both sides; the chunked retention with its carried state and
    # the reference's quadratic form differ in summation order only: 1e-5
    # of a loss near ln(256) = 5.5 is ~20 float32 roundings
    assert abs(float(got) - float(want)) < 1e-5
    assert set(grads) == set(ref_grads)
    for name in sorted(grads):
        g, r = np.asarray(grads[name]), np.asarray(ref_grads[name])
        assert np.abs(r).max() > 0, name       # every parameter is reached
        # relative to the gradient's own scale: 2e-4 leaves room for the
        # float32 sums over 29 positions x 2 layers, and is far under what
        # a wrong term (a state not carried into the backward, a decay off
        # by one position) gives: those are errors of order 1
        assert np.abs(g - r).max() <= 2e-4 * np.abs(r).max() + 1e-8, name


def test_logits_without_labels_and_the_blocked_loss_agree(tiny, monkeypatch):
    """forward(ids) gives the logits; their plain cross entropy is the
    loss forward(ids, labels) takes a block of tokens at a time, here in
    blocks that do (2 x 29 = 58 rows in blocks of 29) and do not divide."""
    model, params, ids = tiny
    logits = functional_call(model, params, ids)
    assert logits.shape == (2, 29, 256)
    logp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32), -1)
    want = -jnp.mean(jnp.take_along_axis(logp, ids[:, 1:, None], -1))
    for block in (29, 2048):
        monkeypatch.setattr(brumby, "LOSS_TOKEN_BLOCK", block)
        got = functional_call(model, params, ids, labels=ids)
        assert abs(float(got) - float(want)) < 2e-6, block
    monkeypatch.setattr(brumby, "FFN_TOKEN_BLOCK", 29)
    again = functional_call(model, params, ids)
    assert np.abs(np.asarray(again - logits)).max() < 1e-5


def test_every_sub_block_matches_the_reference_and_the_controls_do_not(tiny):
    """What the benchmark's family loader does on the chip, at the test
    size: the reference's loss() hands every sub-block's input and output
    to a callback that runs the program's own sub-block on the same input.
    Float32 both sides: equal to summation order. The same protocol tells
    the two planted faults and the all-bf16 reference apart."""
    model, params, ids = tiny
    c = model.config
    layers = model.model.layers

    def errors(cfg, p=params, dtype=jnp.float32):
        out = {}

        def on_block(i, name, h_in, h_out):
            pre = f"model.layers.{i}.{name}."
            arrays = {k[len(pre):]: v for k, v in params.items()
                      if k.startswith(pre)}
            x = h_in.astype(jnp.float32)[None]
            got = functional_call(getattr(layers[i], name), arrays, x)
            want = (h_out - h_in).astype(jnp.float32)
            out[f"{i}.{name}"] = float(
                jnp.linalg.norm((got - x)[0] - want) / jnp.linalg.norm(want))

        ref.loss(p, jnp.asarray(ids), cfg, dtype, on_block=on_block)
        return out

    clean = errors(_ref_cfg(c))
    assert sorted(clean) == ["0.mlp", "0.retention", "1.mlp", "1.retention"]
    assert max(clean.values()) < 1e-5
    # a window of 8 = the chunk: what dropping the carried state computes
    dropped = errors(_ref_cfg(c, window=8))
    assert min(v for k, v in dropped.items() if "retention" in k) > 0.05
    assert max(v for k, v in dropped.items() if "mlp" in k) < 1e-5
    # every gate 1 (log-decay 0): the reference forgets nothing
    ungated = {k: (jnp.full_like(v, 30.0) if k.endswith("g_proj.bias")
                   else jnp.zeros_like(v) if k.endswith("g_proj.weight")
                   else v) for k, v in params.items()}
    assert min(v for k, v in errors(_ref_cfg(c), ungated).items()
               if "retention" in k) > 0.05
    # everything, the decays' running sum too, in bf16
    low = errors(_ref_cfg(c), dtype=jnp.bfloat16)
    assert min(v for k, v in low.items() if "retention" in k) > 1e-3


def test_float32_rope_tables_are_closer_than_the_fused_ops_at_16k():
    """bf16 q at positions 0..16383, theta 1e6: the model's rotation
    (float32 tables, one rounding) against incubate's fused op as it is
    (tables rounded to bf16 first), both held to a float64 rotation."""
    from paddle_tpu.incubate.nn.functional import \
        fused_rotary_position_embedding
    t, d = 16384, 128
    q = jnp.asarray(np.random.RandomState(0).randn(1, t, 2, d), jnp.bfloat16)
    q64 = np.asarray(q, np.float64)
    inv = 1e6 ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    ang = np.arange(t, dtype=np.float64)[:, None] * inv
    cos, sin = (f(ang)[None, :, None, :] for f in (np.cos, np.sin))
    a, b = q64[..., :d // 2], q64[..., d // 2:]
    want = np.concatenate([a * cos - b * sin, b * cos + a * sin], -1)

    def err(got):
        return float(np.linalg.norm(np.asarray(got, np.float64) - want)
                     / np.linalg.norm(want))

    ours = err(brumby._rope(q, 1e6))
    fused = err(fused_rotary_position_embedding(
        paddle.Tensor(q), rotary_emb_base=1e6)[0]._data)
    # one bf16 rounding of the result is 2^-9 / sqrt(3) = 0.11% rms; the
    # fused op's rounded tables and bf16 products add two more (measured
    # 0.17% ours, 0.26% fused; float32 angles at 16k cost 1e-4 of a radian
    # at most, inside the first figure)
    assert ours < 0.002 < fused < 0.004


def test_bf16_model_trains_through_the_trainer_and_the_loss_falls():
    paddle.seed(5)
    model = brumby_tiny(dtype="bfloat16")
    _gate_biases(model, 5)
    opt = optimizer.AdamW(1e-2, parameters=model.parameters())
    trainer = SpmdTrainer(model, opt, create_mesh(devices=jax.devices()[:1]),
                          DP_ONLY_RULES, dtype="bfloat16")
    ids = np.random.RandomState(1).randint(0, 256, (2, 24)).astype(np.int32)
    ref_loss = ref.forward_loss(
        {k: v.astype(jnp.float32) for k, v in trainer.params.items()},
        jnp.asarray(ids), _ref_cfg(model.config))
    losses = [float(trainer.step((ids, ids))) for _ in range(4)]
    assert all(np.isfinite(losses)) and losses[-1] < losses[0]
    # bf16 operands against the float32 reference on the same weights, at
    # a loss near 5.5: bf16's 3 digits
    assert abs(losses[0] - float(ref_loss)) < 0.02
    assert all(v.dtype == jnp.bfloat16 for v in trainer.params.values())


def test_generate_recomputes_the_prefix():
    paddle.seed(2)
    model = brumby_tiny(num_hidden_layers=1)
    ids = np.random.RandomState(2).randint(0, 256, (1, 9)).astype(np.int32)
    out = model.generate(paddle.Tensor(jnp.asarray(ids)), max_new_tokens=2)
    out = np.asarray(out._data)
    assert out.shape == (1, 11) and (out[:, :9] == ids).all()
    logits = functional_call(
        model, {k: v._data for k, v in model.state_dict().items()},
        out[:, :-1])
    assert int(jnp.argmax(logits[0, -1])) == out[0, -1]     # greedy
