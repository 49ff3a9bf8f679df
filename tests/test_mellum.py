"""Mellum 2 (models/mellum.py) against its plain reference
(benchmark/references/mellum_ref.py) at a tiny size on the CPU: hidden 64,
one period (three window layers of 8 keys, one full layer with YaRN), 8
query heads over 1 key-value head of 16, 16 experts with top-4, two
sequences of 29 tokens (3.6 windows), seeded weights.

Each tolerance has its reason beside it. Program and reference both run in
float32 here unless a test says otherwise, so what separates them is the
order of the sums alone.
"""

import importlib.util
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import optimizer
from paddle_tpu.models import mellum
from paddle_tpu.models.mellum import MellumConfig, mellum_tiny
from paddle_tpu.models.rope import apply_rope, rope_frequencies
from paddle_tpu.parallel import DP_ONLY_RULES, SpmdTrainer, create_mesh
from paddle_tpu.parallel.functional import functional_call, make_loss_fn
from paddle_tpu.parallel.moe import dropless_moe, route_top_k

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PUBLISHED_YARN = {
    "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
    "original_max_position_embeddings": 8192, "beta_fast": 32,
    "beta_slow": 1, "attention_factor": 1.2772588722239782}


def _load_reference():
    path = os.path.join(REPO, "benchmark", "references", "mellum_ref.py")
    spec = importlib.util.spec_from_file_location("mellum_ref", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load_reference()


def _ref_cfg(c: MellumConfig, **extra):
    return dict({k: getattr(c, k) for k in ref.CFG_KEYS}, **extra)


@pytest.fixture(scope="module")
def tiny():
    paddle.seed(13)
    # wider initial weights than 0.02: scores then have a spread near 1, so
    # that window, frequencies and routing each move the outputs
    model = mellum_tiny(initializer_range=0.3)
    params = {k: v._data for k, v in model.state_dict().items()}
    ids = np.random.RandomState(4).randint(0, 256, (2, 29)).astype(np.int32)
    return model, params, ids


def test_parameters_carry_the_published_names_and_shapes(tiny):
    model, params, _ = tiny
    shapes = {k: tuple(v.shape) for k, v in params.items()}
    pre = "model.layers.3."
    assert shapes[pre + "self_attn.q_proj.weight"] == (64, 128)
    assert shapes[pre + "self_attn.k_proj.weight"] == (64, 16)
    assert shapes[pre + "self_attn.q_norm.weight"] == (16,)
    assert shapes[pre + "self_attn.o_proj.weight"] == (128, 64)
    assert shapes[pre + "mlp.gate.weight"] == (64, 16)
    assert shapes[pre + "mlp.experts.gate_up_proj"] == (16, 64, 48)
    assert shapes[pre + "mlp.experts.down_proj"] == (16, 24, 64)
    assert shapes["lm_head.weight"] == (64, 256)        # untied
    assert not [k for k in shapes if k.endswith(".bias")]
    assert not [k for k in shapes if "shared" in k]
    assert model.config.layer_types == ["sliding_attention"] * 3 \
        + ["full_attention"]
    published = MellumConfig()
    assert published.layer_types[:8] == (["sliding_attention"] * 3
                                         + ["full_attention"]) * 2
    assert published.rope_parameters["full_attention"] == PUBLISHED_YARN
    assert (published.window_of("sliding_attention"),
            published.window_of("full_attention")) == (1024, None)
    with pytest.raises(NotImplementedError):
        MellumConfig(tie_word_embeddings=True)
    with pytest.raises(NotImplementedError):
        MellumConfig(norm_topk_prob=False)
    with pytest.raises(ValueError):
        MellumConfig(experts_held=(60, 8))


def test_logits_match_the_reference(tiny):
    model, params, ids = tiny
    got = functional_call(model, params, ids)
    cfg = _ref_cfg(model.config)
    with jax.default_matmul_precision("highest"):
        want = jnp.stack([ref.logits(
            ref.hidden_states(params, jnp.asarray(ids[b]), cfg),
            params["model.norm.weight"], params["lm_head.weight"], cfg)
            for b in range(2)])
    assert got.shape == want.shape == (2, 29, 256)
    # float32 both sides: summation order over 4 layers; logits of order 1
    assert np.abs(np.asarray(got - want)).max() < 5e-5


def test_loss_and_every_gradient_match_the_reference(tiny):
    model, params, ids = tiny
    cfg = _ref_cfg(model.config)
    loss_fn = make_loss_fn(model)
    got, grads = jax.jit(jax.value_and_grad(loss_fn))(params, (ids, ids),
                                                      None)
    with jax.default_matmul_precision("highest"):
        want, ref_grads = jax.jit(jax.value_and_grad(
            lambda p, i: ref.forward_loss(p, i, cfg)))(params,
                                                       jnp.asarray(ids))
    # 1e-5 of a loss near ln(256) = 5.5 is ~20 float32 roundings
    assert abs(float(got) - float(want)) < 1e-5
    assert set(grads) == set(ref_grads)
    for name in sorted(grads):
        g, r = np.asarray(grads[name]), np.asarray(ref_grads[name])
        assert np.abs(r).max() > 0, name       # every parameter is reached
        # relative to the gradient's own scale: 2e-4 leaves room for the
        # float32 sums over 29 positions x 4 layers and is far under what a
        # wrong term gives (a mask off by one key, a factor left off the
        # sine, a gate not renormalised: errors of order 0.1-1)
        assert np.abs(g - r).max() <= 2e-4 * np.abs(r).max() + 1e-8, name


def test_logits_without_labels_and_the_blocked_loss_agree(tiny, monkeypatch):
    model, params, ids = tiny
    logits = functional_call(model, params, ids)
    logp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32), -1)
    want = -jnp.mean(jnp.take_along_axis(logp, ids[:, 1:, None], -1))
    for block in (29, 2048):
        monkeypatch.setattr(mellum, "LOSS_TOKEN_BLOCK", block)
        got = functional_call(model, params, ids, labels=ids)
        assert abs(float(got) - float(want)) < 2e-6, block
    monkeypatch.setattr(mellum, "MOE_TOKEN_BLOCK", 29)
    again = functional_call(model, params, ids)
    assert np.abs(np.asarray(again - logits)).max() < 1e-5


def test_loss_and_every_gradient_are_the_same_at_two_moe_blocks(
        tiny, monkeypatch):
    """The experts over all 58 tokens in one call and over two blocks of
    an odd 29: the same sums, an expert's gradient added over two blocks
    in place of one."""
    model, params, ids = tiny
    results = []
    for block in (4096, 29):
        monkeypatch.setattr(mellum, "MOE_TOKEN_BLOCK", block)
        results.append(jax.jit(jax.value_and_grad(make_loss_fn(model)))(
            params, (ids, ids), None))
    (one, g_one), (two, g_two) = results
    # the tolerances of the comparison with the reference above
    assert abs(float(one) - float(two)) < 1e-5
    assert set(g_one) == set(g_two)
    for name in sorted(g_one):
        a, b = np.asarray(g_one[name]), np.asarray(g_two[name])
        assert np.abs(a - b).max() <= 2e-4 * np.abs(a).max() + 1e-8, name


def test_loss_and_gradients_through_the_pallas_grouped_matmul(
        tiny, monkeypatch):
    """The whole model with its experts' products through jax's Pallas
    grouped matmul (interpreted here; on a TPU the Mellum cell's widths
    choose it, parallel/moe.py _GMM_TILES) and through lax.ragged_dot:
    2 x 32 tokens, so that the sorted buffer's 256 rows are whole tiles."""
    from paddle_tpu.parallel import moe
    model, params, _ = tiny
    ids = np.random.RandomState(5).randint(0, 256, (2, 32)).astype(np.int32)

    def step():
        return jax.jit(jax.value_and_grad(make_loss_fn(model)))(
            params, (ids, ids), None)

    want, want_grads = step()
    calls = []

    def tiles(lhs, rhs):
        calls.append((lhs.shape, rhs.shape))
        return ((128, 128, 128),) * 3

    monkeypatch.setattr(moe, "_gmm_tiles", tiles)
    got, grads = step()
    # every layer's two products, traced forward and rematerialised
    assert len(calls) >= 2 * 4 and {c[0][0] for c in calls} == {256}
    # the tolerances of the comparison with the reference above: the same
    # float32 sums in another order
    assert abs(float(got) - float(want)) < 1e-5
    for name in sorted(want_grads):
        g, r = np.asarray(grads[name]), np.asarray(want_grads[name])
        assert np.abs(g - r).max() <= 2e-4 * np.abs(r).max() + 1e-8, name


def _block_errors(model, params, ids, cfg, dtype=jnp.float32):
    """What the benchmark's family loader does on the chip, at the test
    size: {"<layer>.<kind>": error of the program's residual update against
    the reference's on the reference's input}."""
    layers, kinds = model.model.layers, model.config.layer_types
    out = {}

    def on_block(i, name, h_in, h_out):
        pre = f"model.layers.{i}.{name}."
        arrays = {k[len(pre):]: v for k, v in params.items()
                  if k.startswith(pre)}
        x = h_in.astype(jnp.float32)[None]
        got = functional_call(getattr(layers[i], name), arrays, x)
        want = (h_out - h_in).astype(jnp.float32)
        kind = kinds[i] if name == "self_attn" else "sparse_moe"
        out[f"{i}.{kind}"] = float(
            jnp.linalg.norm((got - x)[0] - want) / jnp.linalg.norm(want))

    ref.loss(params, jnp.asarray(ids), cfg, dtype, on_block=on_block)
    return out


def test_every_sub_block_matches_the_reference_and_the_controls_do_not(tiny):
    """Float32 both sides: equal to summation order. The same protocol
    tells each planted fault apart, by the blocks it touches and no
    others."""
    model, params, ids = tiny
    c = model.config

    def by_kind(errors, kind):
        return [v for k, v in errors.items() if k.endswith(kind)]

    clean = _block_errors(model, params, ids, _ref_cfg(c))
    assert sorted(clean) == [
        "0.sliding_attention", "0.sparse_moe", "1.sliding_attention",
        "1.sparse_moe", "2.sliding_attention", "2.sparse_moe",
        "3.full_attention", "3.sparse_moe"]
    assert max(clean.values()) < 1e-5
    # window layers given the whole triangle
    wide = _block_errors(model, params, ids, _ref_cfg(c, sliding_window=None))
    assert min(by_kind(wide, "sliding_attention")) > 0.05
    assert max(by_kind(wide, "full_attention") + by_kind(wide, "moe")) < 1e-5
    # full layers given default frequencies and factor 1
    plain = dict(c.rope_parameters,
                 full_attention=c.rope_parameters["sliding_attention"])
    unscaled = _block_errors(model, params, ids,
                             _ref_cfg(c, rope_parameters=plain))
    assert min(by_kind(unscaled, "full_attention")) > 0.05
    assert max(by_kind(unscaled, "sliding_attention")
               + by_kind(unscaled, "moe")) < 1e-5
    # one of a token's held experts dropped
    dropped = _block_errors(model, params, ids,
                            _ref_cfg(c, drop_first_held=True))
    assert min(by_kind(dropped, "moe")) > 0.05
    assert max(by_kind(dropped, "attention")) < 1e-5
    # everything in bf16
    low = _block_errors(model, params, ids, _ref_cfg(c), dtype=jnp.bfloat16)
    assert min(low.values()) > 1e-3


@pytest.mark.parametrize("head_dim,params", [
    (128, PUBLISHED_YARN),
    (128, {"rope_type": "default", "rope_theta": 500000}),
    (16, {"rope_type": "yarn", "rope_theta": 10000, "factor": 4,
          "original_max_position_embeddings": 16, "beta_fast": 2,
          "beta_slow": 0.25}),
], ids=["published_yarn", "published_default", "tiny_yarn_default_factor"])
def test_rope_frequencies_match_a_float64_evaluation(head_dim, params):
    """inv_freq against the formula one dimension at a time in Python's
    doubles, written out here (not the reference's copy): theta^(-2i/d),
    divided by `factor` where the ramp is 1, blended between the two
    correction dimensions."""
    inv, factor = rope_frequencies(params, head_dim)
    theta, half = float(params["rope_theta"]), head_dim // 2
    want = [theta ** (-2.0 * i / head_dim) for i in range(half)]
    if params["rope_type"] == "yarn":
        L = params["original_max_position_embeddings"]

        def dim_of(rot):
            return head_dim * math.log(L / (rot * 2 * math.pi)) \
                / (2 * math.log(theta))

        low = max(math.floor(dim_of(params["beta_fast"])), 0)
        high = min(math.ceil(dim_of(params["beta_slow"])), head_dim - 1)
        ramp = [min(max((i - low) / (high - low), 0.0), 1.0)
                for i in range(half)]
        want = [w / params["factor"] * r + w * (1 - r)
                for w, r in zip(want, ramp)]
        assert 0 <= low < high < half       # the ramp lies inside the pairs
        assert ramp[low] == 0.0 and ramp[high] == 1.0
        assert factor == pytest.approx(params.get(
            "attention_factor", 0.1 * math.log(params["factor"]) + 1))
    else:
        assert factor == 1.0
    assert inv.dtype == jnp.float32 and inv.shape == (half,)
    # float32 arithmetic on numbers down to 1e-7: a few ulps, relative
    np.testing.assert_allclose(np.asarray(inv, np.float64), want, rtol=2e-6)
    if head_dim == 128 and params["rope_type"] == "yarn":
        assert (low, high) == (18, 35)      # by hand, ISSUE 34
        assert factor == 1.2772588722239782


def test_the_attention_factor_scales_the_rotation_and_default_is_brumbys():
    from paddle_tpu.models import brumby
    x = jnp.asarray(np.random.RandomState(0).randn(1, 40, 2, 16), jnp.float32)
    inv, _ = rope_frequencies({"rope_type": "default", "rope_theta": 1e4}, 16)
    plain = apply_rope(x, inv)
    np.testing.assert_array_equal(np.asarray(plain),
                                  np.asarray(brumby._rope(x, 1e4)))
    np.testing.assert_allclose(np.asarray(apply_rope(x, inv, 1.25)),
                               1.25 * np.asarray(plain), rtol=1e-5,
                               atol=1e-6)
    # a rotation: norms are kept
    np.testing.assert_allclose(np.linalg.norm(np.asarray(plain), axis=-1),
                               np.linalg.norm(np.asarray(x), axis=-1),
                               rtol=1e-5)


def test_softmax_over_the_chosen_logits_is_the_renormalised_top_k():
    """route_top_k's gates against the published routing written out:
    softmax over all 64 outputs, top-8 of the probabilities, weights
    divided by their sum."""
    rs = np.random.RandomState(7)
    x = jnp.asarray(rs.randn(50, 32), jnp.float32)
    w = jnp.asarray(rs.randn(32, 64), jnp.float32)
    ids, gates = route_top_k(x, w, 8)
    probs = jax.nn.softmax(x @ w, axis=-1)
    top, top_ids = jax.lax.top_k(probs, 8)
    np.testing.assert_array_equal(np.sort(np.asarray(ids), -1),
                                  np.sort(np.asarray(top_ids), -1))
    want = top / jnp.sum(top, -1, keepdims=True)
    order = np.argsort(np.asarray(ids), -1)
    want_order = np.argsort(np.asarray(top_ids), -1)
    np.testing.assert_allclose(
        np.take_along_axis(np.asarray(gates), order, -1),
        np.take_along_axis(np.asarray(want), want_order, -1), rtol=2e-6)


def test_the_four_shares_add_up_to_the_uncut_layer(tiny):
    """Routed parts from each quarter of the experts (the deployment: four
    chips share a layer's 16 of 64; here 4 of 16) add up to what the uncut
    reference gives for the whole layer; nothing is computed by every
    chip alike (no shared expert), so nothing is counted once."""
    model, params, ids = tiny
    c = model.config
    pre = "model.layers.1.mlp."
    p = {k[len(pre):]: v for k, v in params.items() if k.startswith(pre)}
    x = jnp.asarray(np.random.RandomState(9).randn(58, 64), jnp.float32)
    with jax.default_matmul_precision("highest"):
        whole = ref.routed(x, p, _ref_cfg(c))
    parts = []
    for first in range(0, 16, 4):
        parts.append(dropless_moe(
            x, p["gate.weight"], p["experts.gate_up_proj"][first:first + 4],
            p["experts.down_proj"][first:first + 4], c.num_experts_per_tok,
            (first, 4)))
        with jax.default_matmul_precision("highest"):
            want = ref.routed(x, dict(
                p, **{"experts.gate_up_proj":
                      p["experts.gate_up_proj"][first:first + 4],
                      "experts.down_proj":
                      p["experts.down_proj"][first:first + 4]}),
                _ref_cfg(c, experts_held=(first, 4)))
        scale = float(jnp.abs(whole).max())
        assert float(jnp.abs(parts[-1] - want).max()) < 1e-5 * scale
    total = sum(parts)
    assert float(jnp.abs(total - whole).max()) < 1e-5 * scale
    # a share alone is not the layer: the cut leaves something out
    assert float(jnp.abs(parts[0] - whole).max()) > 0.1 * scale


def test_the_model_builds_under_lazy_guard():
    with paddle.LazyGuard():
        model = mellum_tiny()
    shapes = {k: tuple(t.shape) for k, t in model.state_dict().items()}
    assert shapes["model.layers.0.mlp.experts.down_proj"] == (16, 24, 64)


def test_bf16_model_trains_through_the_trainer_and_the_loss_falls():
    paddle.seed(5)
    model = mellum_tiny(dtype="bfloat16", experts_held=(4, 8))
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


def test_routing_out_of_the_backward_matches_the_reference_without_it(tiny):
    """`differentiate_routing=False` (what a share of the experts trains
    with): the loss is what it was; every gradient equals the reference's
    with its gates held constant; the routers get none; the others lose
    the routers' path, so they are no longer the full model's."""
    model, params, ids = tiny
    cut = mellum_tiny(initializer_range=0.3, differentiate_routing=False)
    assert model.config.differentiate_routing
    assert not cut.config.differentiate_routing
    full, full_grads = jax.jit(jax.value_and_grad(make_loss_fn(model)))(
        params, (ids, ids), None)
    got, grads = jax.jit(jax.value_and_grad(make_loss_fn(cut)))(
        params, (ids, ids), None)
    cfg = _ref_cfg(cut.config)
    assert cfg["differentiate_routing"] is False
    with jax.default_matmul_precision("highest"):
        want, ref_grads = jax.jit(jax.value_and_grad(
            lambda p, i: ref.forward_loss(p, i, cfg)))(params,
                                                       jnp.asarray(ids))
    assert float(got) == float(full) and abs(float(got) - float(want)) < 1e-5
    routers = [n for n in grads if n.endswith("mlp.gate.weight")]
    assert len(routers) == 4 and set(grads) == set(ref_grads)
    moved = 0
    for name in sorted(grads):
        g, r = np.asarray(grads[name]), np.asarray(ref_grads[name])
        if name in routers:
            assert not g.any() and not r.any(), name
            assert np.abs(np.asarray(full_grads[name])).max() > 0
            continue
        # the tolerance of test_loss_and_every_gradient_match_the_reference
        assert np.abs(g - r).max() <= 2e-4 * np.abs(r).max() + 1e-8, name
        f = np.asarray(full_grads[name])
        moved += np.abs(g - f).max() > 1e-2 * np.abs(f).max()
    assert moved >= 10       # what lies under a router lost a path


def test_an_undifferentiated_router_stays_bit_for_bit_through_bf16_adamw():
    """With a zero gradient AdamW still decays the weight by lr x 0.01 of
    itself a step; at the cell's rate (1e-4) that is a millionth, which a
    bf16 weight (half an ulp: 2^-9) rounds away: the routing function is
    the seed's for the whole run, and everything else trains."""
    paddle.seed(5)
    model = mellum_tiny(dtype="bfloat16", experts_held=(4, 8),
                        differentiate_routing=False)
    opt = optimizer.AdamW(1e-4, parameters=model.parameters())
    trainer = SpmdTrainer(model, opt, create_mesh(devices=jax.devices()[:1]),
                          DP_ONLY_RULES, dtype="bfloat16")
    before = {k: np.asarray(v.astype(jnp.float32))
              for k, v in trainer.params.items()}
    ids = np.random.RandomState(1).randint(0, 256, (2, 24)).astype(np.int32)
    losses = [float(trainer.step((ids, ids))) for _ in range(4)]
    assert all(np.isfinite(losses)) and losses[-1] < losses[0]
    for name, was in before.items():
        now = np.asarray(trainer.params[name].astype(jnp.float32))
        if name.endswith("mlp.gate.weight"):
            assert (now == was).all(), name
        elif not name.endswith(("norm.weight", "layernorm.weight")):
            assert (now != was).any(), name


def test_generate_recomputes_the_prefix():
    paddle.seed(2)
    model = mellum_tiny()
    ids = np.random.RandomState(2).randint(0, 256, (1, 11)).astype(np.int32)
    out = model.generate(paddle.Tensor(jnp.asarray(ids)), max_new_tokens=2)
    out = np.asarray(out._data)
    assert out.shape == (1, 13) and (out[:, :11] == ids).all()
    logits = functional_call(
        model, {k: v._data for k, v in model.state_dict().items()},
        out[:, :-1])
    assert int(jnp.argmax(logits[0, -1])) == out[0, -1]     # greedy
