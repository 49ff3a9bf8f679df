"""Phi-4-mini-flash (models/phi4flash.py) against its plain reference
(benchmark/references/phi4flash_ref.py) at a tiny size on the CPU: the
cell's ten layers in the published order at their published indices
(two Mamba / window pairs, the memory Mamba, the full layer, two GMU /
cross pairs), hidden 64, 8 query over 4 key-value heads of 8 lanes, state
16, window 8, two sequences of 29 tokens (3.6 windows), seeded weights.

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
from paddle_tpu.models import phi4flash
from paddle_tpu.models.phi4flash import (Phi4FlashConfig, lambda_init,
                                         phi4flash_tiny,
                                         published_layer_types)
from paddle_tpu.ops.pallas import selective_scan as scan_kernels
from paddle_tpu.parallel import DP_ONLY_RULES, SpmdTrainer, create_mesh
from paddle_tpu.parallel.functional import functional_call, make_loss_fn

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_reference():
    path = os.path.join(REPO, "benchmark", "references", "phi4flash_ref.py")
    spec = importlib.util.spec_from_file_location("phi4flash_ref", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load_reference()


def _ref_cfg(c, **extra):
    return dict({k: getattr(c, k) for k in ref.CFG_KEYS}, **extra)


@pytest.fixture(scope="module")
def tiny():
    paddle.seed(13)
    model = phi4flash_tiny(initializer_range=0.2)
    params = {k: v._data for k, v in model.state_dict().items()}
    ids = np.random.RandomState(4).randint(0, 256, (2, 29)).astype(np.int32)
    return model, params, ids


def test_the_layer_list_rebuilds_the_published_order():
    kinds = published_layer_types(32, 2)
    assert kinds[:16] == ["mamba", "sliding_attention"] * 8
    assert kinds[16:18] == ["memory_mamba", "full_attention"]
    assert kinds[18:] == ["gmu", "cross_attention"] * 7
    assert Phi4FlashConfig().layer_types == kinds
    # the cell's cut: each kept layer is the published one at its index
    cfg = phi4flash_tiny().config
    assert cfg.layer_indices == [0, 1, 2, 3, 16, 17, 18, 19, 20, 21]
    assert cfg.layer_types == [kinds[i] for i in cfg.layer_indices]
    assert lambda_init(0) == pytest.approx(0.2)
    assert lambda_init(17) == pytest.approx(0.8 - 0.6 * np.exp(-5.1))
    with pytest.raises(ValueError):          # a GMU with no memory before it
        Phi4FlashConfig(num_hidden_layers=2, layer_types=["gmu", "mamba"])
    with pytest.raises(ValueError):
        Phi4FlashConfig(num_hidden_layers=2, layer_types=[
            "cross_attention", "full_attention"])
    with pytest.raises(NotImplementedError):
        Phi4FlashConfig(tie_word_embeddings=False)


def test_parameters_carry_the_published_shapes(tiny):
    model, params, _ = tiny
    shapes = {k: tuple(v.shape) for k, v in params.items()}
    # hidden 64 -> E 128, dt rank 4, 16 states
    mamba = "model.layers.4.mixer."
    assert shapes[mamba + "in_proj.weight"] == (64, 256)
    assert shapes[mamba + "conv1d.weight"] == (4, 128)
    assert shapes[mamba + "x_proj.weight"] == (128, 4 + 32)
    assert shapes[mamba + "dt_proj.weight"] == (4, 128)
    assert shapes[mamba + "A_log"] == (128, 16)
    assert shapes[mamba + "out_proj.weight"] == (128, 64)
    full = "model.layers.5.mixer."
    assert shapes[full + "qkv_proj.weight"] == (64, (8 + 2 * 4) * 8)
    assert shapes[full + "sub_norm.weight"] == (16,)
    assert shapes[full + "lambda_q1"] == (8,)
    assert shapes["model.layers.7.mixer.q_proj.weight"] == (64, 64)
    assert "model.layers.7.mixer.qkv_proj.weight" not in shapes
    assert shapes["model.layers.6.mixer.in_proj.weight"] == (64, 128)
    assert shapes["model.layers.6.mlp.fc1.weight"] == (64, 192)
    assert not [k for k in shapes if "lm_head" in k]        # tied
    # Mamba-1's published values, not a normal draw
    a_log = np.asarray(params[mamba + "A_log"])
    assert np.allclose(a_log, np.log(np.arange(1, 17))[None], atol=1e-6)
    assert np.all(np.asarray(params[mamba + "D"]) == 1)


def test_logits_match_the_reference(tiny):
    model, params, ids = tiny
    got = functional_call(model, params, ids)
    cfg = _ref_cfg(model.config)
    with jax.default_matmul_precision("highest"):
        want = jnp.stack([ref.logits(
            ref.hidden_states(params, jnp.asarray(ids[b]), cfg), params, cfg)
            for b in range(2)])
    assert got.shape == want.shape == (2, 29, 256)
    # float32 both sides: summation order over 10 layers; logits of order 1
    assert np.abs(np.asarray(got - want)).max() < 1e-4 * np.abs(
        np.asarray(want)).max()


_JITTED = {}


def _grads(model, params, ids):
    """(loss, gradients) of the model's train loss: one compile a model and
    rule, whatever the parameters' values."""
    key = (id(model), scan_kernels.supported)
    if key not in _JITTED:
        _JITTED[key] = jax.jit(jax.value_and_grad(make_loss_fn(model)))
    return _JITTED[key](params, (ids, ids), None)


def test_loss_and_every_gradient_match_the_reference(tiny):
    model, params, ids = tiny
    cfg = _ref_cfg(model.config)
    got, grads = _grads(model, params, ids)
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
        # relative to the gradient's own scale: 5e-4 leaves room for the
        # float32 sums over 29 positions x 10 layers and is far under what a
        # wrong term gives (a state not carried, a mask off by one key, a
        # lambda left out: errors of order 0.01-1)
        assert np.abs(g - r).max() <= 5e-4 * np.abs(r).max() + 1e-8, name


def test_the_references_layer_by_layer_gradients_are_its_jax_grad(
        tiny, monkeypatch):
    """What the cell holds the program's first step to: the reference's
    loss and gradients one layer's vjp at a time (the memory's and the
    shared keys' and values' cotangents summed by hand), with its row
    blocks and scan chunks small enough here that the rematerialised paths
    run, against jax.value_and_grad of the same reference written whole."""
    model, params, _ = tiny
    cfg = _ref_cfg(model.config)
    ids = np.random.RandomState(5).randint(0, 256, (2, 32)).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        want, want_grads = jax.jit(jax.value_and_grad(
            lambda p, i: ref.forward_loss(p, i, cfg)))(params,
                                                       jnp.asarray(ids))
    monkeypatch.setattr(ref, "SCAN_CHUNK", 8)
    monkeypatch.setattr(ref, "ROW_BLOCK", 8)
    got, grads = ref.loss_and_grads(params, ids, cfg)
    assert abs(got - float(want)) < 1e-5
    assert set(grads) == set(want_grads)
    for name, g in grads.items():
        r = np.asarray(want_grads[name])
        # float32 both sides, sums in another order (the same 5e-4 as the
        # program's gradients above)
        assert np.abs(g - r).max() <= 5e-4 * np.abs(r).max() + 1e-8, name


def test_the_cross_layers_reach_the_full_layer_and_the_gmus_the_memory(
        tiny):
    """The cross-decoder's only path to layer 17's key and value
    projection is the shared k and v, and the GMUs' only path to the
    memory Mamba's parameters below its output projection is the memory:
    cut the later layers off the loss and those gradients change."""
    model, params, ids = tiny
    _, grads = _grads(model, params, ids)
    c = model.config
    kv_cols = slice(c.num_attention_heads * c.head_dim, None)

    def without(kinds):
        # the same loss with the chosen mixers' output projections zeroed:
        # they then add nothing and pass nothing back
        cut = dict(params)
        for i, kind in enumerate(c.layer_types):
            if kind in kinds:
                name = ("o_proj.weight" if kind == "cross_attention"
                        else "out_proj.weight")
                key = f"model.layers.{i}.mixer.{name}"
                cut[key] = jnp.zeros_like(params[key])
        return _grads(model, cut, ids)[1]

    no_cross = without(("cross_attention",))
    full_kv = "model.layers.5.mixer.qkv_proj.weight"
    assert np.abs(np.asarray(grads[full_kv])[:, kv_cols]
                  - np.asarray(no_cross[full_kv])[:, kv_cols]).max() > 1e-4
    no_gmu = without(("gmu",))
    for name in ("in_proj.weight", "A_log", "x_proj.weight"):
        key = f"model.layers.4.mixer.{name}"
        assert np.abs(np.asarray(grads[key])
                      - np.asarray(no_gmu[key])).max() > 1e-6, name


@pytest.mark.parametrize("window", [None, 5])
def test_differential_attention_is_the_two_softmax_formula(window):
    """models/phi4flash.py differential_attention (one attention call over
    stacked maps, q and k zero-padded) against the formula written out per
    head: RMSNorm(softmax(q1 k1^T / sqrt(d)) v - lam softmax(q2 k2^T /
    sqrt(d)) v) w (1 - lam_init), v two value heads side by side."""
    ks = jax.random.split(jax.random.key(1), 6)
    b, t, nq, nkv, dh = 2, 11, 8, 4, 8
    q = jax.random.normal(ks[0], (b, t, nq, dh))
    k = jax.random.normal(ks[1], (b, t, nkv, dh))
    v = jax.random.normal(ks[2], (b, t, nkv, dh))
    lams = [0.3 * jax.random.normal(kk, (dh,)) for kk in
            jax.random.split(ks[3], 4)]
    w = jax.random.normal(ks[4], (2 * dh,))
    init, eps = lambda_init(17), 1e-5
    got = phi4flash.differential_attention(q, k, v, lams, w, init, window,
                                           eps)
    lam = (np.exp(np.dot(lams[0], lams[1])) - np.exp(np.dot(lams[2],
                                                            lams[3]))
           + init)
    back = np.arange(t)[:, None] - np.arange(t)[None, :]
    keep = (back >= 0) & ((back < window) if window else True)

    def softmax_map(qh, kh):
        s = np.where(keep, qh @ kh.T / np.sqrt(dh), -np.inf)
        p = np.exp(s - s.max(-1, keepdims=True))
        return p / p.sum(-1, keepdims=True)

    q, k, v = (np.asarray(x, np.float64) for x in (q, k, v))
    want = np.zeros((b, t, nq // 2, 2 * dh))
    for bi in range(b):
        for i in range(nq // 2):
            j = i // ((nq // 2) // (nkv // 2))
            vj = np.concatenate([v[bi, :, 2 * j], v[bi, :, 2 * j + 1]], -1)
            o = (softmax_map(q[bi, :, 2 * i], k[bi, :, 2 * j]) @ vj
                 - lam * softmax_map(q[bi, :, 2 * i + 1],
                                     k[bi, :, 2 * j + 1]) @ vj)
            o = o / np.sqrt(np.mean(o * o, -1, keepdims=True) + eps)
            want[bi, :, i] = o * np.asarray(w) * (1 - init)
    assert np.abs(np.asarray(got).reshape(want.shape) - want).max() < 1e-4


def test_loss_and_gradients_through_the_scan_kernels(tiny, monkeypatch):
    """With the rule steered to the Pallas kernels (interpreted here), the
    loss and every gradient are the jax.numpy scan's to float32 rounding:
    the model hands the kernels what they need (E = 128 channels, 16
    states, B and C in any type) and takes their cotangents back."""
    model, params, ids = tiny
    want, wgrads = _grads(model, params, ids)
    monkeypatch.setattr(scan_kernels, "supported", lambda u, a: True)
    got, grads = _grads(model, params, ids)
    assert abs(float(got) - float(want)) < 1e-6
    for name in sorted(grads):
        g, w = np.asarray(grads[name]), np.asarray(wgrads[name])
        assert np.abs(g - w).max() <= 1e-4 * np.abs(w).max() + 1e-8, name


def test_the_model_builds_under_lazy_guard_and_counts_its_parameters():
    """The cell's parameter count at the published widths, by the shapes a
    lazily built model declares (nothing is drawn): 3 Mamba-1, 2 window,
    1 full, 2 cross, 2 GMU mixers, 10 MLPs, the tied embedding slice."""
    kinds = published_layer_types(32)
    idx = [0, 1, 2, 3, 16, 17, 18, 19, 20, 21]
    with paddle.LazyGuard():
        model = phi4flash.Phi4FlashForCausalLM(Phi4FlashConfig(
            vocab_size=25008, num_hidden_layers=10,
            layer_types=[kinds[i] for i in idx], layer_indices=idx,
            dtype="bfloat16"))
    n = sum(int(np.prod(p.shape)) for p in model.parameters())
    # mixers with their biases, lambdas and sub-norm; two LayerNorms
    # (weight and bias) a layer; the final LayerNorm
    mixers = {"mamba": 41_241_600, "attention": 19_661_184,
              "cross": 13_107_584, "gmu": 26_214_400}
    per_layer = 78_643_200 + 2 * 2 * 2560                 # MLP and norms
    assert n == (3 * mixers["mamba"] + 3 * mixers["attention"]
                 + 2 * mixers["cross"] + 2 * mixers["gmu"]
                 + 10 * per_layer + 25008 * 2560 + 2 * 2560)
    assert n == 1_111_912_320


def test_bf16_model_trains_through_the_trainer_and_the_loss_falls():
    paddle.seed(0)
    model = phi4flash_tiny(dtype="bfloat16")
    opt = optimizer.AdamW(1e-2, parameters=model.parameters())
    trainer = SpmdTrainer(model, opt, create_mesh(devices=jax.devices()[:1]),
                          DP_ONLY_RULES, dtype="bfloat16")
    ids = np.random.RandomState(0).randint(0, 256, (1, 24)).astype(np.int32)
    losses = [float(trainer.step((ids, ids))) for _ in range(4)]
    assert all(np.isfinite(losses)) and losses[-1] < losses[0]
