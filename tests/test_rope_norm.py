"""models/rope.py `norm_rope`: the per-head q/k RMSNorm, its weight and the
rotation as one function, its two branches (the `jax.numpy` composition and
the kernels of ops/pallas/rope_norm.py, interpreted here) against a float64
evaluation written out in this file, forward and gradients, and the rule
that sends a call down one or the other.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.generation import _rms
from paddle_tpu.models import rope
from paddle_tpu.models.rope import apply_rope, norm_rope, rope_frequencies
from paddle_tpu.ops.pallas import rope_norm

EPS = 1e-6
DEFAULT = {"rope_type": "default", "rope_theta": 1e6}
# a factor of 4 over 64 original positions: the ramp lies inside the
# pairs at both head sizes, and the attention factor is not 1
YARN = {"rope_type": "yarn", "rope_theta": 1e4, "factor": 4.0,
        "original_max_position_embeddings": 64, "beta_fast": 4,
        "beta_slow": 0.5, "attention_factor": 1.25}
# (batch, positions, heads, head_dim): the q and k projections of the
# Mellum cell (2 x 16,384 x 32 and x 4) and of the Brumby cell (1 x 16,384
# x 40 and x 8), cut to a few hundred positions
CELL_SHAPES = {"mellum_q": (2, 192, 32, 128), "mellum_k": (2, 192, 4, 128),
               "brumby_q": (1, 320, 40, 128), "brumby_k": (1, 320, 8, 128)}


def _operands(shape, dtype, seed=0):
    rng = np.random.RandomState(seed)
    x = jnp.asarray(rng.randn(*shape) * 1.5, dtype)
    w = jnp.asarray(1 + 0.2 * rng.randn(shape[-1]), dtype)
    g = jnp.asarray(rng.randn(*shape), dtype)
    return x, w, g


def _kernels(x, w, inv, factor):
    """The kernels' branch whatever the backend: off a TPU the calls are
    interpreted (ops/pallas/flash_attention.py _interpret_default)."""
    return rope._norm_rope_in_vmem(x, w, inv, EPS, float(factor))


def _composition(x, w, inv, factor):
    return apply_rope(_rms(x, w, EPS), inv, factor)


PATHS = {"kernels": _kernels, "composition": _composition}


def _float64(x, w, g, inv, factor):
    """(out, dx, dw) in doubles, the rotation written by halves: out =
    [a cos - b sin, b cos + a sin] of y = w u / sqrt(mean(u^2) + eps)."""
    u, w, g = (np.asarray(t, np.float64) for t in (x, w, g))
    half = u.shape[-1] // 2
    angle = np.arange(u.shape[1], dtype=np.float64)[:, None] \
        * np.asarray(inv, np.float64)
    cos, sin = (factor * f(angle)[None, :, None, :] for f in (np.cos, np.sin))
    r = 1.0 / np.sqrt(np.mean(u * u, -1, keepdims=True) + EPS)
    n = u * r
    y = n * w
    a, b = y[..., :half], y[..., half:]
    out = np.concatenate([a * cos - b * sin, b * cos + a * sin], -1)
    ga, gb = g[..., :half], g[..., half:]
    dy = np.concatenate([ga * cos + gb * sin, gb * cos - ga * sin], -1)
    dn = dy * w
    du = r * (dn - n * np.mean(dn * n, -1, keepdims=True))
    return out, du, np.sum(dy * n, axis=(0, 1, 2))


def _relative(got, want):
    got = np.asarray(jnp.asarray(got, jnp.float32), np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def _bf16_steps(got, ref):
    """The largest difference in bf16 steps at the size of the rotated pair
    an entry belongs to (an entry that the pair's two terms nearly cancel
    in is small beside what was rounded)."""
    got, ref = (np.asarray(jnp.asarray(t, jnp.float32), np.float64)
                for t in (got, ref))
    pair = np.sqrt(ref ** 2 + np.roll(ref, ref.shape[-1] // 2, -1) ** 2)
    step = 2.0 ** (np.floor(np.log2(np.maximum(pair, 1e-30))) - 7)
    return float(np.max(np.abs(got - ref) / step))


def test_the_float64_evaluation_s_gradients_are_its_finite_differences():
    """What every other test here is held to: its du and dw against central
    differences of its own forward."""
    x, w, g = _operands((1, 5, 2, 8), jnp.float32, 3)
    inv, factor = rope_frequencies(DEFAULT, 8)
    x64, w64, g64 = (np.asarray(t, np.float64) for t in (x, w, g))
    _, du, dw = _float64(x64, w64, g64, inv, 1.25)

    def value(x_, w_):
        return float(np.sum(_float64(x_, w_, g64, inv, 1.25)[0] * g64))

    h = 1e-6
    for index in [(0, 0, 0, 0), (0, 3, 1, 5), (0, 4, 0, 7)]:
        bump = np.zeros_like(x64)
        bump[index] = h
        slope = (value(x64 + bump, w64) - value(x64 - bump, w64)) / (2 * h)
        assert slope == pytest.approx(du[index], rel=1e-6, abs=1e-9)
    for lane in (0, 3, 7):
        bump = np.zeros_like(w64)
        bump[lane] = h
        slope = (value(x64, w64 + bump) - value(x64, w64 - bump)) / (2 * h)
        assert slope == pytest.approx(dw[lane], rel=1e-6, abs=1e-9)


CASES = (
    [("kernels", name, "bfloat16", kind)
     for name in CELL_SHAPES for kind in ("default", "yarn")]
    + [("kernels", "mellum_k", "float32", "yarn"),
       ("kernels", "brumby_k", "float32", "default")]
    + [("composition", name, "bfloat16", kind)
       for name in ("mellum_k", "brumby_k") for kind in ("default", "yarn")]
    + [("composition", "tiny", dtype, kind)
       for dtype in ("bfloat16", "float32") for kind in ("default", "yarn")])


@pytest.mark.parametrize("path, shape, dtype, kind", CASES,
                         ids=["-".join(c) for c in CASES])
@pytest.mark.parametrize("what", ["forward", "dx", "dw"])
def test_norm_rope_against_a_float64_evaluation(path, shape, dtype, kind,
                                                what):
    """Each branch at the cells' projections (head_dim 128) and the
    composition at the tiny models' head_dim 16, default and YaRN
    frequencies with an attention factor. bf16: three roundings a forward
    entry (the norm, the weight, the rotation), so three bf16 steps at the
    pair's size at most, 0.4% rms; a gradient is rounded once (the kernels)
    or at every step of the composition's backward."""
    dims = CELL_SHAPES.get(shape, (2, 40, 3, 16))
    x, w, g = _operands(dims, getattr(jnp, dtype))
    inv, factor = rope_frequencies(YARN if kind == "yarn" else DEFAULT,
                                   dims[-1])
    want = dict(zip(("forward", "dx", "dw"),
                    _float64(x, w, g, inv, factor)))[what]
    fn = PATHS[path]
    if what == "forward":
        got = fn(x, w, inv, factor)
        assert got.dtype == x.dtype and got.shape == x.shape
        if dtype == "bfloat16":
            assert _bf16_steps(got, want) <= 3.0
    else:
        dx, dw = jax.vjp(lambda x_, w_: fn(x_, w_, inv, factor), x, w)[1](g)
        assert dx.dtype == x.dtype and dw.dtype == w.dtype
        assert dx.shape == x.shape and dw.shape == w.shape
        got = dx if what == "dx" else dw
    # the composition's dw is a sum of bf16 products over every token and
    # head; the kernels' is float32 until its one rounding
    limit = {"float32": 2e-5, "bfloat16": 0.03 if (
        what, path) == ("dw", "composition") else 0.005}[dtype]
    assert _relative(got, want) < limit


@pytest.mark.parametrize("shape", list(CELL_SHAPES))
@pytest.mark.parametrize("kind", ["default", "yarn"])
def test_the_kernels_forward_is_the_composition_s_to_a_bf16_step(shape, kind):
    """The kernels round where the composition does (the normalised value,
    its product with the weight, the rotated value), so against the
    composition run an operation at a time (a compiled one may keep float32
    between two roundings) nothing differs by more than one bf16 step."""
    x, w, _ = _operands(CELL_SHAPES[shape], jnp.bfloat16, 1)
    inv, factor = rope_frequencies(YARN if kind == "yarn" else DEFAULT, 128)
    got, ref = _kernels(x, w, inv, factor), _composition(x, w, inv, factor)
    assert _bf16_steps(got, ref) <= 1.0
    assert float(jnp.mean((got != ref).astype(jnp.float32))) < 1e-3


def test_the_tables_are_the_rotation_s_by_halves():
    """cos twice and sin with the first half's sign: x cos + roll(x, d/2)
    sin is [a cos - b sin, b cos + a sin], scaled by the factor."""
    inv, _ = rope_frequencies(DEFAULT, 16)
    cos, sin = rope._rotation_tables(24, inv, 1.25)
    angle = np.arange(24)[:, None] * np.asarray(inv, np.float64)
    assert cos.dtype == sin.dtype == jnp.float32 and cos.shape == (24, 16)
    np.testing.assert_allclose(cos, 1.25 * np.cos(np.tile(angle, 2)),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(
        sin, 1.25 * np.sin(np.tile(angle, 2)) * np.repeat([-1, 1], 8),
        rtol=1e-5, atol=1e-6)


# (case, backend, shape, x's type, the weight's type) -> the kernels?
RULE = [
    ("mellum_q_on_a_tpu", "tpu", (2, 16384, 32, 128), "bfloat16", None, True),
    ("brumby_k_on_a_tpu", "tpu", (1, 16384, 8, 128), "bfloat16", None, True),
    ("float32", "tpu", (1, 2048, 8, 128), "float32", None, True),
    ("head_dim_256", "tpu", (1, 2048, 4, 256), "bfloat16", None, True),
    ("a_few_hundred_positions", "tpu", (2, 320, 4, 128), "bfloat16", None,
     True),
    ("the_tiny_models_head_dim", "tpu", (2, 64, 4, 16), "bfloat16", None,
     False),
    ("head_dim_64", "tpu", (2, 2048, 4, 64), "bfloat16", None, False),
    ("head_dim_192", "tpu", (2, 2048, 4, 192), "bfloat16", None, False),
    ("no_row_tile_divides_the_positions", "tpu", (1, 1000, 4, 128),
     "bfloat16", None, False),
    ("float16", "tpu", (1, 2048, 4, 128), "float16", None, False),
    ("a_float32_weight_on_bf16", "tpu", (1, 2048, 4, 128), "bfloat16",
     "float32", False),
    ("cpu", "cpu", (2, 16384, 32, 128), "bfloat16", None, False),
    ("gpu", "gpu", (2, 16384, 32, 128), "bfloat16", None, False),
]


@pytest.mark.parametrize("case", RULE, ids=[c[0] for c in RULE])
def test_the_input_decides_the_branch(monkeypatch, case):
    """`norm_rope` runs the kernels on a TPU at a head_dim of whole lane
    registers, bf16 or float32 throughout, positions a row tile divides;
    the composition everywhere else. Nothing else is asked."""
    _, backend, shape, dtype, weight_dtype, kernels = case
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    x = jax.ShapeDtypeStruct(shape, getattr(jnp, dtype))
    w = jax.ShapeDtypeStruct(shape[-1:], getattr(jnp, weight_dtype or dtype))
    assert rope._rotates_whole_lanes(x, w) is kernels
    taken = []
    monkeypatch.setattr(rope, "_norm_rope_in_vmem",
                        lambda x_, *a: taken.append("kernels") or x_)
    monkeypatch.setattr(rope, "apply_rope",
                        lambda x_, *a: taken.append("composition") or x_)
    inv, factor = rope_frequencies(DEFAULT, shape[-1])
    jax.eval_shape(lambda x_, w_: norm_rope(x_, w_, EPS, inv, factor), x, w)
    assert taken == ["kernels" if kernels else "composition"]


@pytest.mark.parametrize("positions, most, tile", [
    (16384, 512, 512), (8192, 512, 512), (320, 512, 320), (1000, 512, None),
    (2000, 512, 400), (24, 512, None), (48, 512, 48), (16384, 1024, 1024)])
def test_a_row_tile_divides_the_positions(positions, most, tile):
    assert rope_norm.row_tile(positions, most) == tile
    if tile:
        assert tile % rope_norm.ROW_MULTIPLE == 0 and positions % tile == 0


@pytest.mark.parametrize("tile, heads", [(64, 1), (32, 4), (128, 2), (16, 8)])
def test_every_tiling_of_the_kernels_gives_the_same_numbers(tile, heads):
    """Row tiles and heads a block change the grid, not the numbers: the
    tables' block follows the row tile through two sequences, the weight's
    gradient is summed over every tile."""
    x, w, g = _operands((2, 128, 8, 128), jnp.bfloat16, 2)
    inv, factor = rope_frequencies(YARN, 128)
    tables = rope._rotation_tables(128, inv, factor)
    rows, g_rows = rope._as_rows(x), rope._as_rows(g)
    want = rope_norm.forward(rows, w, *tables, EPS)
    want_dx, want_dw = rope_norm.backward(rows, w, *tables, g_rows, EPS)
    got = rope_norm.forward(rows, w, *tables, EPS, tile=tile, heads=heads)
    dx, dw = rope_norm.backward(rows, w, *tables, g_rows, EPS, tile=tile,
                                heads=heads)
    # a sum over a head's lanes may be taken in another order at another
    # block shape: a bf16 step in a few entries of a hundred thousand
    for a, b in ((got, want), (dx, want_dx)):
        assert _bf16_steps(a, b) <= 1.0
        assert float(jnp.mean((a != b).astype(jnp.float32))) < 1e-4
    np.testing.assert_allclose(dw, want_dw, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("x, d, positions", [
    ((64, 256), 96, 64),          # a head of 96 lanes
    ((64, 200), 128, 64),         # not whole heads
    ((64, 256), 128, 48),         # tokens no multiple of the positions
], ids=["head_dim", "heads", "positions"])
def test_the_kernels_refuse_what_they_cannot_tile(x, d, positions):
    table = jnp.zeros((positions, d), jnp.float32)
    with pytest.raises(ValueError):
        rope_norm.forward(jnp.zeros(x, jnp.bfloat16),
                          jnp.ones((d,), jnp.bfloat16), table, table, EPS)


def test_a_mellum_mixer_is_the_same_through_either_branch(monkeypatch):
    """models/mellum.py MellumAttention at head_dim 128, value and every
    gradient: the kernels' branch (interpreted) against the composition's.
    """
    import paddle_tpu as paddle
    from paddle_tpu.models.mellum import MellumAttention, MellumConfig
    cfg = MellumConfig(
        num_hidden_layers=1, layer_types=["full_attention"], vocab_size=64,
        hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
        head_dim=128, dtype="float32")
    paddle.seed(0)
    sub = MellumAttention(cfg, "full_attention")
    params = {n.replace(".", "_"): t._data * (1 + 0.1 * i)
              for i, (n, t) in enumerate(sub.named_parameters())}
    h = jnp.asarray(np.random.RandomState(0).randn(2, 32, 64), jnp.float32)

    def value_and_grads():
        return jax.value_and_grad(lambda h_, p_: jnp.sum(
            sub._pure(h_, **p_) ** 2), argnums=(0, 1))(h, params)

    want = value_and_grads()
    monkeypatch.setattr(rope, "_rotates_whole_lanes", lambda *a: True)
    got = value_and_grads()
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=1e-5)
