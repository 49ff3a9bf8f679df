"""ops/power_retention.py at a small size on the CPU: the chunked form with
its carried state against the quadratic form (the `a_ts` matrix of the
published description) and against the token-by-token recurrence with the
MINIMAL symmetric expansion, both written here in float64 numpy and
sharing nothing with the op; values and gradients; chunks that do and do
not divide the length; five query heads a state head. At head_dim 128 the
products with an expansion are the kernels of ops/pallas/power_retention.py
(under the Pallas interpreter here): each against the jax.numpy expansion,
their two differentiable operations against `jax.vjp` of the einsum form,
and the whole op against the same two float64 forms; at any other
head_dim the jax.numpy expansion stays.

Each tolerance has its reason beside it.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import power_retention as op
from paddle_tpu.ops.pallas import power_retention as kernels
from paddle_tpu.ops.power_retention import (expand_keys, expand_queries,
                                            power_retention,
                                            retention_features)

EPS = 1e-6


def _draw(seed, b=2, s=29, heads=10, groups=2, d=8, horizon=(2.0, 64.0)):
    """q, k, v, log_g in float32: unit-scale vectors, log-decays whose
    horizons 1 / (1 - g) are log-uniform in `horizon`."""
    rs = np.random.RandomState(seed)
    q = rs.randn(b, s, heads, d).astype(np.float32)
    k = rs.randn(b, s, groups, d).astype(np.float32)
    v = rs.randn(b, s, groups, d).astype(np.float32)
    hz = np.exp(rs.uniform(np.log(horizon[0]), np.log(horizon[1]),
                           (b, s, groups)))
    return q, k, v, np.log1p(-1.0 / hz).astype(np.float32)


def _quadratic(q, k, v, log_g, eps=EPS):
    """y_t = sum_{s<=t} a_ts v_s / (sum_{s<=t} a_ts + eps), a_ts =
    exp(L_t - L_s) (q_t . k_s / sqrt(d))^2, float64, one head at a time."""
    q, k, v, log_g = (np.asarray(t, np.float64) for t in (q, k, v, log_g))
    b, s, heads, d = q.shape
    rep = heads // k.shape[2]
    cum = np.cumsum(log_g, axis=1)
    y = np.zeros_like(q)
    causal = np.tril(np.ones((s, s), bool))
    for i in range(b):
        for h in range(heads):
            j = h // rep
            a = (q[i, :, h] @ k[i, :, j].T) ** 2 / d
            a = a * np.exp(np.where(
                causal, cum[i, :, j][:, None] - cum[i, :, j][None, :], -np.inf))
            y[i, :, h] = a @ v[i, :, j] / (a.sum(-1, keepdims=True) + eps)
    return y


def _phi_minimal(u):
    """The symmetric degree-2 embedding of u / d^(1/4): entries u_a u_b for
    a <= b, weight 1 on a = b and sqrt(2) on a < b; d (d + 1) / 2 of them."""
    d = u.shape[-1]
    u = np.asarray(u, np.float64) / d ** 0.25
    a, b = np.triu_indices(d)
    return u[..., a] * u[..., b] * np.where(a == b, 1.0, np.sqrt(2.0))


def _recurrence(q, k, v, log_g, eps=EPS):
    """S_t = g_t S_{t-1} + phi(k_t) v_t^T, z_t = g_t z_{t-1} + phi(k_t),
    y_t = S_t^T phi(q_t) / (z_t . phi(q_t) + eps), one token at a time."""
    q, k, v, log_g = (np.asarray(t, np.float64) for t in (q, k, v, log_g))
    b, s, heads, d = q.shape
    groups = k.shape[2]
    rep = heads // groups
    y = np.zeros_like(q)
    for i in range(b):
        for j in range(groups):
            state = np.zeros((d * (d + 1) // 2, d))
            norm = np.zeros(d * (d + 1) // 2)
            for t in range(s):
                g = np.exp(log_g[i, t, j])
                pk = _phi_minimal(k[i, t, j])
                state = g * state + pk[:, None] * v[i, t, j][None, :]
                norm = g * norm + pk
                for h in range(j * rep, (j + 1) * rep):
                    pq = _phi_minimal(q[i, t, h])
                    y[i, t, h] = state.T @ pq / (norm @ pq + eps)
    return y


def _op(chunk):
    def run(q, k, v, log_g):
        with jax.default_matmul_precision("highest"):
            return power_retention(q, k, v, log_g, chunk, EPS)
    return jax.jit(run)


def test_the_expansion_gives_the_squared_scaled_product():
    rs = np.random.RandomState(0)
    for d in (8, 16, 128):
        q = rs.randn(7, d).astype(np.float32)
        k = rs.randn(7, d).astype(np.float32)
        got = np.einsum("if,jf->ij", np.asarray(expand_queries(q), np.float64),
                        np.asarray(expand_keys(k), np.float64))
        want = (q.astype(np.float64) @ k.astype(np.float64).T) ** 2 / d
        assert expand_keys(k).shape == (7, retention_features(d))
        # float32 entries summed in float64: the roundings of d^2 / 2
        # products, each 6e-8 of its size
        assert np.abs(got - want).max() <= 1e-5 * np.abs(want).max(), d
    # 65 rotations of 128 lanes for the 8,256 minimal entries
    assert retention_features(128) == 8320
    assert _phi_minimal(np.ones(128)).shape == (8256,)
    # and the minimal embedding the recurrence below uses is the same map
    got = _phi_minimal(q) @ _phi_minimal(k).T
    assert np.abs(got - want).max() <= 1e-12 * np.abs(want).max()
    with pytest.raises(ValueError, match="odd"):
        expand_keys(np.ones((3, 7), np.float32))


# head_dim 128: the expansions are made in the kernels; one batch row, one
# state head with its five query heads (the recurrence holds 8,256 x 128)
_D128 = dict(b=1, heads=5, groups=1, d=128)


@pytest.mark.parametrize(
    "seq, chunk, shape",
    [(32, 8, {}), (29, 8, {}), (5, 8, {}), (29, 29, {}), (40, 16, _D128),
     (21, 16, _D128)],
    ids=["whole_chunks", "ragged_tail", "under_a_chunk", "one_chunk",
         "head_dim_128_kernels", "head_dim_128_kernels_ragged_tail"])
def test_chunked_is_the_quadratic_form_is_the_recurrence(seq, chunk, shape):
    q, k, v, log_g = _draw(seq, s=seq, **shape)
    quad = _quadratic(q, k, v, log_g)
    rec = _recurrence(q, k, v, log_g)
    # float64 both: the two published forms are one function
    assert np.abs(quad - rec).max() <= 1e-9 * np.abs(quad).max()
    got = np.asarray(_op(chunk)(q, k, v, log_g))
    assert got.shape == q.shape and got.dtype == np.float32
    # float32 against float64 over at most 32 positions: 2e-5 of the
    # largest output; a decay applied one position early or late, or a
    # state read by the wrong group, is off by 10% and more
    assert np.abs(got - quad).max() <= 2e-5 * np.abs(quad).max()


def _ref_jnp(q, k, v, log_g):
    """The quadratic form again in float64 jax.numpy, for jax.grad."""
    d = q.shape[-1]
    rep = q.shape[2] // k.shape[2]
    cum = jnp.cumsum(log_g, axis=1)
    seg = cum[:, :, None, :] - cum[:, None, :, :]            # (b, t, s, g)
    s = q.shape[1]
    causal = jnp.tril(jnp.ones((s, s), bool))[None, :, :, None]
    decay = jnp.repeat(jnp.exp(jnp.where(causal, seg, -jnp.inf)), rep, -1)
    kk, vv = jnp.repeat(k, rep, 2), jnp.repeat(v, rep, 2)
    a = jnp.einsum("bthd,bshd->btsh", q, kk) ** 2 / d * decay
    return jnp.einsum("btsh,bshd->bthd", a, vv) \
        / (a.sum(2)[..., None] + EPS)


@pytest.mark.parametrize(
    "seq, chunk, shape", [(24, 8, {}), (21, 8, {}), (40, 16, _D128)],
    ids=["whole_chunks", "ragged_tail", "head_dim_128_kernels"])
def test_gradients_match_the_quadratic_forms(seq, chunk, shape):
    q, k, v, log_g = _draw(100 + seq, s=seq, **{"b": 1, **shape})
    w = np.random.RandomState(5).randn(*q.shape)

    def loss(fn):
        return lambda *a: jnp.sum(fn(*a) * w.astype(a[0].dtype))

    got = jax.jit(jax.grad(loss(_op(chunk)), argnums=(0, 1, 2, 3)))(
        q, k, v, log_g)
    with jax.enable_x64():
        want = jax.grad(loss(_ref_jnp), argnums=(0, 1, 2, 3))(
            *(jnp.asarray(t, jnp.float64) for t in (q, k, v, log_g)))
    for name, g, r in zip("q k v log_g".split(), got, want):
        g, r = np.asarray(g), np.asarray(r)
        assert np.isfinite(g).all() and np.abs(r).max() > 0, name
        # float32 against float64, relative to the gradient's own scale;
        # the log-decays' gradient is a sum over every later position of
        # the sequence and every head of the group, hence 1e-4; a carried
        # state left out of the backward is an error of order 1
        assert np.abs(g - r).max() <= 1e-4 * np.abs(r).max(), name


def test_a_gate_of_one_is_plain_normalised_power_attention():
    q, k, v, _ = _draw(1, b=1, s=20)
    log_g = np.zeros(k.shape[:3], np.float32)
    d = q.shape[-1]
    rep = q.shape[2] // k.shape[2]
    a = np.einsum("bthd,bshd->bhts", q.astype(np.float64),
                  np.repeat(k, rep, 2).astype(np.float64)) ** 2 / d
    a = a * np.tril(np.ones((20, 20)))
    want = np.einsum("bhts,bshd->bthd", a, np.repeat(v, rep, 2)) \
        / (np.moveaxis(a.sum(-1), 1, 2)[..., None] + EPS)
    got = np.asarray(_op(8)(q, k, v, log_g))
    assert np.abs(got - want).max() <= 2e-5 * np.abs(want).max()


def test_a_gate_near_zero_remembers_one_token():
    """g -> 0: every earlier position's weight vanishes and y_t = v_t of
    the head's own group, but for what eps takes: a_tt / (a_tt + eps)."""
    q, k, v, _ = _draw(2, b=1, s=20)
    log_g = np.full(k.shape[:3], -60.0, np.float32)
    got = np.asarray(_op(8)(q, k, v, log_g))
    rep = q.shape[2] // k.shape[2]
    own = np.einsum("bthd,bthd->bth", q.astype(np.float64),
                    np.repeat(k, rep, 2)) ** 2 / q.shape[-1]
    want = np.repeat(v, rep, 2) * (own / (own + EPS))[..., None]
    assert np.median(own / (own + EPS)) > 0.99999     # nearly all of v_t
    # float32 against float64 of one product and one division
    assert np.abs(got - want).max() <= 2e-5 * np.abs(want).max()


def test_bf16_operands_keep_the_decays_in_float32():
    """bf16 q, k, v with float32 log-decays, horizons up to 4,096 tokens
    over 96 positions and 4 chunks: within bf16's rounding of the float64
    form computed from the same rounded operands. Decays summed in bf16
    would lose the long horizons altogether (1 - 1/4096 rounds to 1)."""
    q, k, v, log_g = _draw(3, b=1, s=96, horizon=(16.0, 4096.0))
    qb, kb, vb = (jnp.asarray(t, jnp.bfloat16) for t in (q, k, v))
    got = jax.jit(lambda *a: power_retention(*a, 32, EPS))(qb, kb, vb, log_g)
    assert got.dtype == jnp.bfloat16
    want = _quadratic(*(np.asarray(t, np.float32) for t in (qb, kb, vb)),
                      log_g)
    err = np.linalg.norm(np.asarray(got, np.float64) - want) \
        / np.linalg.norm(want)
    # expansions, weights and the carried state each round once to 8 bits
    # (2^-9 = 0.2% an entry); measured 0.24%
    assert err < 0.01


# -- the kernels of ops/pallas/power_retention.py, under the interpreter ------

def _operands(seed, n, e, dtype, d=128):
    """u (n, d), m (features, e), w and dy (n, e) in `dtype`."""
    rs = np.random.RandomState(seed)
    return tuple(jnp.asarray(rs.randn(*shape), dtype) for shape in
                 ((n, d), (retention_features(d), e), (n, e), (n, e)))


def _rel(got, want):
    got, want = (np.asarray(t, np.float64) for t in (got, want))
    return np.linalg.norm(got - want) / np.linalg.norm(want)


@pytest.fixture
def small_tiles(monkeypatch):
    """32 rows a grid step, so that 96 rows are three whole steps and 100
    rows three and a padded fourth."""
    monkeypatch.setattr(kernels, "_ROWS", 32)


_SIDES = {"query_side": (True, expand_queries), "key_side": (False, expand_keys)}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bf16"])
@pytest.mark.parametrize("n", [96, 100], ids=["whole_tiles", "padded_tile"])
@pytest.mark.parametrize("side", list(_SIDES))
@pytest.mark.parametrize("kernel", ["read", "write", "back"])
def test_each_kernel_is_the_product_with_the_expansion(small_tiles, kernel,
                                                       side, n, dtype):
    weighted, expand = _SIDES[side]
    u, m, w, dy = _operands(n, n, 256, dtype)
    with jax.default_matmul_precision("highest"):
        if kernel == "read":
            got = kernels.read(u, m, weighted)
            want = jnp.einsum("nf,fe->ne", expand(u), m,
                              preferred_element_type=jnp.float32)
        elif kernel == "write":
            got = kernels.write(u, w, weighted)
            want = jnp.einsum("nf,ne->fe", expand(u), w,
                              preferred_element_type=jnp.float32)
        else:
            got = kernels.back(u, dy, m, weighted)
            want = jax.vjp(lambda t: jnp.einsum(
                "nf,fe->ne", expand(t), m,
                preferred_element_type=jnp.float32), u)[1](
                    dy.astype(jnp.float32))[0]
    assert got.shape == want.shape and got.dtype == want.dtype
    if kernel != "back":
        # the same expansion rounded at the same point, the same products
        # summed in float32 in another order
        assert _rel(got, want) <= 1e-6
    elif dtype == jnp.float32:
        assert _rel(got, want) <= 1e-6
    else:
        # jax.vjp rounds the expansion's cotangent and each factor's to
        # bf16 on the way back (2^-9 an entry each); the kernel keeps them
        # in float32 and rounds du once
        assert _rel(got, want) <= 8e-3


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bf16"])
@pytest.mark.parametrize("side", list(_SIDES))
@pytest.mark.parametrize("operation", ["phi_dot", "phi_t_dot"])
def test_the_differentiable_operations_are_the_einsum_forms(small_tiles,
                                                            operation, side,
                                                            dtype):
    """Values and the cotangents of BOTH operands against jax.vjp of the
    einsum over the jax.numpy expansion, 100 rows (a padded tile)."""
    weighted, expand = _SIDES[side]
    u, m, w, dy = _operands(7, 100, 128, dtype)
    if operation == "phi_dot":
        second, spec = m, "nf,fe->ne"
        fn = lambda a, b: kernels.phi_dot(a, b, weighted)
        ct = dy.astype(jnp.float32)
    else:
        second, spec = w, "nf,ne->fe"
        fn = lambda a, b: kernels.phi_t_dot(a, b, weighted)
        ct = jnp.asarray(np.random.RandomState(8).randn(
            retention_features(128), 128), jnp.float32)

    def ref(a, b):
        return jnp.einsum(spec, expand(a), b,
                          preferred_element_type=jnp.float32)

    with jax.default_matmul_precision("highest"):
        got, got_vjp = jax.vjp(fn, u, second)
        want, want_vjp = jax.vjp(ref, u, second)
        got_ct, want_ct = got_vjp(ct), want_vjp(ct)
    assert _rel(got, want) <= 1e-6
    for name, g, r in zip(("u", "second"), got_ct, want_ct):
        assert g.shape == r.shape and g.dtype == r.dtype, name
        # float32: the same sums in another order. bf16: the einsum form
        # rounds the cotangent it multiplies to bf16 as the kernels do,
        # and phi's own cotangent and each factor's besides (2^-9 an entry
        # each; measured 0.25-0.41%)
        assert _rel(g, r) <= (1e-6 if dtype == jnp.float32 else 8e-3), name


@pytest.mark.parametrize("group", [1, 4, 65], ids=["one_rotation_a_step",
                                                    "a_rotation_left_over",
                                                    "all_at_once"])
@pytest.mark.parametrize("kernel", ["read", "write", "back"])
def test_any_group_of_rotations_is_the_same_product(small_tiles, monkeypatch,
                                                    kernel, group):
    """`_GROUP` rotations go through the MXU at once; 65 = 16 x 4 + 1, so
    a group of 4 leaves one rotation for the step after the loop."""
    u, m, w, dy = _operands(3, 40, 128, jnp.float32)
    args = {"read": (u, m), "write": (u, w), "back": (u, dy, m)}[kernel]
    fn = getattr(kernels, kernel)
    with jax.default_matmul_precision("highest"):
        want = fn(*args, True)
        monkeypatch.setattr(kernels, "_GROUP", group)
        got = fn(*args, True)
    assert _rel(got, want) <= 1e-6


@pytest.mark.parametrize("n, tile", [(5120, 2560), (1024, 1024), (6144, 2048),
                                     (100, 112), (2561, 1296)])
def test_row_tiles_spread_the_rows_evenly(n, tile):
    """The fewest grid steps of at most 2,560 rows, the rows spread evenly
    over them in multiples of 16: the Brumby cell's 5,120 query rows are
    two steps and its 1,024 key rows one, and an odd count pads a few rows,
    not most of a tile."""
    assert kernels._row_tile(n) == tile
    assert tile % 16 == 0 and tile <= kernels._ROWS
    assert -(-n // tile) == -(-n // kernels._ROWS)


def test_kernels_refuse_what_is_no_lane_rotation():
    u, m, _, _ = _operands(0, 16, 128, jnp.float32, d=64)
    with pytest.raises(ValueError, match="multiple of 128"):
        kernels.read(u, m, True)


@pytest.mark.parametrize("d, in_kernels", [(16, False), (128, True)],
                         ids=["head_dim_16_jax_numpy", "head_dim_128_kernels"])
def test_the_head_dim_alone_chooses_the_path(d, in_kernels):
    """head_dim % 128 decides, and nothing else: the traced op holds the
    three kernels (forward and backward) or none, and either way it is the
    quadratic form."""
    q, k, v, log_g = _draw(11, b=1, s=24, heads=5, groups=1, d=d)

    def loss(*a):
        return jnp.sum(power_retention(*a, 8, EPS))

    text = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(q, k, v, log_g))
    for name in ("retn_read", "retn_write", "retn_back"):
        assert (name in text) == in_kernels, name
    assert op._in_vmem(d) == in_kernels
    got = np.asarray(_op(8)(q, k, v, log_g))
    quad = _quadratic(q, k, v, log_g)
    assert np.abs(got - quad).max() <= 2e-5 * np.abs(quad).max()


def test_bf16_kernels_round_where_the_expansion_rounds():
    """bf16 at head_dim 128, four chunks, five query heads a state head:
    the op through the kernels against the op through the jax.numpy
    expansion (the path every other head_dim takes), which rounds at the
    same points: they differ by the order of float32 sums alone."""
    q, k, v, log_g = _draw(4, b=1, s=64, heads=5, groups=1, d=128,
                           horizon=(16.0, 4096.0))
    qb, kb, vb = (jnp.asarray(t, jnp.bfloat16) for t in (q, k, v))
    run = jax.jit(lambda *a: power_retention(*a, 16, EPS))
    got = run(qb, kb, vb, log_g)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(op, "_in_vmem", lambda d: False)
        plain = jax.jit(lambda *a: power_retention(*a, 16, EPS))(
            qb, kb, vb, log_g)
    assert got.dtype == jnp.bfloat16
    # one bf16 rounding of y where a float32 sum fell on the other side
    # of a rounding boundary: 2^-8 of an entry at most, and few of them
    assert _rel(got, plain) <= 1e-3
    want = _quadratic(*(np.asarray(t, np.float32) for t in (qb, kb, vb)),
                      log_g)
    # as test_bf16_operands_keep_the_decays_in_float32: measured 0.24%
    assert _rel(got, want) < 0.01
