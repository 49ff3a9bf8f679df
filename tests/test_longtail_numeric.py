"""Numeric-vs-NumPy checks for long-tail tensor ops (VERDICT r3 #5).

Every name here previously appeared in COVERAGE_GAP.md (existence-only:
resolved by the surface gate's hasattr but never behaviorally exercised).
reference: test/legacy_test/op_test.py numeric-compare pattern.
"""

import numpy as np
import pytest

import paddle_tpu as paddle

rs = np.random.RandomState(7)


def T(a, **kw):
    return paddle.Tensor(np.asarray(a), **kw)


# --------------------------------------------------------------------------
# in-place twins: fn_(x) must equal fn(x) and rebind x itself
# --------------------------------------------------------------------------

INPLACE_UNARY = [
    # (name, domain_lo, domain_hi)
    ("acos_", -0.8, 0.8), ("atan_", -1, 1), ("cos_", -1, 1),
    ("sin_", -1, 1), ("sinh_", -1, 1), ("tan_", -0.5, 0.5),
    ("erf_", -1, 1), ("expm1_", -1, 1), ("log_", 0.5, 2.0),
    ("log2_", 0.5, 2.0), ("log10_", 0.5, 2.0), ("lgamma_", 2.0, 4.0),
    ("digamma_", 2.0, 4.0), ("gammaln_", 2.0, 4.0), ("frac_", 0.2, 0.8),
    ("i0_", -1, 1), ("neg_", -1, 1), ("reshape_", -1, 1),
    ("squeeze_", -1, 1), ("unsqueeze_", -1, 1), ("flatten_", -1, 1),
    ("tril_", -1, 1), ("triu_", -1, 1), ("t_", -1, 1),
    ("transpose_", -1, 1), ("trunc_", 0.2, 0.8), ("nan_to_num_", -1, 1),
    ("logit_", 0.2, 0.8), ("sinc_", 0.3, 0.9),
]

_IN_ARGS = {  # extra args for the non-nullary twins
    "reshape_": ([16],), "squeeze_": (), "unsqueeze_": (0,),
    "flatten_": (), "t_": (), "transpose_": ([1, 0],),
}


@pytest.mark.parametrize("name,lo,hi", INPLACE_UNARY,
                         ids=[n for n, _, _ in INPLACE_UNARY])
def test_inplace_twin_matches_outofplace(name, lo, hi):
    base = rs.uniform(lo, hi, (4, 4)).astype(np.float32)
    args = _IN_ARGS.get(name, ())
    x = T(base.copy())
    ref = getattr(paddle, name[:-1])(T(base.copy()), *args)
    ret = getattr(x, name)(*args)
    assert ret is x, f"{name} must rebind self"
    np.testing.assert_allclose(x.numpy(), ref.numpy(), rtol=1e-5,
                               atol=1e-6, err_msg=name)


INPLACE_BINARY = [
    ("multiply_", 0.5, 2.0), ("divide_", 0.5, 2.0), ("pow_", 0.5, 2.0),
    ("mod_", 0.5, 2.0), ("remainder_", 0.5, 2.0),
    ("floor_divide_", 1.0, 3.0), ("floor_mod_", 0.5, 2.0),
    ("copysign_", 0.5, 2.0), ("hypot_", 0.5, 2.0),
    ("gammainc_", 0.5, 2.0), ("gammaincc_", 0.5, 2.0),
    ("multigammaln_", 3.0, 5.0), ("nanquantile", 0.0, 1.0),
]


@pytest.mark.parametrize(
    "name,lo,hi",
    [s for s in INPLACE_BINARY if s[0].endswith("_")],
    ids=[n for n, _, _ in INPLACE_BINARY if n.endswith("_")])
def test_inplace_binary_twin(name, lo, hi):
    a = rs.uniform(lo, hi, (3, 4)).astype(np.float32)
    b = rs.uniform(lo, hi, (3, 4)).astype(np.float32)
    if name == "multigammaln_":
        other = 2  # integer order p
    else:
        other = T(b)
    x = T(a.copy())
    ref = getattr(paddle, name[:-1])(T(a.copy()), other)
    ret = getattr(x, name)(other)
    assert ret is x
    np.testing.assert_allclose(x.numpy(), ref.numpy(), rtol=1e-5,
                               atol=1e-6, err_msg=name)


def test_inplace_index_and_mask_twins():
    idx = T(np.array([0, 2], np.int64))
    u = rs.randn(2, 4).astype(np.float32)
    base = rs.randn(3, 4).astype(np.float32)
    x = T(base.copy())
    x.index_add_(idx, 0, T(u))
    ref = base.copy()
    ref[[0, 2]] += u
    np.testing.assert_allclose(x.numpy(), ref, rtol=1e-5)

    x = T(base.copy())
    x.index_fill_(idx, 0, 9.0)
    ref = base.copy()
    ref[[0, 2]] = 9.0
    np.testing.assert_allclose(x.numpy(), ref)

    m = np.array([[True, False, True, False]] * 3)
    x = T(base.copy())
    x.masked_fill_(T(m), 0.5)
    ref = np.where(m, 0.5, base)
    np.testing.assert_allclose(x.numpy(), ref)

    x = T(base.copy())
    vals = np.arange(1, 7, dtype=np.float32)
    x.masked_scatter_(T(m), T(vals))
    ref = base.copy()
    ref[m] = vals[:m.sum()]
    np.testing.assert_allclose(x.numpy(), ref)

    x = T(base.copy())
    x.scatter_(T(np.array([1], np.int64)), T(np.full((1, 4), 7.0,
                                                     np.float32)))
    ref = base.copy()
    ref[1] = 7.0
    np.testing.assert_allclose(x.numpy(), ref)

    x = T(base.copy())
    x.index_put_((T(np.array([0], np.int64)), T(np.array([1], np.int64))),
                 T(np.array([42.0], np.float32)))
    ref = base.copy()
    ref[0, 1] = 42.0
    np.testing.assert_allclose(x.numpy(), ref)


def test_inplace_random_twins_change_values_keep_shape():
    """bernoulli_/cauchy_/geometric_/log_normal_/normal_ fill in place;
    statistical sanity instead of bitwise compare."""
    paddle.seed(11)
    x = T(np.zeros((400,), np.float32))
    x.normal_(mean=2.0, std=0.5)
    assert abs(float(x.numpy().mean()) - 2.0) < 0.15
    x.bernoulli_(p=0.3)
    vals = set(np.unique(x.numpy()).tolist())
    assert vals.issubset({0.0, 1.0})
    assert 0.1 < x.numpy().mean() < 0.5
    x.log_normal_(mean=0.0, std=0.25)
    assert (x.numpy() > 0).all()  # lognormal support
    x.geometric_(0.5)
    assert (x.numpy() >= 1).all() or (x.numpy() >= 0).all()
    x.cauchy_()
    assert np.isfinite(np.median(x.numpy()))
    x.exponential_(1.0)
    assert (x.numpy() >= 0).all()


# --------------------------------------------------------------------------
# logical / bitwise / comparison families vs numpy
# --------------------------------------------------------------------------

def _bits():
    return (rs.randint(0, 16, (3, 4)).astype(np.int32),
            rs.randint(0, 16, (3, 4)).astype(np.int32))


BITWISE = [
    ("bitwise_and", np.bitwise_and), ("bitwise_or", np.bitwise_or),
    ("bitwise_xor", np.bitwise_xor),
    ("bitwise_left_shift", np.left_shift),
    ("bitwise_right_shift", np.right_shift),
]


@pytest.mark.parametrize("name,ref", BITWISE, ids=[n for n, _ in BITWISE])
def test_bitwise_vs_numpy(name, ref):
    a, b = _bits()
    if "shift" in name:
        b = (b % 4).astype(np.int32)
    got = getattr(paddle, name)(T(a), T(b)).numpy()
    np.testing.assert_array_equal(got, ref(a, b))
    # in-place twin
    x = T(a.copy())
    assert getattr(x, name + "_")(T(b)) is x
    np.testing.assert_array_equal(x.numpy(), ref(a, b))


def test_bitwise_not():
    a, _ = _bits()
    np.testing.assert_array_equal(paddle.bitwise_not(T(a)).numpy(),
                                  np.invert(a))
    x = T(a.copy())
    x.bitwise_not_()
    np.testing.assert_array_equal(x.numpy(), np.invert(a))


LOGICAL = [
    ("logical_and", np.logical_and), ("logical_or", np.logical_or),
    ("logical_xor", np.logical_xor),
]


@pytest.mark.parametrize("name,ref", LOGICAL, ids=[n for n, _ in LOGICAL])
def test_logical_vs_numpy(name, ref):
    a = rs.rand(3, 4) > 0.5
    b = rs.rand(3, 4) > 0.5
    np.testing.assert_array_equal(
        getattr(paddle, name)(T(a), T(b)).numpy(), ref(a, b))
    x = T(a.copy())
    assert getattr(x, name + "_")(T(b)) is x
    np.testing.assert_array_equal(x.numpy(), ref(a, b))


def test_logical_not():
    a = rs.rand(3, 4) > 0.5
    np.testing.assert_array_equal(paddle.logical_not(T(a)).numpy(), ~a)
    x = T(a.copy())
    x.logical_not_()
    np.testing.assert_array_equal(x.numpy(), ~a)


COMPARE = [
    ("greater_than", np.greater), ("greater_equal", np.greater_equal),
    ("less_than", np.less), ("less_equal", np.less_equal),
    ("not_equal", np.not_equal), ("equal", np.equal),
]


@pytest.mark.parametrize("name,ref", COMPARE, ids=[n for n, _ in COMPARE])
def test_compare_vs_numpy(name, ref):
    a = rs.randint(0, 3, (4, 5)).astype(np.float32)
    b = rs.randint(0, 3, (4, 5)).astype(np.float32)
    np.testing.assert_array_equal(
        getattr(paddle, name)(T(a), T(b)).numpy(), ref(a, b))
    # the generated in-place comparison twin casts back onto x
    x = T(a.copy())
    assert getattr(x, name + "_")(T(b)) is x
    np.testing.assert_array_equal(x.numpy().astype(bool), ref(a, b))


def test_equal_all_and_is_empty_and_numel():
    a = rs.randn(3, 4).astype(np.float32)
    assert bool(paddle.equal_all(T(a), T(a.copy())))
    assert not bool(paddle.equal_all(T(a), T(a + 1)))
    assert int(paddle.numel(T(a))) == 12
    assert bool(paddle.is_empty(T(np.zeros((0, 4), np.float32))))
    assert not bool(paddle.is_empty(T(a)))


# --------------------------------------------------------------------------
# stack / split family vs numpy
# --------------------------------------------------------------------------

STACKS = [
    ("hstack", np.hstack), ("vstack", np.vstack), ("dstack", np.dstack),
    ("column_stack", np.column_stack), ("row_stack", np.vstack),
]


@pytest.mark.parametrize("name,ref", STACKS, ids=[n for n, _ in STACKS])
def test_stack_family(name, ref):
    a = rs.randn(3, 4).astype(np.float32)
    b = rs.randn(3, 4).astype(np.float32)
    np.testing.assert_allclose(
        getattr(paddle, name)([T(a), T(b)]).numpy(), ref([a, b]))


SPLITS = [
    ("hsplit", np.hsplit, (4, 6), 2), ("vsplit", np.vsplit, (4, 6), 2),
    ("dsplit", np.dsplit, (2, 3, 4), 2),
]


@pytest.mark.parametrize("name,ref,shape,n", SPLITS,
                         ids=[s[0] for s in SPLITS])
def test_split_family(name, ref, shape, n):
    a = rs.randn(*shape).astype(np.float32)
    got = getattr(paddle, name)(T(a), n)
    want = ref(a, n)
    assert len(got) == len(want)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g.numpy(), w)


def test_tensor_split_uneven():
    a = rs.randn(7, 2).astype(np.float32)
    got = paddle.tensor_split(T(a), 3)
    want = np.array_split(a, 3)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g.numpy(), w)
    got = paddle.tensor_split(T(a), [2, 5])
    want = np.split(a, [2, 5])
    for g, w in zip(got, want):
        np.testing.assert_allclose(g.numpy(), w)


def test_atleast_family():
    s = T(np.float32(3.0))
    v = T(np.array([1.0, 2.0], np.float32))
    m = T(rs.randn(2, 2).astype(np.float32))
    assert list(paddle.atleast_1d(s).shape) == [1]
    assert list(paddle.atleast_2d(v).shape) == [1, 2]
    assert list(paddle.atleast_3d(m).shape) == [1, 2, 2] or \
        list(paddle.atleast_3d(m).shape) == [2, 2, 1]
    # numpy parity for the 3d promotion of a matrix
    np.testing.assert_allclose(paddle.atleast_3d(m).numpy(),
                               np.atleast_3d(m.numpy()))
    outs = paddle.atleast_1d(s, v)
    assert isinstance(outs, (list, tuple)) and len(outs) == 2


# --------------------------------------------------------------------------
# integer / numeric utility ops vs numpy
# --------------------------------------------------------------------------

def test_gcd_lcm():
    a = rs.randint(1, 40, (3, 4)).astype(np.int32)
    b = rs.randint(1, 40, (3, 4)).astype(np.int32)
    np.testing.assert_array_equal(paddle.gcd(T(a), T(b)).numpy(),
                                  np.gcd(a, b))
    np.testing.assert_array_equal(paddle.lcm(T(a), T(b)).numpy(),
                                  np.lcm(a, b))
    x = T(a.copy())
    x.gcd_(T(b))
    np.testing.assert_array_equal(x.numpy(), np.gcd(a, b))
    x = T(a.copy())
    x.lcm_(T(b))
    np.testing.assert_array_equal(x.numpy(), np.lcm(a, b))


def test_ldexp_frexp_nextafter():
    a = rs.uniform(0.5, 2.0, (3, 4)).astype(np.float32)
    e = rs.randint(-3, 4, (3, 4)).astype(np.int32)
    np.testing.assert_allclose(paddle.ldexp(T(a), T(e)).numpy(),
                               np.ldexp(a, e), rtol=1e-6)
    m, ex = paddle.frexp(T(a))
    rm, rex = np.frexp(a)
    np.testing.assert_allclose(m.numpy(), rm, rtol=1e-6)
    np.testing.assert_array_equal(ex.numpy().astype(np.int32), rex)
    b = a + 1.0
    np.testing.assert_array_equal(paddle.nextafter(T(a), T(b)).numpy(),
                                  np.nextafter(a, b))
    x = T(a.copy())
    x.ldexp_(T(e))
    np.testing.assert_allclose(x.numpy(), np.ldexp(a, e), rtol=1e-6)


def test_histogram_family():
    a = rs.uniform(0, 10, (100,)).astype(np.float32)
    got = paddle.histogram(T(a), bins=5, min=0, max=10).numpy()
    want, _ = np.histogram(a, bins=5, range=(0, 10))
    np.testing.assert_array_equal(got, want)
    edges = paddle.histogram_bin_edges(T(a), bins=5, min=0, max=10).numpy()
    np.testing.assert_allclose(edges, np.histogram_bin_edges(
        a, bins=5, range=(0, 10)), rtol=1e-6)
    pts = rs.uniform(0, 1, (50, 2)).astype(np.float32)
    hist, e = paddle.histogramdd(T(pts), bins=[3, 3],
                                 ranges=[0.0, 1.0, 0.0, 1.0])
    ref, re_ = np.histogramdd(pts, bins=[3, 3],
                              range=[(0, 1), (0, 1)])
    np.testing.assert_allclose(hist.numpy(), ref)


def test_searchsorted_bucketize():
    edges = np.array([1.0, 3.0, 5.0, 7.0], np.float32)
    x = np.array([[0.5, 3.0], [6.9, 9.0]], np.float32)
    np.testing.assert_array_equal(
        paddle.searchsorted(T(edges), T(x)).numpy(),
        np.searchsorted(edges, x, side="left"))
    np.testing.assert_array_equal(
        paddle.searchsorted(T(edges), T(x), right=True).numpy(),
        np.searchsorted(edges, x, side="right"))
    np.testing.assert_array_equal(
        paddle.bucketize(T(x), T(edges)).numpy(),
        np.searchsorted(edges, x, side="left"))


def test_count_nonzero_argmin():
    a = np.array([[0.0, 1.0, 0.0], [2.0, 0.0, 3.0]], np.float32)
    assert int(paddle.count_nonzero(T(a))) == 3
    np.testing.assert_array_equal(
        paddle.count_nonzero(T(a), axis=1).numpy(),
        np.count_nonzero(a, axis=1))
    np.testing.assert_array_equal(paddle.argmin(T(a), axis=1).numpy(),
                                  np.argmin(a, axis=1))


def test_isinf_isneginf_isposinf_isreal():
    a = np.array([1.0, np.inf, -np.inf, np.nan], np.float32)
    np.testing.assert_array_equal(paddle.isinf(T(a)).numpy(), np.isinf(a))
    np.testing.assert_array_equal(paddle.isneginf(T(a)).numpy(),
                                  np.isneginf(a))
    np.testing.assert_array_equal(paddle.isposinf(T(a)).numpy(),
                                  np.isposinf(a))
    assert paddle.isreal(T(a)).numpy().all()
    c = np.array([1 + 0j, 1 + 2j], np.complex64)
    np.testing.assert_array_equal(paddle.isreal(T(c)).numpy(),
                                  np.isreal(c))


def test_dtype_predicates():
    f = T(np.ones((2,), np.float32))
    i = T(np.ones((2,), np.int32))
    c = T(np.ones((2,), np.complex64))
    assert paddle.is_floating_point(f) and not paddle.is_floating_point(i)
    assert paddle.is_integer(i) and not paddle.is_integer(f)
    assert paddle.is_complex(c) and not paddle.is_complex(f)
    assert paddle.is_tensor(f) and not paddle.is_tensor(np.ones(2))


# --------------------------------------------------------------------------
# complex family
# --------------------------------------------------------------------------

def test_complex_build_and_views():
    re = rs.randn(3, 4).astype(np.float32)
    im = rs.randn(3, 4).astype(np.float32)
    c = paddle.complex(T(re), T(im))
    np.testing.assert_allclose(c.numpy(), re + 1j * im, rtol=1e-6)
    np.testing.assert_allclose(paddle.real(c).numpy(), re)
    np.testing.assert_allclose(paddle.imag(c).numpy(), im)
    np.testing.assert_allclose(paddle.conj(c).numpy(), re - 1j * im,
                               rtol=1e-6)
    np.testing.assert_allclose(paddle.angle(c).numpy(),
                               np.angle(re + 1j * im), rtol=1e-5,
                               atol=1e-6)
    # as_real: (...,) complex -> (..., 2) float; as_complex inverts
    r2 = paddle.as_real(c)
    assert list(r2.shape) == [3, 4, 2]
    np.testing.assert_allclose(r2.numpy()[..., 0], re)
    back = paddle.as_complex(r2)
    np.testing.assert_allclose(back.numpy(), c.numpy())


def test_polar():
    mag = rs.uniform(0.5, 2.0, (3,)).astype(np.float32)
    ang = rs.uniform(-3, 3, (3,)).astype(np.float32)
    got = paddle.polar(T(mag), T(ang)).numpy()
    np.testing.assert_allclose(got, mag * np.exp(1j * ang), rtol=1e-5)


# --------------------------------------------------------------------------
# gather/scatter-nd, index_sample, multiplex, shard_index
# --------------------------------------------------------------------------

def test_gather_nd_scatter_nd():
    a = rs.randn(3, 4, 5).astype(np.float32)
    idx = np.array([[0, 1], [2, 3]], np.int64)
    np.testing.assert_allclose(paddle.gather_nd(T(a), T(idx)).numpy(),
                               a[[0, 2], [1, 3]])
    # scatter_nd: build (6,) from updates at given flat indices
    sidx = np.array([[1], [3]], np.int64)
    upd = np.array([9.0, 10.0], np.float32)
    got = paddle.scatter_nd(T(sidx), T(upd), [6]).numpy()
    want = np.zeros(6, np.float32)
    want[[1, 3]] = upd
    np.testing.assert_allclose(got, want)
    base = rs.randn(6).astype(np.float32)
    got = paddle.scatter_nd_add(T(base), T(sidx), T(upd)).numpy()
    want = base.copy()
    want[[1, 3]] += upd
    np.testing.assert_allclose(got, want, rtol=1e-6)


def test_index_sample_and_multiplex():
    x = rs.randn(3, 5).astype(np.float32)
    idx = rs.randint(0, 5, (3, 2)).astype(np.int64)
    got = paddle.index_sample(T(x), T(idx)).numpy()
    np.testing.assert_allclose(got, np.take_along_axis(x, idx, 1))
    ins = [rs.randn(4, 3).astype(np.float32) for _ in range(3)]
    sel = np.array([0, 2, 1, 0], np.int32)
    got = paddle.multiplex([T(v) for v in ins], T(sel)).numpy()
    want = np.stack([ins[s][i] for i, s in enumerate(sel)])
    np.testing.assert_allclose(got, want)


def test_shard_index():
    lab = np.array([[1], [6], [11], [15]], np.int64)
    # 16 ids, 2 shards, shard 0 keeps [0,8)
    got = paddle.shard_index(T(lab), index_num=16, nshards=2, shard_id=0,
                             ignore_value=-1).numpy()
    np.testing.assert_array_equal(got, [[1], [6], [-1], [-1]])


def test_masked_select_and_select_scatter():
    a = rs.randn(3, 4).astype(np.float32)
    m = a > 0
    np.testing.assert_allclose(paddle.masked_select(T(a), T(m)).numpy(),
                               a[m])
    u = np.full((4,), 5.0, np.float32)
    got = paddle.select_scatter(T(a.copy()), T(u), 0, 1).numpy()
    want = a.copy()
    want[1] = 5.0
    np.testing.assert_allclose(got, want)


def test_strided_slice():
    a = rs.randn(6, 8).astype(np.float32)
    got = paddle.strided_slice(T(a), axes=[0, 1], starts=[1, 0],
                               ends=[5, 8], strides=[2, 3]).numpy()
    np.testing.assert_allclose(got, a[1:5:2, 0:8:3])


def test_unflatten_and_view_as():
    a = rs.randn(2, 12).astype(np.float32)
    got = paddle.unflatten(T(a), 1, [3, 4])
    assert list(got.shape) == [2, 3, 4]
    np.testing.assert_allclose(got.numpy(), a.reshape(2, 3, 4))
    other = T(np.zeros((4, 6), np.float32))
    np.testing.assert_allclose(paddle.view_as(T(a), other).numpy(),
                               a.reshape(4, 6))


def test_unique_consecutive():
    a = np.array([1, 1, 2, 2, 2, 3, 1, 1], np.int64)
    out, inverse, counts = paddle.unique_consecutive(
        T(a), return_inverse=True, return_counts=True)
    np.testing.assert_array_equal(out.numpy(), [1, 2, 3, 1])
    np.testing.assert_array_equal(counts.numpy(), [2, 3, 1, 2])
    np.testing.assert_array_equal(out.numpy()[inverse.numpy()], a)


# --------------------------------------------------------------------------
# creation / shape utilities
# --------------------------------------------------------------------------

def test_creation_like_family():
    a = rs.randn(3, 4).astype(np.float32)
    e = paddle.empty_like(T(a))
    assert list(e.shape) == [3, 4] and e.dtype == paddle.float32
    f = paddle.full_like(T(a), 2.5)
    np.testing.assert_allclose(f.numpy(), np.full((3, 4), 2.5))
    paddle.seed(5)
    r = paddle.randint_like(T(a), 0, 10)
    arr = r.numpy()
    assert arr.shape == (3, 4) and (arr >= 0).all() and (arr < 10).all()


def test_logspace_meshgrid_broadcast():
    np.testing.assert_allclose(
        paddle.logspace(0, 3, 4).numpy(), np.logspace(0, 3, 4), rtol=1e-5)
    xs, ys = paddle.meshgrid(T(np.arange(3, dtype=np.float32)),
                             T(np.arange(2, dtype=np.float32)))
    rx, ry = np.meshgrid(np.arange(3), np.arange(2), indexing="ij")
    np.testing.assert_allclose(xs.numpy(), rx)
    np.testing.assert_allclose(ys.numpy(), ry)
    assert paddle.broadcast_shape([3, 1, 4], [2, 4]) == [3, 2, 4]
    outs = paddle.broadcast_tensors([T(np.zeros((3, 1), np.float32)),
                                     T(np.zeros((1, 4), np.float32))])
    assert all(list(o.shape) == [3, 4] for o in outs)


def test_expand_as_clone_assign_increment():
    a = rs.randn(1, 4).astype(np.float32)
    tgt = T(np.zeros((3, 4), np.float32))
    np.testing.assert_allclose(paddle.expand_as(T(a), tgt).numpy(),
                               np.broadcast_to(a, (3, 4)))
    x = T(a.copy(), stop_gradient=False)
    c = paddle.clone(x)
    np.testing.assert_allclose(c.numpy(), a)
    assert c is not x
    # clone participates in autograd
    (c.sum()).backward()
    assert x.grad is not None
    y = paddle.assign(T(a))
    np.testing.assert_allclose(y.numpy(), a)
    z = T(np.array([1.0], np.float32))
    out = paddle.increment(z, 2.0)
    np.testing.assert_allclose(out.numpy(), [3.0])


def test_tril_triu_indices():
    got = paddle.tril_indices(3, 3, 0).numpy()
    want = np.vstack(np.tril_indices(3, 0, 3))
    np.testing.assert_array_equal(got, want)
    got = paddle.triu_indices(3, 3, 0).numpy()
    want = np.vstack(np.triu_indices(3, 0, 3))
    np.testing.assert_array_equal(got, want)


def test_cast_inplace_and_equal_twin():
    x = T(np.array([1.9, 2.1], np.float32))
    x.cast_("int32")
    assert x.dtype == paddle.int32
    np.testing.assert_array_equal(x.numpy(), [1, 2])


# --------------------------------------------------------------------------
# special functions
# --------------------------------------------------------------------------

def test_gammainc_gammaincc_multigammaln():
    from scipy import special as sp
    a = rs.uniform(0.5, 3.0, (3, 4)).astype(np.float32)
    x = rs.uniform(0.5, 3.0, (3, 4)).astype(np.float32)
    np.testing.assert_allclose(paddle.gammainc(T(a), T(x)).numpy(),
                               sp.gammainc(a, x), rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(paddle.gammaincc(T(a), T(x)).numpy(),
                               sp.gammaincc(a, x), rtol=1e-4, atol=1e-5)
    v = rs.uniform(2.5, 5.0, (4,)).astype(np.float32)
    np.testing.assert_allclose(paddle.multigammaln(T(v), 2).numpy(),
                               sp.multigammaln(v[:, None], 2).ravel()
                               if v.ndim else sp.multigammaln(v, 2),
                               rtol=1e-4)


def test_polygamma_orders():
    from scipy import special as sp
    x = rs.uniform(1.5, 4.0, (5,)).astype(np.float32)
    for n in (0, 1, 2):
        np.testing.assert_allclose(paddle.polygamma(T(x), n).numpy(),
                                   sp.polygamma(n, x).astype(np.float32),
                                   rtol=1e-3, atol=1e-4)


def test_binomial_standard_gamma_sampling():
    paddle.seed(3)
    cnt = T(np.full((2000,), 10.0, np.float32))
    p = T(np.full((2000,), 0.3, np.float32))
    draws = paddle.binomial(cnt, p).numpy()
    assert draws.min() >= 0 and draws.max() <= 10
    assert abs(draws.mean() - 3.0) < 0.3
    g = paddle.standard_gamma(T(np.full((2000,), 2.0, np.float32))).numpy()
    assert (g > 0).all() and abs(g.mean() - 2.0) < 0.3
    n = paddle.standard_normal([2000]).numpy()
    assert abs(n.mean()) < 0.15 and abs(n.std() - 1.0) < 0.15
    nm = paddle.normal(mean=1.0, std=2.0, shape=[2000]).numpy()
    assert abs(nm.mean() - 1.0) < 0.3
    ln = paddle.log_normal(mean=0.0, std=0.5, shape=[2000]).numpy()
    assert (ln > 0).all()


# --------------------------------------------------------------------------
# global mode/flag helpers
# --------------------------------------------------------------------------

def test_default_dtype_roundtrip():
    old = paddle.get_default_dtype()
    try:
        # float64 is gated off by jax's no-x64 default on TPU; exercise the
        # roundtrip with a dtype the backend honors
        paddle.set_default_dtype("float16")
        assert "float16" in str(paddle.get_default_dtype())
        x = paddle.ones([2])
        assert x.dtype == paddle.float16
    finally:
        paddle.set_default_dtype(old)


def test_grad_enabled_toggles():
    assert paddle.is_grad_enabled()
    with paddle.set_grad_enabled(False):
        assert not paddle.is_grad_enabled()
        with paddle.enable_grad():
            assert paddle.is_grad_enabled()
    assert paddle.is_grad_enabled()


def test_static_mode_toggle_and_rng_state():
    assert paddle.in_dynamic_mode()
    paddle.enable_static()
    try:
        assert not paddle.in_dynamic_mode()
    finally:
        paddle.disable_static()
    assert paddle.in_dynamic_mode()
    st = paddle.get_rng_state()
    a = paddle.randn([4]).numpy()
    paddle.set_rng_state(st)
    b = paddle.randn([4]).numpy()
    np.testing.assert_array_equal(a, b)
    # cuda rng state: no-op aliases on TPU/CPU builds, must not crash
    paddle.set_cuda_rng_state(paddle.get_cuda_rng_state())


def test_flags_and_printoptions_and_signal():
    old = paddle.get_flags(["FLAGS_check_nan_inf"])
    assert "FLAGS_check_nan_inf" in old
    paddle.set_printoptions(precision=4)
    paddle.disable_signal_handler()  # must be callable
    paddle.check_shape([2, 2])
    with pytest.raises(ValueError):
        paddle.check_shape([2, -3])


def test_places_construct():
    assert "cpu" in str(paddle.CPUPlace()).lower()
    paddle.CUDAPlace(0)
    paddle.CUDAPinnedPlace()


def test_lazy_guard_puts_the_draw_off_until_initialize():
    from paddle_tpu import LazyGuard
    import paddle_tpu.nn as nn
    with LazyGuard():
        lin = nn.Linear(3, 2)
    assert not np.asarray(lin.weight._data).any()       # zeros, no draw
    y = lin(T(rs.randn(2, 3).astype(np.float32)))
    assert list(y.shape) == [2, 2]
    lin.weight.initialize()
    assert np.asarray(lin.weight._data).any()
    drawn = np.asarray(lin.weight._data).copy()
    lin.weight.initialize()                             # once only
    assert (np.asarray(lin.weight._data) == drawn).all()
    assert np.asarray(nn.Linear(3, 2).weight._data).any()   # guard is off
