"""ops/gelu_once.py: exact GELU whose value and derivative are made together.

Differentiated or not, its value is the float32 GELU rounded to the input's
type once; its gradient is the cotangent times a derivative kept in that
type. What the compiler makes of it for a TPU is
tests/test_flash_mosaic_compile.py's to hold; here, the numbers.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.framework.core import execute
from paddle_tpu.models import gpt as gpt_mod
from paddle_tpu.nn import functional as F
from paddle_tpu.ops.gelu_once import gelu_once

COMPILED = pytest.mark.parametrize("compiled", [False, True],
                                   ids=["eager", "jit"])


def _inputs(dtype, n=1 << 14):
    x = 3.0 * jax.random.normal(jax.random.PRNGKey(0), (n,), jnp.float32)
    ct = jax.random.normal(jax.random.PRNGKey(1), (n,), jnp.float32)
    return x.astype(dtype), ct.astype(dtype)


def _vjp(f, x, ct):
    out, pull = jax.vjp(f, x)
    return out, pull(ct)[0]


def _f32(a):
    return np.asarray(a).astype(np.float32)


def test_float32_value_and_gradient_are_jax_nn_gelu():
    x, ct = _inputs(jnp.float32)
    got, dgot = _vjp(gelu_once, x, ct)
    want, dwant = _vjp(lambda a: jax.nn.gelu(a, approximate=False), x, ct)
    # cotangents reach 4: a few float32 ulps of the product. The value: a
    # float32 ulp of 0.5 (1 + erf), 6e-8, times an input that reaches 12,
    # which only shows far out in the negative tail
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=2e-6)
    np.testing.assert_allclose(np.asarray(dgot), np.asarray(dwant),
                               rtol=1e-6, atol=2e-6)


@COMPILED
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_differentiated_or_not_the_value_is_the_same_bits(dtype, compiled):
    """custom_vjp's contract: the forward rule returns what the primal body
    returns. A `no_grad` evaluation or `generate` of a GPT then computes
    the activation its training step trained with, bit for bit."""
    x, _ = _inputs(dtype)
    primal = gelu_once
    forward = lambda a: jax.vjp(gelu_once, a)[0]  # noqa: E731
    if compiled:
        primal, forward = jax.jit(primal), jax.jit(forward)
    got, want = forward(x), primal(x)
    assert got.dtype == want.dtype == dtype
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@COMPILED
def test_bf16_value_is_the_float32_gelu_rounded_once(compiled):
    """Half a bf16 ulp (2^-8 at most, relative) from the float32 function
    of the same bf16 input, plus what 0.5 (1 + erf) loses far out in the
    negative tail (6e-8 |x|, absolute); jax.nn.gelu on a bf16 input rounds
    three times on the way and lands further off."""
    x, _ = _inputs(jnp.bfloat16)
    want = _f32(jax.nn.gelu(x.astype(jnp.float32), approximate=False))
    chain = _f32(jax.nn.gelu(x, approximate=False))
    got = (jax.jit(gelu_once) if compiled else gelu_once)(x)
    assert got.dtype == jnp.bfloat16
    err = np.abs(_f32(got) - want)
    assert np.all(err <= 2.0 ** -8 * np.abs(want) + 2e-6)
    assert err.max() <= np.abs(chain - want).max()
    assert err.mean() <= np.abs(chain - want).mean()
    # against what F.gelu computes: a bf16 ulp or two of the input's size
    assert np.all(np.abs(_f32(got) - chain) <= 2.0 ** -7 * np.abs(_f32(x)))


def test_bf16_gradient_within_one_ulp_of_the_float32_derivative():
    """The derivative is rounded to bf16 once and the product once more:
    one bf16 ulp (2^-7 at most, relative) of the float32 derivative times
    the cotangent. Where the derivative crosses zero (x near -0.75) that
    ulp is of the cotangent, not of the product (a derivative of 0.0008
    is the difference of two terms of 0.23). The worst element is no
    further off than autodiff of jax.nn.gelu on the bf16 input, which
    rounds x / sqrt 2 before erfc; the mean is a little further (the
    product of two rounded factors), by less than half."""
    x, ct = _inputs(jnp.bfloat16)
    _, got = _vjp(gelu_once, x, ct)
    assert got.dtype == jnp.bfloat16
    slope = jax.vmap(jax.grad(lambda a: jax.nn.gelu(a, approximate=False)))(
        x.astype(jnp.float32))
    ctf = _f32(ct)
    want = np.asarray(slope) * ctf
    err = np.abs(_f32(got) - want)
    assert np.all(err <= 2.0 ** -7 * np.maximum(np.abs(want), np.abs(ctf)))
    _, auto = _vjp(lambda a: jax.nn.gelu(a, approximate=False), x, ct)
    auto_err = np.abs(_f32(auto) - want)
    assert err.max() <= auto_err.max()
    assert err.mean() <= 1.5 * auto_err.mean()


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5),
                                       (jnp.bfloat16, 0.0)],
                         ids=["f32", "bf16"])
def test_under_checkpoint_the_forward_rule_reruns(dtype, tol):
    """Recomputed, the forward rule makes the same value and derivative
    again: the gradients are those of the step that keeps them (bit for
    bit in bf16, where both round the same numbers)."""
    x, ct = _inputs(dtype, 256)
    w = (jax.random.normal(jax.random.PRNGKey(2), (256, 256)) / 16).astype(
        dtype)

    def loss(a, f):
        return jnp.sum((f(a @ w) @ w * ct).astype(jnp.float32))

    plain = jax.jit(jax.grad(lambda a: loss(a, gelu_once)))(x)
    remat = jax.jit(jax.grad(lambda a: jax.checkpoint(
        loss, static_argnums=1)(a, gelu_once)))(x)
    assert "optimization_barrier" in str(jax.make_jaxpr(jax.grad(
        lambda a: jax.checkpoint(loss, static_argnums=1)(a, gelu_once)))(x))
    np.testing.assert_allclose(_f32(remat), _f32(plain), rtol=tol, atol=tol)
    if dtype == jnp.float32:
        want = jax.grad(lambda a: loss(
            a, lambda b: jax.nn.gelu(b, approximate=False)))(x)
        np.testing.assert_allclose(np.asarray(plain), np.asarray(want),
                                   rtol=1e-5, atol=1e-6)


def test_through_the_eager_tape():
    data = np.asarray(_inputs(jnp.float32, 512)[0]).reshape(8, 64)
    grads = []
    for act in (lambda t: execute(gelu_once, t, _name="gelu_once"),
                lambda t: F.gelu(t, approximate=False)):
        x = paddle.to_tensor(data, stop_gradient=False)
        y = act(x)
        (y * y).sum().backward()
        grads.append((y.numpy(), x.grad.numpy()))
    np.testing.assert_allclose(grads[0][0], grads[1][0], rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(grads[0][1], grads[1][1], rtol=1e-5, atol=1e-5)


def test_bf16_tape_value_is_the_no_grad_value():
    """Through `execute`, which calls jax.vjp when a gradient is wanted and
    the function alone under no_grad."""
    data = _inputs(jnp.bfloat16, 512)[0].reshape(8, 64)
    x = paddle.Tensor(data)
    x.stop_gradient = False
    taped = execute(gelu_once, x, _name="gelu_once")
    assert not taped.stop_gradient
    with paddle.no_grad():
        plain = execute(gelu_once, x, _name="gelu_once")
    assert plain.stop_gradient
    np.testing.assert_array_equal(np.asarray(taped._data),
                                  np.asarray(plain._data))


def test_gpt_tiny_loss_and_every_gradient_match_the_model_with_f_gelu(
        monkeypatch):
    ids = paddle.to_tensor(np.random.RandomState(0).randint(0, 512, (2, 16)))
    results = []
    for plain in (False, True):
        if plain:
            monkeypatch.setattr(
                gpt_mod, "gelu_once",
                lambda x: jax.nn.gelu(x, approximate=False))
        paddle.seed(0)
        model = gpt_mod.gpt_tiny()
        loss, _ = model(ids, labels=ids)
        loss.backward()
        results.append((float(loss), {n: p.grad.numpy()
                                      for n, p in model.named_parameters()}))
    (loss, grads), (want_loss, want) = results
    assert abs(loss - want_loss) <= 1e-6 * want_loss
    assert set(grads) == set(want) and len(grads) > 20
    for name in want:
        scale = np.abs(want[name]).max()
        np.testing.assert_allclose(grads[name], want[name], rtol=1e-4,
                                   atol=1e-6 * max(scale, 1.0), err_msg=name)


def test_a_bf16_gpt_serves_the_logits_it_trains_with():
    """The forward of a training step (tape on, the forward rule) and a
    `no_grad` forward (the primal body) of a bf16 gpt_tiny: same logits,
    bit for bit, and a gradient for every parameter."""
    paddle.seed(0)
    model = gpt_mod.gpt_tiny().bfloat16()
    ids = paddle.to_tensor(np.random.RandomState(0).randint(0, 512, (2, 16)))
    loss, trained = model(ids, labels=ids)
    assert trained._data.dtype == jnp.bfloat16
    with paddle.no_grad():
        served = model(ids)
    np.testing.assert_array_equal(np.asarray(trained._data),
                                  np.asarray(served._data))
    loss.backward()
    for name, p in model.named_parameters():
        assert p.grad is not None and np.isfinite(_f32(p.grad._data)).all(), \
            name


def test_a_forward_only_program_holds_no_derivative():
    """A `no_grad` trace runs the primal body: nothing behind a barrier.
    The same model differentiated has one barrier a block. `generate` and
    a compiled forward run."""
    paddle.seed(0)
    model = gpt_mod.gpt_tiny()
    ids = np.random.RandomState(0).randint(0, 512, (2, 16))

    def forward(arr):
        with paddle.no_grad():
            return model(paddle.Tensor(arr))._data
    assert "optimization_barrier" not in str(
        jax.make_jaxpr(forward)(jnp.asarray(ids)))

    def loss(arr):
        return model(paddle.Tensor(arr), labels=paddle.Tensor(arr))[0]._data
    assert str(jax.make_jaxpr(loss)(jnp.asarray(ids))).count(
        "optimization_barrier") == model.config.num_hidden_layers

    out = model.generate(paddle.to_tensor(ids), max_new_tokens=2)
    assert out.shape == [2, 18]
    static = paddle.jit.to_static(model)
    with paddle.no_grad():
        logits = static(paddle.to_tensor(ids))
    assert logits.shape == [2, 16, 512]
