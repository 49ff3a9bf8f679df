"""ops/pallas/moe_gather.py, interpreted, against parallel/moe.py
`_gather_rows` bit for bit, and the rule that picks it."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops.pallas import moe_gather
from paddle_tpu.parallel import moe


def _bits(a):
    return np.asarray(jax.lax.bitcast_convert_type(
        a, jnp.uint16 if a.dtype == jnp.bfloat16 else jnp.uint32))


def _same(y, slot, **kw):
    got = moe_gather.gather(y, slot, interpret=True, **kw)
    want = moe._gather_rows(y, slot)
    assert got.dtype == want.dtype and got.shape == want.shape
    np.testing.assert_array_equal(_bits(got), _bits(want))


def _routed(tokens, experts, top_k, held, width, dtype, seed):
    """(y, slot) as dropless_moe hands them to the combine: the slot table
    of seeded top-k routing, a token's live slots first where fewer
    experts are held than it chooses, rows past the last live one NaN."""
    key = jax.random.key(seed)
    ids = jax.lax.top_k(jax.random.normal(key, (tokens, experts)), top_k)[1]
    _, slot, sizes = moe.sorted_assignments(ids, (0, held))
    cols = min(top_k, held)
    if cols < top_k:
        slot = jnp.sort(slot, axis=1)[:, :cols]
    rows = tokens * cols
    y = jax.random.normal(jax.random.fold_in(key, 1), (rows, width), dtype)
    y = jnp.where((jnp.arange(rows) < jnp.sum(sizes))[:, None], y, jnp.nan)
    return y.astype(dtype), slot


@pytest.mark.parametrize("tokens, experts, top_k, held, width, tile", [
    (128, 64, 8, 16, 256, 32),      # the Mellum cell's routing: unsorted
    (128, 72, 10, 9, 384, 32),      # granite's: 9 of 10 columns, live first
    (64, 16, 4, 16, 256, 16),       # every slot live
], ids=["unsorted_k8_of_16", "live_first_9_of_10", "all_held"])
def test_routed_tables_equal_the_gathers_bit_for_bit(tokens, experts, top_k,
                                                     held, width, tile):
    y, slot = _routed(tokens, experts, top_k, held, width, jnp.bfloat16, 0)
    _same(y, slot, tile=tile)


def test_tokens_with_none_some_and_all_slots_live():
    rng = np.random.default_rng(1)
    rows, k = 512, 8
    slot = rng.integers(0, rows, (64, k))
    slot[:16] = rows                        # no live slot (the sentinel)
    slot[16:32] = rng.permutation(rows)[:16 * k].reshape(16, k)  # all
    some = rng.random((32, k)) < 0.3
    slot[32:] = np.where(some, slot[32:], rows)
    y = jnp.asarray(rng.standard_normal((rows, 256)), jnp.bfloat16)
    _same(y, jnp.asarray(slot, jnp.int32), tile=16)


def test_more_blocks_than_the_buffer_holds_come_in_rounds():
    """A tile whose slots name more distinct 8-row blocks than the buffer
    holds fetches the rest in rounds: slower, the same sums."""
    rng = np.random.default_rng(2)
    rows = 4096
    slot = np.where(rng.random((64, 8)) < 0.8,
                    rng.integers(0, rows, (64, 8)), rows)
    y = jnp.asarray(rng.standard_normal((rows, 256)), jnp.bfloat16)
    _same(y, jnp.asarray(slot, jnp.int32), tile=32)


def test_cancelling_rows_and_negative_zeros():
    """Rows that cancel to zero and a row of -0.0: the sum is +0.0 where
    the gathers give +0.0."""
    rng = np.random.default_rng(3)
    rows = 128
    y = rng.standard_normal((rows, 384)).astype(np.float32)
    y[1] = -y[0]
    y[2] = -0.0
    slot = np.where(rng.random((32, 3)) < 0.5,
                    rng.integers(0, rows, (32, 3)), rows)
    slot[0] = [0, 1, rows]
    slot[1] = [2, rows, 2]
    slot[2] = [rows, 2, rows]
    _same(jnp.asarray(y, jnp.bfloat16), jnp.asarray(slot, jnp.int32),
          tile=8)


def test_the_rule(monkeypatch):
    bf = jnp.bfloat16

    def sds(shape, dtype=bf):
        return jax.ShapeDtypeStruct(shape, dtype)
    slot = sds((4096, 8), jnp.int32)
    # off a TPU the gathers stay
    assert not moe_gather.usable(sds((32768, 2304)), slot)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert moe_gather.usable(sds((32768, 2304)), slot)
    assert moe_gather.usable(sds((18432, 4096)), sds((2048, 9), jnp.int32))
    assert not moe_gather.usable(sds((32768, 2304), jnp.float32), slot)
    assert not moe_gather.usable(sds((32768, 2300)), slot)      # odd width
    assert not moe_gather.usable(sds((32764, 2304)), slot)      # rows
    assert not moe_gather.usable(sds((32768, 2304), jnp.float16), slot)
    assert not moe_gather.usable(sds((36, 256)), sds((12, 3), jnp.int32))


@pytest.mark.parametrize("held", [(0, 8), (2, 3)],
                         ids=["unsorted", "live_first"])
@pytest.mark.parametrize("differentiate_routing", [True, False])
def test_dropless_moe_value_and_gradients_are_unchanged(
        monkeypatch, held, differentiate_routing):
    """The layer through the kernel (interpreted, the rule steered) gives
    the value and the gradients in x, w_in and w_out of the gathers', bit
    for bit: forward as the combine, backward as the dispatch's
    transpose."""
    tokens, width, inter, experts, top_k = 64, 128, 64, 16, 4
    keys = jax.random.split(jax.random.key(4), 4)
    bf = jnp.bfloat16
    x = jax.random.normal(keys[0], (tokens, width), bf)
    router = jax.random.normal(keys[1], (width, experts), bf)
    w_in = jax.random.normal(keys[2], (held[1], width, 2 * inter), bf) * 0.05
    w_out = jax.random.normal(keys[3], (held[1], inter, width), bf) * 0.05

    def loss(x, router, w_in, w_out):
        out = moe.dropless_moe(x, router, w_in, w_out, top_k, held,
                               differentiate_routing)
        return jnp.sum(out.astype(jnp.float32) ** 2), out

    def run():
        return jax.jit(jax.value_and_grad(loss, (0, 2, 3), has_aux=True))(
            x, router, w_in, w_out)

    (_, want), want_grads = run()
    calls = []
    real = moe_gather.gather

    def counted(y, slot, **kw):
        calls.append(slot.shape)
        return real(y, slot, tile=32, interpret=True)
    monkeypatch.setattr(moe._gather_kernel, "usable", lambda y, slot: True)
    monkeypatch.setattr(moe._gather_kernel, "gather", counted)
    (_, got), got_grads = run()
    assert len(calls) == 2          # the combine, the dispatch's transpose
    np.testing.assert_array_equal(_bits(got), _bits(want))
    for g, w in zip(got_grads, want_grads):
        np.testing.assert_array_equal(_bits(g), _bits(w))
