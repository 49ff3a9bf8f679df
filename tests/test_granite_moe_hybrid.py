"""Granite 4.0-H (models/granite_moe_hybrid.py) against its plain reference
(benchmark/references/granite_hybrid_ref.py) at a tiny size on the CPU:
hidden 64, a shortened period that keeps all three layer kinds (mamba,
attention, mamba), 8 experts with top-3, float32, seeded weights with the
published Mamba-2 initialisation (the model's constructor draws it).

Each tolerance has its reason beside it. Program and reference both run in
float32 here, so what separates them is the order of the sums alone.
"""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import optimizer
from paddle_tpu.models import granite_moe_hybrid
from paddle_tpu.models.granite_moe_hybrid import (GraniteMoeHybridConfig,
                                                  granite_hybrid_tiny)
from paddle_tpu.ops.mamba2 import ssd_chunked_scan
from paddle_tpu.parallel import DP_ONLY_RULES, SpmdTrainer, create_mesh
from paddle_tpu.parallel.functional import make_loss_fn
from paddle_tpu.parallel import moe
from paddle_tpu.parallel.moe import route_top_k, sorted_assignments

# jitted: eager, every small op of the sort and the grouped product
# compiles on its own
dropless_moe = jax.jit(moe.dropless_moe, static_argnums=(4, 5))

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_reference():
    path = os.path.join(REPO, "benchmark", "references",
                        "granite_hybrid_ref.py")
    spec = importlib.util.spec_from_file_location("granite_hybrid_ref", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load_reference()


def _ref_cfg(c: GraniteMoeHybridConfig):
    return {k: getattr(c, k) for k in ref.CFG_KEYS}


@pytest.fixture(scope="module")
def tiny():
    paddle.seed(7)
    model = granite_hybrid_tiny()
    params = {k: v._data for k, v in model.state_dict().items()}
    # two sequences; 29 tokens: no multiple of the chunk (8)
    ids = np.random.RandomState(3).randint(0, 256, (2, 29)).astype(np.int32)
    return model, params, ids


def test_published_mamba_initialisation_is_what_the_constructor_draws(tiny):
    _, params, _ = tiny
    a = np.exp(np.asarray(params["model.layers.0.mamba.A_log"]))
    assert a.min() >= 1.0 and a.max() <= 16.0 and a.std() > 1.0
    step = np.log1p(np.exp(np.asarray(params["model.layers.0.mamba.dt_bias"])))
    assert step.min() >= 0.9e-3 and step.max() <= 0.11
    assert np.all(np.asarray(params["model.layers.2.mamba.D"]) == 1.0)
    taps = np.asarray(params["model.layers.0.mamba.conv1d.weight"])
    assert taps.shape == (4, 160) and 0.4 < np.abs(taps).max() <= 0.5


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
    # float32 both sides; the chunked scan, the sorted experts and the
    # reference's step-by-step sums differ in summation order only:
    # 1e-5 of a loss near ln(256) = 5.5 is ~20 float32 roundings
    assert abs(float(got) - float(want)) < 1e-5
    assert set(grads) == set(ref_grads)
    for name in sorted(grads):
        g, r = np.asarray(grads[name]), np.asarray(ref_grads[name])
        assert np.abs(r).max() > 0, name       # every parameter is reached
        # relative to the gradient's own scale: 2e-4 leaves room for the
        # float32 sums over 29 positions x 3 layers of recurrence, and is
        # far under what a wrong term (a missing D, a decay off by one
        # position) gives: those are errors of order 1
        assert np.abs(g - r).max() <= 2e-4 * np.abs(r).max() + 1e-8, name


def _recurrence(x, dt, a, bm, cm, d):
    """S_t = exp(dt_t a) S_{t-1} + dt_t x_t B_t^T; y_t = S_t C_t + d x_t,
    one step at a time, in float64 numpy."""
    x, dt, a, bm, cm, d = (np.asarray(t, np.float64)
                           for t in (x, dt, a, bm, cm, d))
    b, s, h, p = x.shape
    y = np.zeros_like(x)
    for i in range(b):
        state = np.zeros((h, p, bm.shape[-1]))
        for t in range(s):
            state = (np.exp(dt[i, t] * a)[:, None, None] * state
                     + (dt[i, t][:, None] * x[i, t])[:, :, None]
                     * bm[i, t][None, None, :])
            y[i, t] = state @ cm[i, t] + d[:, None] * x[i, t]
    return y


@pytest.mark.parametrize("seq", [32, 29, 5],
                         ids=["whole_chunks", "ragged_tail", "under_a_chunk"])
def test_chunked_scan_is_the_recurrence(seq):
    rs = np.random.RandomState(seq)
    b, h, p, n, chunk = 2, 4, 8, 16, 8
    x = rs.randn(b, seq, h, p).astype(np.float32)
    dt = np.exp(rs.uniform(np.log(1e-3), np.log(0.5), (b, seq, h))
                ).astype(np.float32)
    a = -rs.uniform(1.0, 16.0, (h,)).astype(np.float32)
    bm = rs.randn(b, seq, n).astype(np.float32)
    cm = rs.randn(b, seq, n).astype(np.float32)
    d = rs.randn(h).astype(np.float32)
    want = _recurrence(x, dt, a, bm, cm, d)
    with jax.default_matmul_precision("highest"):
        got = ssd_chunked_scan(*map(jnp.asarray, (x, dt, a, bm, cm, d)),
                               chunk)
    # float32 against float64 over at most 32 steps: 1e-5 of the largest
    # output; a decay applied one position early or late is off by ~10%
    assert np.abs(np.asarray(got) - want).max() <= 1e-5 * np.abs(want).max()

    # and its gradient is finite where the masked half of the decay matrix
    # would overflow (exp of a positive sum) if it were masked after exp
    big = jnp.asarray(dt * 200.0)
    g = jax.grad(lambda t: ssd_chunked_scan(
        jnp.asarray(x), t, jnp.asarray(a), jnp.asarray(bm), jnp.asarray(cm),
        jnp.asarray(d), chunk).sum())(big)
    assert np.isfinite(np.asarray(g)).all()


def _moe_weights(rs, d=32, e=8, inter=16):
    return (jnp.asarray(rs.randn(d, e).astype(np.float32)),
            jnp.asarray(rs.randn(e, d, 2 * inter).astype(np.float32) * 0.2),
            jnp.asarray(rs.randn(e, inter, d).astype(np.float32) * 0.2))


def _ref_routed(x, router, w_in, w_out, k, held):
    cfg = {"experts_held": held, "num_experts_per_tok": k}
    first, count = held
    return ref.routed(x, {"router.weight": router,
                          "experts.input_linear": w_in[first:first + count],
                          "experts.output_linear": w_out[first:first + count]},
                      cfg)


def test_the_shares_add_up_to_the_uncut_layer():
    """Routed parts from every share of the experts, plus the shared expert
    counted once, equal the uncut reference layer."""
    rs = np.random.RandomState(0)
    router, w_in, w_out = _moe_weights(rs)
    x = jnp.asarray(rs.randn(40, 32).astype(np.float32))
    s_in = jnp.asarray(rs.randn(32, 24).astype(np.float32) * 0.2)
    s_out = jnp.asarray(rs.randn(12, 32).astype(np.float32) * 0.2)
    k = 3
    with jax.default_matmul_precision("highest"):
        whole = jax.jit(_ref_routed, static_argnums=(4, 5))(
            x, router, w_in, w_out, k, (0, 8)) \
            + ref._gated(x, s_in, s_out)
        parts = sum(dropless_moe(x, router, w_in[f:f + c], w_out[f:f + c], k,
                                 (f, c))
                    for f, c in ((0, 3), (3, 3), (6, 2)))
        got = parts + ref._gated(x, s_in, s_out)
    # float32, sums of at most 3 expert terms in another order
    np.testing.assert_allclose(np.asarray(got), np.asarray(whole),
                               rtol=1e-5, atol=1e-5)
    # a share alone is not the layer: the cut leaves something out
    assert np.abs(np.asarray(parts - dropless_moe(
        x, router, w_in[:3], w_out[:3], k, (0, 3)))).max() > 1e-2


def test_dropless_under_skew_loses_nothing():
    """Every token's first choice is ONE held expert: it takes all T rows,
    four times the even share, and every row is computed."""
    rs = np.random.RandomState(1)
    router, w_in, w_out = _moe_weights(rs)
    x = jnp.asarray(np.abs(rs.randn(64, 32)).astype(np.float32))
    router = router.at[:, 5].set(10.0)         # x > 0: expert 5 always wins
    k, held = 3, (4, 2)
    ids, _ = route_top_k(x, router, k)
    assert np.all(np.asarray(ids[:, 0]) == 5)
    _, slot, sizes = sorted_assignments(ids, held)
    assert int(sizes[1]) == 64                 # all of them landed
    assert int((np.asarray(slot) < 64 * 2).sum()) == int(sizes.sum())
    with jax.default_matmul_precision("highest"):
        got = dropless_moe(x, router, w_in[4:6], w_out[4:6], k, held)
        want = jax.jit(_ref_routed, static_argnums=(4, 5))(
            x, router, w_in, w_out, k, held)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    # the gradient reaches the tokens through the gather-only transposes
    gx = jax.jit(jax.grad(lambda t: moe.dropless_moe(
        t, router, w_in[4:6], w_out[4:6], k, held).sum()))(x)
    rx = jax.jit(jax.grad(lambda t: _ref_routed(
        t, router, w_in, w_out, k, held).sum()))(x)
    np.testing.assert_allclose(np.asarray(gx), np.asarray(rx), rtol=1e-4,
                               atol=1e-5)


@pytest.mark.parametrize("held", [(0, 8), (4, 2), (7, 1)],
                         ids=["all_experts", "two_of_eight", "the_last_one"])
def test_sorted_assignments_against_numpy(held):
    """Rows, slots and sizes against a stable numpy sort and a bincount,
    for a routing that sends some experts nothing."""
    rs = np.random.RandomState(8)
    first, count = held
    k, tokens = 3, 50
    ids = np.stack([rs.choice([0, 1, 2, 4, 6, 7], k, replace=False)
                    for _ in range(tokens)]).astype(np.int32)  # never 3, 5
    source, slot, sizes = jax.jit(sorted_assignments, static_argnums=1)(
        jnp.asarray(ids), held)
    rows = tokens * min(k, count)
    local = ids.reshape(-1) - first
    flat = np.where((local >= 0) & (local < count), local, count)
    order = np.argsort(flat, kind="stable")
    np.testing.assert_array_equal(np.asarray(source), order[:rows])
    np.testing.assert_array_equal(
        np.asarray(sizes), np.bincount(flat, minlength=count + 1)[:count])
    want = np.where(flat < count, np.argsort(order), rows)
    np.testing.assert_array_equal(np.asarray(slot).reshape(-1), want)


def test_gates_reach_their_rows_by_a_sort_and_come_back_by_one():
    """`_in_slot_order` is the gather values[source], and its transpose
    is the gather's for the rows that hold an assignment. The rows that
    hold none send nothing back, whatever they hold: on the chip the
    grouped-matmul kernels leave them unwritten, and a NaN there reached
    the router through the gates' gradient (PR 29's first chip run)."""
    rs = np.random.RandomState(9)
    ids = jnp.asarray(np.stack([rs.choice(8, 3, replace=False)
                                for _ in range(40)]).astype(np.int32))
    held = (2, 4)
    source, slot, sizes = sorted_assignments(ids, held)
    rows, live = source.shape[0], int(sizes.sum())
    assert 0 < live < rows
    values = jnp.asarray(rs.randn(120).astype(np.float32))
    weights = rs.randn(rows).astype(np.float32)
    np.testing.assert_array_equal(
        np.asarray(moe._in_slot_order(values, slot.reshape(-1), rows)),
        np.asarray(values[source]))

    def by_sort(v, w):
        return jnp.sum(moe._in_slot_order(v, slot.reshape(-1), rows) * w)

    def by_gather(v):
        return jnp.sum(v[source[:live]] * weights[:live])

    want = np.asarray(jax.grad(by_gather)(values))
    for rest in (weights[live:], np.full(rows - live, np.nan, np.float32)):
        w = jnp.asarray(np.concatenate([weights[:live], rest]))
        np.testing.assert_array_equal(
            np.asarray(jax.grad(by_sort)(values, w)), want)


def _leaving_rows_unwritten(real):
    """`lax.ragged_dot` as the chip's kernel behaves: the rows past the
    last group are not written (here: NaN), in the product and in the
    rows' gradient, and what the caller put there is not read."""
    def poison(out, sizes):
        live = jnp.arange(out.shape[0]) < jnp.sum(sizes)
        return jnp.where(live[:, None], out, jnp.nan)

    @jax.custom_vjp
    def ragged_dot(lhs, rhs, sizes):
        return poison(real(lhs, rhs, sizes), sizes)

    def fwd(lhs, rhs, sizes):
        return ragged_dot(lhs, rhs, sizes), (lhs, rhs, sizes)

    def bwd(res, g):
        lhs, rhs, sizes = res
        live = jnp.arange(g.shape[0]) < jnp.sum(sizes)
        d_lhs, d_rhs = jax.vjp(lambda a, b: real(a, b, sizes), lhs, rhs)[1](
            jnp.where(live[:, None], g, 0))
        return poison(d_lhs, sizes), d_rhs, None

    ragged_dot.defvjp(fwd, bwd)
    return ragged_dot


def test_rows_that_hold_no_assignment_may_hold_anything(monkeypatch):
    """Output and every gradient are the same, and finite, when the
    grouped products leave the rows past the last assignment unwritten,
    as the TPU's kernel does and the CPU's does not (it writes zeros, so
    no other test here sees what leaks out of those rows)."""
    rs = np.random.RandomState(11)
    router, w_in, w_out = _moe_weights(rs)
    x = jnp.asarray(rs.randn(64, 32).astype(np.float32))
    k, held = 3, (4, 2)
    args = (x, router, w_in[4:6], w_out[4:6])

    def grads():
        def f(x_, r_, wi, wo):
            out = moe.dropless_moe(x_, r_, wi, wo, k, held)
            return jnp.sum(out * jnp.cos(jnp.arange(out.size).reshape(
                out.shape))), out
        return jax.jit(jax.grad(f, (0, 1, 2, 3), has_aux=True))(*args)

    g_want, out_want = grads()
    monkeypatch.setattr(jax.lax, "ragged_dot",
                        _leaving_rows_unwritten(jax.lax.ragged_dot))
    g_got, out_got = grads()
    np.testing.assert_array_equal(np.asarray(out_got), np.asarray(out_want))
    for g, r in zip(g_got, g_want):
        assert np.abs(np.asarray(r)).max() > 0
        np.testing.assert_array_equal(np.asarray(g), np.asarray(r))


def test_pallas_grouped_matmul_is_ragged_dot_forward_and_backward():
    """`moe._gmm` (jax's Pallas grouped matmul, interpreted here, each of
    its three products at its own tiles) against `lax.ragged_dot` and its
    two gradients: uneven groups, one empty, a k and an n that the tiles
    do not divide, and NaN in the rows past the last group, which neither
    reads and neither has to write."""
    rs = np.random.RandomState(35)
    sizes = jnp.asarray([100, 0, 156, 71], jnp.int32)
    live = np.arange(512) < int(sizes.sum())
    lhs = rs.randn(512, 160).astype(np.float32)
    lhs[~live] = np.nan
    lhs = jnp.asarray(lhs)
    rhs = jnp.asarray(rs.randn(4, 160, 200).astype(np.float32))
    cot = jnp.asarray(np.where(live[:, None], rs.randn(512, 200), np.nan)
                      .astype(np.float32))
    tiles = ((128, 128, 128), (256, 256, 128), (128, 128, 256))

    def run(product):
        out, back = jax.vjp(product, lhs, rhs)
        return (out,) + back(cot)

    with jax.default_matmul_precision("highest"):
        want = run(lambda a, b: jax.lax.ragged_dot(a, b, sizes))
        got = run(lambda a, b: moe._gmm(a, b, sizes, tiles, True))
    for g, w, rows in zip(got, want, (live, live, slice(None))):
        g, w = np.asarray(g)[rows], np.asarray(w)[rows]
        assert np.isfinite(w).all() and np.abs(w).max() > 1
        # float32 both: the order of a 160- or 327-term sum
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-4)
    assert not np.asarray(got[2])[1].any()       # the empty group's


@pytest.mark.parametrize("rows, k, n, dtype, backend, picked", [
    (32768, 2304, 1792, "bfloat16", "tpu", True),    # the Mellum cell's
    (32768, 896, 2304, "bfloat16", "tpu", True),     # two products
    (18432, 4096, 1536, "bfloat16", "tpu", False),   # granite's: no entry
    (32768, 2304, 1792, "float32", "tpu", False),
    (32768 + 128, 2304, 1792, "bfloat16", "tpu", False),  # half a tile over
    (32768, 2304, 1792, "bfloat16", "cpu", False),
], ids=["mellum_in", "mellum_out", "granite", "float32", "odd_rows", "cpu"])
def test_the_pallas_grouped_matmul_is_chosen_by_shape_dtype_and_backend(
        monkeypatch, rows, k, n, dtype, backend, picked):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    tiles = moe._gmm_tiles(jax.ShapeDtypeStruct((rows, k), dtype),
                           jax.ShapeDtypeStruct((16, k, n), dtype))
    assert (tiles is not None) == picked
    for product, (tm, tk, tn) in zip((
            (rows, k, n), (rows, n, k), (rows, k, n)), tiles or ()):
        # whole 128-lane tiles that divide what they cut
        assert product[0] % tm == 0 and tk % 128 == 0 and tn % 128 == 0
        assert product[1] % tk == 0 and product[2] % tn == 0


def test_gates_are_the_softmax_of_the_top_k_logits():
    """route_top_k picks the chosen logits out by comparison; values and
    gradients are those of lax.top_k's own values."""
    rs = np.random.RandomState(10)
    x = jnp.asarray(rs.randn(33, 32).astype(np.float32))
    router = jnp.asarray(rs.randn(32, 8).astype(np.float32))
    cot = jnp.asarray(rs.randn(33, 3).astype(np.float32))

    def plain(x_, r_):
        top, ids = jax.lax.top_k(x_ @ r_, 3)
        return ids, jax.nn.softmax(top, axis=-1)

    with jax.default_matmul_precision("highest"):
        ids, gates = route_top_k(x, router, 3)
        want_ids, want = plain(x, router)
        got_g = jax.grad(lambda a, b: jnp.sum(
            route_top_k(a, b, 3)[1] * cot), (0, 1))(x, router)
        want_g = jax.grad(lambda a, b: jnp.sum(plain(a, b)[1] * cot),
                          (0, 1))(x, router)
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(want_ids))
    np.testing.assert_array_equal(np.asarray(gates), np.asarray(want))
    for g, r in zip(got_g, want_g):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r), rtol=1e-6,
                                   atol=1e-6)


def test_ffn_in_token_blocks_is_the_ffn(tiny, monkeypatch):
    model, params, ids = tiny
    ids = np.tile(ids[:, :24], (1, 2))          # 2 x 48 tokens: 6 blocks of 16
    loss_fn = make_loss_fn(model)
    whole = jax.jit(loss_fn)(params, (ids, ids), None)   # under one block
    monkeypatch.setattr(granite_moe_hybrid, "FFN_TOKEN_BLOCK", 16)
    blocked, grads = jax.jit(jax.value_and_grad(loss_fn))(
        params, (ids, ids), None)
    assert abs(float(whole) - float(blocked)) < 1e-6   # the same sums
    assert all(np.isfinite(np.asarray(g)).all() for g in grads.values())


def test_trains_through_spmd_trainer_with_a_share_of_the_experts():
    paddle.seed(11)
    model = granite_hybrid_tiny(experts_held=(2, 4))
    assert model.state_dict()[
        "model.layers.0.block_sparse_moe.experts.input_linear"].shape[0] == 4
    opt = optimizer.AdamW(3e-3, parameters=model.parameters())
    trainer = SpmdTrainer(model, opt, create_mesh(devices=jax.devices()[:1]),
                          DP_ONLY_RULES)
    ids = np.random.RandomState(0).randint(0, 256, (2, 32)).astype(np.int32)
    cfg = _ref_cfg(model.config)
    with jax.default_matmul_precision("highest"):
        want = float(ref.loss(dict(trainer.params), jnp.asarray(ids), cfg))
    losses = [float(trainer.step((ids, ids))) for _ in range(4)]
    assert abs(losses[0] - want) < 1e-5        # float32 both, as above
    assert losses[-1] < losses[0] - 0.05
    # step_memory() lowers what step() lowered: no second compile of the
    # step (57 s at the published widths), and the compiler's own peak
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **kw: compiles.append(kw.get("fun_name"))
        if event == "/jax/core/compile/backend_compile_duration" else None)
    mem = trainer.step_memory((ids, ids))
    assert "train_step" not in compiles
    assert mem["argument"] > 0 and mem["temp"] > 0
    assert mem["peak"] <= mem["argument"] + mem["output"] - mem["alias"] \
        + mem["temp"]


def test_reference_calls_back_after_every_sub_block(tiny):
    """loss(on_block=) hands a caller each sub-block's input and output:
    what benchmark/families/granite_hybrid.py holds the program's
    sub-blocks to on the chip."""
    model, params, ids = tiny
    cfg = _ref_cfg(model.config)
    seen = []
    want = ref.loss(params, jnp.asarray(ids), cfg)
    got = ref.loss(params, jnp.asarray(ids), cfg,
                   on_block=lambda i, name, a, b: seen.append(
                       (i, name, a.shape, float(jnp.abs(b - a).max()) > 0)))
    assert got == want
    assert seen == [(0, "mamba", (29, 64), True),
                    (0, "block_sparse_moe", (29, 64), True),
                    (1, "self_attn", (29, 64), True),
                    (1, "block_sparse_moe", (29, 64), True),
                    (2, "mamba", (29, 64), True),
                    (2, "block_sparse_moe", (29, 64), True)]
