"""The rule that picks the attention kernel (ops/pallas/attention_router):
what it answers at every shape a measurement exists for, each side of its
constant, its gates, that every caller gets the one answer, and numeric
parity of the flash kernels with dense attention.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.framework import flags as _flags
from paddle_tpu.ops.pallas import attention_router as ar
from paddle_tpu.ops.pallas import flash_attention as fa


@pytest.fixture(autouse=True)
def _fresh_router():
    ar.clear_routing_cache()
    yield
    _flags.set_flags({"FLAGS_flash_attention_backend": "auto"})
    ar.clear_routing_cache()


@pytest.fixture
def on_tpu(monkeypatch):
    """The callers ask jax for the backend: say it is a TPU."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


# Every shape a v5e measurement exists for, with the measurement: the
# kernels won each, forward and backward. grid: fa_fwd / fa_bwd_dq /
# fa_bwd_dkv steps a call where the benchmark's records hold them.
MEASURED = [
    # id, bh, seq, head_dim, grid steps, provenance
    ("isolated_s1024", 128, 1024, 128, None,
     "PR 27 and PR 30 call 48: fwd 0.747 vs 1.592 ms, fwd+bwd 2.050 vs "
     "5.271"),
    ("isolated_s2048", 32, 2048, 128, None,
     "fwd 0.506 vs 1.585 ms, fwd+bwd 1.474 vs 5.065"),
    ("isolated_s4096", 8, 4096, 128, None,
     "fwd 0.395 vs 3.968 ms, fwd+bwd 1.226 vs 7.322"),
    ("isolated_d96", 32, 2048, 96, None,
     "zero-padded to 128: fwd 0.553 vs 1.699 ms, fwd+bwd 1.602 vs 5.085"),
    ("e2e_llama_535m", 64, 2048, 128, None,
     "round 5 train step: Pallas backward 0.4261 MFU, hybrid 0.4063"),
    ("e2e_llama_780m", 128, 2048, 96, None,
     "PR 27 train step: Pallas backward 0.5775 MFU, hybrid 0.4137"),
    ("cell_gpt3_xl_d12", 64, 2048, 128, (128, 256, 256),
     "ledger, PR 27-29: 19,823 -> 27,320 tokens/s with these kernels"),
    ("cell_granite_gqa_32_8", 32, 8192, 128, (256, 512, 512),
     "ledger, PR 28-29; dense would hold 8 GiB of float32 scores"),
    ("four_chip_shard", 16, 2048, 128, None,
     "PR 27, by hand: 27,355 -> 33,463 tokens/s on 2x2"),
]


class TestMeasuredShapes:
    @pytest.mark.parametrize("case", MEASURED, ids=[c[0] for c in MEASURED])
    def test_flash_forward_and_backward(self, case):
        _, bh, seq, d, grid, why = case
        dec = ar.route(bh, seq, seq, d, "bfloat16", True, platform="tpu")
        assert (dec.fwd, dec.bwd) == ("pallas", "pallas"), why
        assert dec.tiles == fa.tiles_for_shape(bh, seq, seq, d, "bfloat16",
                                               True)
        if grid is not None:
            assert tuple(dec.grid_steps[k] for k in (
                "fa_fwd", "fa_bwd_dq", "fa_bwd_dkv")) == grid


# Each side of the rule's one constant, and what the sweep said of the
# rest of the shape (PR 30, chip call 48; PERF.md section 6)
EDGES = [
    # id, seq_q, seq_k, head_dim, dtype, causal, backend
    ("at_the_constant", 512, 512, 128, "bfloat16", True, "pallas"),
    ("one_under_it", 511, 511, 128, "bfloat16", True, "xla"),
    ("dense_3x_faster_at_256", 256, 256, 128, "bfloat16", True, "xla"),
    ("shortest_serving_bucket", 64, 64, 128, "bfloat16", True, "xla"),
    ("head_dim_64_wins_at_512", 512, 512, 64, "bfloat16", True, "pallas"),
    ("head_dim_64_loses_at_256", 256, 256, 64, "bfloat16", True, "xla"),
    ("head_dim_64_at_8k_is_not_dense", 8192, 8192, 64, "bfloat16", True,
     "pallas"),
    ("non_causal_wins_at_512", 512, 512, 128, "bfloat16", False, "pallas"),
    ("non_causal_loses_at_256", 256, 256, 64, "bfloat16", False, "xla"),
    ("float32_wins_at_512", 512, 512, 128, "float32", True, "pallas"),
    ("few_queries_many_keys", 128, 2048, 128, "bfloat16", True, "xla"),
    ("decode_row", 1, 4096, 128, "bfloat16", True, "xla"),
    ("chunk_against_a_long_cache", 1024, 4096, 128, "bfloat16", True,
     "pallas"),
]


class TestTheConstant:
    @pytest.mark.parametrize("case", EDGES, ids=[c[0] for c in EDGES])
    def test_each_side(self, case):
        _, sq, sk, d, dtype, causal, backend = case
        dec = ar.route(32, sq, sk, d, dtype, causal, platform="tpu")
        assert (dec.fwd, dec.bwd) == (backend, backend)
        assert str(ar._FLASH_MIN_SEQ_Q) in dec.why

    def test_decision_log_lists_each_shape_once(self):
        for _ in range(2):
            ar.route(4, 640, 640, 64, "float32", True, platform="tpu")
        ar.route(4, 640, 320, 64, "float32", True, platform="tpu")
        keys = [key for key, _ in ar.decision_log()]
        assert keys == [(4, 640, 640, 64, "float32", True, None),
                        (4, 640, 320, 64, "float32", True, None)]


class TestBackendParity:
    """Gradients through the flash kernels against dense attention on the
    measured shape set (scaled bh for interpreter speed; the (seq, d)
    geometry is the production one for the not-slow subset's s1024)."""

    def _parity(self, bh, sq, d, dtype, tol):
        rs = np.random.RandomState(1)
        q = jnp.asarray(rs.randn(bh, sq, d), dtype)
        k = jnp.asarray(rs.randn(bh, sq, d), dtype)
        v = jnp.asarray(rs.randn(bh, sq, d), dtype)
        scale = d ** -0.5

        def loss_flash(q_, k_, v_):
            return jnp.sum(fa._flash_attention_bhsd(q_, k_, v_, True,
                                                    scale) ** 2)

        def loss_dense(q_, k_, v_):
            return jnp.sum(fa._xla_attention_bhsd(q_, k_, v_, True,
                                                  scale) ** 2)

        got = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        base = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
        for a, b, nm in zip(got, base, "qkv"):
            np.testing.assert_allclose(
                np.asarray(a, np.float32), np.asarray(b, np.float32),
                rtol=tol, atol=tol, err_msg=f"d{nm}")

    def test_parity_production_geometry_s1024(self):
        # full production (seq, d) at reduced bh
        self._parity(1, 1024, 128, jnp.float32, 2e-3)

    @pytest.mark.slow
    @pytest.mark.parametrize("bh,sq,d", [(2, 2048, 128), (2, 2048, 96),
                                         (1, 4096, 128)])
    def test_parity_full_r5_shape_set(self, bh, sq, d):
        self._parity(bh, sq, d, jnp.float32, 2e-3)


class TestDecisionCarriesTiles:
    """Every Decision names the tiles the flash kernels take at its shape
    and the grid steps one call of each makes (decision_log() surfaces
    them): the count that says the two-level tiles engaged."""

    def test_a_dense_decision_carries_them_too(self):
        dec = ar.route(64, 2048, 2048, 128, "bfloat16", True,
                       platform="cpu")
        assert dec.fwd == "xla"
        assert dec.tiles == fa.choose_tiles(2048, 2048, 128, 2)
        assert dec.grid_steps == dec.tiles.grid_steps(64, 2048, 2048)
        assert max(dec.grid_steps.values()) <= 1024

    def test_padded_head_dim_and_cross_length(self):
        # head_dim 96 rides zero-padded to the lane width in the kernels
        dec = ar.route(8, 300, 1000, 96, "float32", True, platform="cpu")
        assert dec.tiles == fa.choose_tiles(300, 1000, 128, 4)
        res_q, streamed_k, _ = dec.tiles.fwd
        assert dec.grid_steps["fa_fwd"] == (
            8 * -(-300 // res_q) * -(-1000 // streamed_k))


class TestWindow:
    """A window is in the rule's key, its log and its counts, and not in
    its choice."""

    CELL = (64, 16384, 16384, 128, "bfloat16", True)   # the Mellum cell's

    def test_the_window_is_in_the_key_and_the_log(self):
        full = ar.route(*self.CELL, platform="tpu")
        banded = ar.route(*self.CELL, platform="tpu", window=1024)
        again = ar.route(*self.CELL, platform="tpu", window=1024)
        assert again is banded and banded is not full
        assert [key for key, _ in ar.decision_log()] == [
            self.CELL + (None,), self.CELL + (1024,)]
        assert set(full.grid_steps) == {"fa_fwd", "fa_bwd_dq", "fa_bwd_dkv"}
        assert set(banded.grid_steps) == {"faw_fwd", "faw_bwd_dq",
                                          "faw_bwd_dkv"}
        assert banded.tiles == fa.choose_tiles(16384, 16384, 128, 2,
                                               window=1024)
        assert banded.grid_steps == banded.tiles.grid_steps(
            64, 16384, 16384, 1024)

    def test_a_window_that_hides_nothing_is_the_causal_decision(self):
        """The accepted cells' shapes, whatever window reaches them: the
        same Decision object, tiles and grid steps as without one."""
        for bh, seq, steps in ((64, 2048, (128, 256, 256)),
                               (32, 8192, (256, 512, 512))):
            plain = ar.route(bh, seq, seq, 128, "bfloat16", True,
                             platform="tpu")
            for window in (seq, seq + 1, 1 << 20):
                assert ar.route(bh, seq, seq, 128, "bfloat16", True,
                                platform="tpu", window=window) is plain
            assert tuple(plain.grid_steps[k] for k in (
                "fa_fwd", "fa_bwd_dq", "fa_bwd_dkv")) == steps
        assert len(ar.decision_log()) == 2

    @pytest.mark.parametrize("seq_q,backend", [(256, "xla"), (512, "pallas"),
                                               (16384, "pallas")])
    def test_the_choice_stays_one_rule_on_seq_q(self, seq_q, backend):
        for window in (None, 64, 1024):
            dec = ar.route(8, seq_q, seq_q, 128, "bfloat16", True,
                           platform="tpu", window=window)
            assert (dec.fwd, dec.bwd) == (backend, backend)
            assert str(ar._FLASH_MIN_SEQ_Q) in dec.why
        assert ar.route(8, seq_q, seq_q, 128, "bfloat16", True,
                        platform="cpu", window=64).fwd == "xla"

    def test_visited_pair_share(self):
        """visited / needed pairs, the three kernels' mean: 1.0 at best.
        Without a window the causal schedule's own waste at the diagonal;
        under the cell's window never twice the band; a call that is not
        causal skips nothing and reports None."""
        full = ar.route(*self.CELL, platform="tpu")
        banded = ar.route(*self.CELL, platform="tpu", window=1024)
        assert 1.0 < full.visited_pair_share < 1.1
        assert 1.0 < banded.visited_pair_share < 2.0
        visited = banded.tiles.visited_pairs(16384, 16384, 1024)
        assert banded.visited_pair_share == pytest.approx(
            sum(visited.values()) / (3 * 16_253_440))
        # the causal kernels on the same band would visit the triangle
        assert sum(full.tiles.visited_pairs(16384, 16384).values()) \
            > 8 * sum(visited.values()) / 2
        assert ar.route(8, 512, 512, 128, "bfloat16", False,
                        platform="tpu").visited_pair_share is None
        with pytest.raises(ValueError):
            ar.route(8, 512, 512, 128, "bfloat16", False, platform="tpu",
                     window=64)

    def test_the_functional_entry_asks_with_the_window(self, on_tpu):
        from paddle_tpu.nn.functional import attention as attn
        assert attn._use_pallas((2, 1024, 4, 128), 128, False,
                                dtype="bfloat16", causal=True, seq_k=1024,
                                window=256) is True
        assert ar.decision_log()[-1][0] == (8, 1024, 1024, 128, "bfloat16",
                                            True, 256)


class TestFusedEpilogue:
    """The rmsnorm(attn+residual)*gamma epilogue fused into the flash
    flush must match the unfused composition exactly (incl. zero-padded
    head dims, where the mean divisor is the TRUE d)."""

    @pytest.mark.parametrize("d", [32, 48])   # 48 exercises lane padding
    def test_matches_unfused(self, d):
        rs = np.random.RandomState(3)
        b, s, h = 1, 200, 2
        q = jnp.asarray(rs.randn(b, s, h, d), jnp.float32)
        k = jnp.asarray(rs.randn(b, s, h, d), jnp.float32)
        v = jnp.asarray(rs.randn(b, s, h, d), jnp.float32)
        res = jnp.asarray(rs.randn(b, s, h, d), jnp.float32)
        w = jnp.asarray(rs.randn(d), jnp.float32)
        fused = fa.flash_attention_rms_epilogue_bshd(q, k, v, res, w)
        att = fa.flash_attention_bshd(q, k, v, causal=True)
        hh = att + res
        ref = hh * jax.lax.rsqrt(
            jnp.mean(hh * hh, axis=-1, keepdims=True) + 1e-6) * w
        np.testing.assert_allclose(np.asarray(fused), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_incubate_functional_unfused_path(self):
        import paddle_tpu as paddle
        from paddle_tpu.incubate.nn import functional as IF
        rs = np.random.RandomState(4)
        b, s, h, kvh, d = 1, 64, 4, 2, 16    # GQA
        q = paddle.Tensor(jnp.asarray(rs.randn(b, s, h, d), jnp.float32))
        k = paddle.Tensor(jnp.asarray(rs.randn(b, s, kvh, d), jnp.float32))
        v = paddle.Tensor(jnp.asarray(rs.randn(b, s, kvh, d), jnp.float32))
        res = paddle.Tensor(jnp.asarray(rs.randn(b, s, h, d), jnp.float32))
        w = paddle.Tensor(jnp.asarray(rs.randn(d), jnp.float32))
        out = IF.fused_attention_rms_epilogue(q, k, v, res, w)
        assert list(out.shape) == [b, s, h, d]
        # parity vs the fused kernel run directly (expanded-kv equal math)
        fused = fa.flash_attention_rms_epilogue_bshd(
            q._data, k._data, v._data, res._data, w._data)
        np.testing.assert_allclose(np.asarray(out._data), np.asarray(fused),
                                   rtol=2e-4, atol=2e-4)




def _spy_on_flash(monkeypatch):
    """Count calls of the kernels' entry point, and answer with zeros:
    the interpreter would take minutes at these sizes."""
    calls = []

    def spy(q, k, v, causal=False, scale=None, window=None):
        calls.append(q.shape)
        return jnp.zeros(q.shape, q.dtype)
    monkeypatch.setattr(fa, "flash_attention_bshd", spy)
    return calls


class TestGates:
    """What stands before the rule in nn.functional attention."""

    SHAPE = (1, 1024, 2, 128)     # the rule says flash on a TPU

    def _sdpa(self, **kw):
        import paddle_tpu as paddle
        from paddle_tpu.nn import functional as F
        q = paddle.Tensor(jnp.zeros(self.SHAPE, jnp.bfloat16))
        return F.scaled_dot_product_attention(q, q, q, is_causal=True, **kw)

    def test_dropout_runs_dense(self, on_tpu, monkeypatch):
        calls = _spy_on_flash(monkeypatch)
        self._sdpa(dropout_p=0.1, training=True)
        assert calls == []
        self._sdpa(dropout_p=0.1, training=False)   # no dropout at eval
        assert calls == [self.SHAPE]

    def test_no_tpu_runs_dense(self):
        from paddle_tpu.nn.functional import attention as attn
        assert attn._use_pallas(self.SHAPE, 128, False, "bfloat16") is False
        _flags.set_flags({"FLAGS_flash_attention_backend": "pallas"})
        assert attn._use_pallas(self.SHAPE, 128, False, "bfloat16") is False

    def test_flag_xla_runs_dense(self, on_tpu):
        from paddle_tpu.nn.functional import attention as attn
        assert attn._use_pallas(self.SHAPE, 128, False, "bfloat16") is True
        _flags.set_flags({"FLAGS_flash_attention_backend": "xla"})
        assert attn._use_pallas(self.SHAPE, 128, False, "bfloat16") is False

    def test_flag_pallas_runs_the_kernels_under_the_constant(self, on_tpu):
        from paddle_tpu.nn.functional import attention as attn
        short = (1, 64, 2, 128)
        assert attn._use_pallas(short, 128, False, "bfloat16") is False
        _flags.set_flags({"FLAGS_flash_attention_backend": "pallas"})
        assert attn._use_pallas(short, 128, False, "bfloat16") is True

    def test_sdp_kernel_sets_and_restores_the_flag(self, on_tpu):
        from paddle_tpu.nn.functional import attention as attn
        with attn.sdp_kernel(enable_flash=False):
            assert _flags.flag_value("flash_attention_backend") == "xla"
            assert not attn._use_pallas(self.SHAPE, 128, False, "bfloat16")
        assert _flags.flag_value("flash_attention_backend") == "auto"
        with attn.sdp_kernel(enable_flash=True):
            assert _flags.flag_value("flash_attention_backend") == "pallas"
        assert _flags.flag_value("flash_attention_backend") == "auto"

    def test_the_rule_is_asked_with_the_callers_shape(self, on_tpu):
        from paddle_tpu.nn.functional import attention as attn
        attn._use_pallas((2, 512, 4, 64), 64, False, dtype="bfloat16",
                         causal=True, seq_k=768)
        assert ar.decision_log()[-1][0] == (8, 512, 768, 64, "bfloat16",
                                            True, None)

    def test_bias_still_forces_dense(self, on_tpu):
        from paddle_tpu.nn.functional import attention as attn
        assert attn._use_pallas((2, 2048, 4, 128), 128, True) is False


class TestOneAnswer:
    """sdpa, attention_bshd, generation's prefill, the serving engine's
    probe and chip_smoke's report ask the one rule: they agree."""

    @pytest.mark.parametrize("heads,seq,d,dtype,backend", [
        (2, 2048, 128, "bfloat16", "pallas"),
        (4, 512, 96, "bfloat16", "pallas"),
        (2, 256, 64, "float32", "xla"),
    ])
    def test_every_caller_agrees(self, on_tpu, monkeypatch, heads, seq, d,
                                 dtype, backend):
        import chip_smoke
        import paddle_tpu as paddle
        from paddle_tpu import generation
        from paddle_tpu.inference import ContinuousBatchingEngine
        from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
        from paddle_tpu.nn import functional as F
        from paddle_tpu.nn.functional.attention import attention_bshd

        assert ar.route(heads, seq, seq, d, dtype, True).fwd == backend
        flash = backend == "pallas"
        calls = _spy_on_flash(monkeypatch)
        q = jnp.zeros((1, seq, heads, d), dtype)
        F.scaled_dot_product_attention(*(paddle.Tensor(q),) * 3,
                                       is_causal=True)
        assert bool(calls) == flash
        del calls[:]
        attention_bshd(q, q, q)
        assert bool(calls) == flash
        assert generation._prefill_flash_routed(heads, seq, d, dtype) \
            == flash
        model = LlamaForCausalLM(LlamaConfig(
            vocab_size=32, hidden_size=heads * d, intermediate_size=32,
            num_hidden_layers=1, num_attention_heads=heads,
            max_position_embeddings=seq, dtype=dtype))
        eng = ContinuousBatchingEngine(model, num_blocks=4, block_size=8,
                                       max_batch=1, prefill_buckets=(seq,))
        assert eng.attention_route.fwd == backend
        report = chip_smoke._trainer_attention(1, heads, seq, d, dtype)
        assert report["forward"] == ("pallas_flash" if flash
                                     else "xla_dense")


class TestBackwardOfAFlashForward:
    def test_gradient_runs_the_two_backward_kernels(self):
        """Nothing chooses the backward: at a shape the rule sends to the
        kernels, the gradient of flash_attention_bshd is fa_bwd_dq and
        fa_bwd_dkv."""
        assert ar.route(2, 512, 512, 128, "bfloat16", True,
                        platform="tpu").bwd == "pallas"
        q = jax.ShapeDtypeStruct((1, 512, 2, 128), jnp.bfloat16)
        text = str(jax.make_jaxpr(jax.grad(
            lambda q_, k_, v_: jnp.sum(fa.flash_attention_bshd(
                q_, k_, v_, causal=True).astype(jnp.float32)),
            argnums=(0, 1, 2)))(q, q, q))
        for name in ("fa_fwd", "fa_bwd_dq", "fa_bwd_dkv"):
            assert name in text, name
