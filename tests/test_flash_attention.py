"""Pallas flash-attention fwd+bwd vs the dense XLA reference.

reference capability: paddle/phi/kernels/gpu/flash_attn_kernel.cu,
flash_attn_grad_kernel.cu, test/legacy_test/test_flash_attention.py.
Runs under the Pallas interpreter on CPU; same kernels compile on TPU.
"""

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops.pallas import flash_attention as fa
from paddle_tpu.ops.pallas.flash_attention import (
    Tiles, _flash_attention_bhsd, _flash_bwd_bhsd, _flash_fwd_bhsd,
    _xla_attention_bhsd, choose_tiles, flash_attention_bshd)


def _same(res, streamed, sub):
    return Tiles(fwd=(res, streamed, sub), dq=(res, streamed, sub),
                 dkv=(res, streamed, sub))


# the tile regimes the chooser produces, at sizes the interpreter runs in
# seconds (a tile is (resident, streamed, sub) rows): every streamed tile
# one sub-block, so the grid does the sweep and the clamped index maps
# skip; several sub-blocks a tile with the resident, streamed and sub sizes
# all different between kernels, so the in-kernel loops' bounds do
TILE_REGIMES = {
    "one_sub": _same(64, 64, 64),
    "many_sub": Tiles(fwd=(128, 256, 32), dq=(32, 128, 64),
                      dkv=(64, 256, 128)),
}


class _BothTileRegimes:
    """Run every test in the subclass under both regimes of TILE_REGIMES
    (the chooser itself, at the sizes it hands out, is TestTileChooser's
    and TestProductionKernelSmoke's)."""

    @pytest.fixture(autouse=True, params=list(TILE_REGIMES))
    def _tile_regime(self, request, monkeypatch):
        tiles = TILE_REGIMES[request.param]
        monkeypatch.setattr(fa, "choose_tiles", lambda *a, **k: tiles)
        # the regime has to reach the kernels: the jitted calls key on the
        # tiles they are handed, and every call of the test is spied on
        used = []
        for name in ("_fwd_call", "_bwd_call"):
            real = getattr(fa, name)
            monkeypatch.setattr(fa, name, lambda *a, _real=real, **k: (
                used.append(k["tiles"]), _real(*a, **k))[1])
        yield
        assert used and all(t == tiles for t in used), (tiles, used)


def _rand(rs, *shape, dtype=np.float32):
    return jnp.asarray(rs.randn(*shape).astype(dtype))


CASES = [
    # (seq_q, seq_k, causal): aligned, ragged (pad-masked), cross-length
    # (sk > sq causal is bottom-right aligned); 384/520 are not a multiple
    # of every tile
    (256, 256, False),
    (256, 256, True),
    (200, 200, True),
    (384, 384, True),
    (520, 520, True),
    (128, 320, True),
    (100, 260, False),
]


class TestFlashForward(_BothTileRegimes):
    @pytest.mark.parametrize("sq,sk,causal", CASES)
    def test_matches_dense(self, sq, sk, causal):
        rs = np.random.RandomState(0)
        q, k, v = (_rand(rs, 2, sq, 64), _rand(rs, 2, sk, 64),
                   _rand(rs, 2, sk, 64))
        # a fresh function a call: jit's cache is keyed on the function,
        # and the second regime must not be served the first one's trace
        out = jax.jit(lambda *a: _flash_attention_bhsd(*a, causal, 0.125))(
            q, k, v)
        ref = _xla_attention_bhsd(q, k, v, causal, 0.125)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_lse_is_logsumexp(self):
        rs = np.random.RandomState(1)
        q, k, v = _rand(rs, 2, 256, 32), _rand(rs, 2, 256, 32), _rand(
            rs, 2, 256, 32)
        _, lse = _flash_fwd_bhsd(q, k, v, False, 0.1)
        s = jnp.einsum("bqd,bkd->bqk", q, k) * 0.1
        ref = jax.scipy.special.logsumexp(s, axis=-1)
        np.testing.assert_allclose(np.asarray(lse), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    def test_bf16_io_fp32_accumulate(self):
        rs = np.random.RandomState(2)
        q = _rand(rs, 2, 128, 64).astype(jnp.bfloat16)
        k = _rand(rs, 2, 128, 64).astype(jnp.bfloat16)
        v = _rand(rs, 2, 128, 64).astype(jnp.bfloat16)
        out = _flash_attention_bhsd(q, k, v, True, 0.125)
        assert out.dtype == jnp.bfloat16
        ref = _xla_attention_bhsd(q.astype(jnp.float32),
                                  k.astype(jnp.float32),
                                  v.astype(jnp.float32), True, 0.125)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref), rtol=0.05,
            atol=0.05)

    def test_bshd_layout(self):
        rs = np.random.RandomState(3)
        q = _rand(rs, 2, 96, 4, 32)   # (b, s, h, d)
        k = _rand(rs, 2, 96, 4, 32)
        v = _rand(rs, 2, 96, 4, 32)
        out = flash_attention_bshd(q, k, v, causal=True)
        qt = jnp.swapaxes(q, 1, 2).reshape(8, 96, 32)
        kt = jnp.swapaxes(k, 1, 2).reshape(8, 96, 32)
        vt = jnp.swapaxes(v, 1, 2).reshape(8, 96, 32)
        ref = _xla_attention_bhsd(qt, kt, vt, True, 32 ** -0.5)
        ref = jnp.swapaxes(ref.reshape(2, 4, 96, 32), 1, 2)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)


class TestHeadDimPadding:
    """Non-lane-aligned head dims (96 = llama_780m, 32 = tiny) zero-pad to
    the 128-lane tile inside flash_attention_bshd; outputs AND grads must
    match the dense reference with the true-d softmax scale."""

    @pytest.mark.parametrize("d", [96, 32])
    def test_forward_and_grads_match_dense(self, d):
        rs = np.random.RandomState(7)
        q = _rand(rs, 1, 64, 2, d)
        k = _rand(rs, 1, 64, 2, d)
        v = _rand(rs, 1, 64, 2, d)

        def flash_loss(q, k, v):
            return jnp.sum(flash_attention_bshd(q, k, v, causal=True) ** 2)

        def dense_loss(q, k, v):
            qt = jnp.swapaxes(q, 1, 2).reshape(2, 64, d)
            kt = jnp.swapaxes(k, 1, 2).reshape(2, 64, d)
            vt = jnp.swapaxes(v, 1, 2).reshape(2, 64, d)
            ref = _xla_attention_bhsd(qt, kt, vt, True, d ** -0.5)
            ref = jnp.swapaxes(ref.reshape(1, 2, 64, d), 1, 2)
            return jnp.sum(ref ** 2)

        lf, gf = jax.value_and_grad(flash_loss, argnums=(0, 1, 2))(q, k, v)
        ld, gd = jax.value_and_grad(dense_loss, argnums=(0, 1, 2))(q, k, v)
        np.testing.assert_allclose(float(lf), float(ld), rtol=2e-5)
        for a, b in zip(gf, gd):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=5e-4, atol=5e-5)
            assert a.shape[-1] == d  # pad columns sliced off


class TestFlashBackward(_BothTileRegimes):
    """The handwritten Pallas backward (dQ kernel + dK/dV kernel) must match
    autodiff of the dense reference at fp32 tolerance."""

    @pytest.mark.parametrize("sq,sk,causal", CASES)
    def test_grads_match_dense(self, sq, sk, causal):
        rs = np.random.RandomState(4)
        q, k, v = (_rand(rs, 2, sq, 64), _rand(rs, 2, sk, 64),
                   _rand(rs, 2, sk, 64))

        def loss_f(q_, k_, v_):
            o = _flash_attention_bhsd(q_, k_, v_, causal, 0.125)
            return jnp.sum(jnp.sin(o))

        def loss_r(q_, k_, v_):
            o = _xla_attention_bhsd(q_, k_, v_, causal, 0.125)
            return jnp.sum(jnp.sin(o))

        g = jax.jit(jax.grad(loss_f, argnums=(0, 1, 2)))(q, k, v)
        gr = jax.grad(loss_r, argnums=(0, 1, 2))(q, k, v)
        for a, b, nm in zip(g, gr, "qkv"):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5,
                err_msg=f"d{nm} sq={sq} sk={sk} causal={causal}")

    def test_no_quadratic_residuals(self):
        """The vjp residuals must be O(S): q, k, v, o, lse — never the
        (S, S) score matrix (the pre-round-3 backward rematerialized
        through dense XLA attention)."""
        sq = 512
        rs = np.random.RandomState(5)
        q, k, v = (_rand(rs, 1, sq, 32), _rand(rs, 1, sq, 32),
                   _rand(rs, 1, sq, 32))
        _, vjp_fn = jax.vjp(
            lambda a, b, c: _flash_attention_bhsd(a, b, c, True, 0.1),
            q, k, v)
        leaves = jax.tree_util.tree_leaves(vjp_fn)
        assert leaves, "expected residual arrays in the vjp closure"
        for leaf in leaves:
            if hasattr(leaf, "shape"):
                assert sq * sq not in (np.prod(leaf.shape[-2:], dtype=int),), \
                    f"quadratic residual {leaf.shape}"


class TestFlashAttnUnpadded:
    """Packed varlen attention must equal per-sequence dense attention."""

    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_per_sequence(self, causal):
        import paddle_tpu as paddle
        import paddle_tpu.nn.functional as F

        rs = np.random.RandomState(7)
        lens = [5, 9, 3]
        total = sum(lens)
        h, d = 2, 16
        cu = np.concatenate([[0], np.cumsum(lens)]).astype(np.int32)
        q = rs.randn(total, h, d).astype(np.float32)
        k = rs.randn(total, h, d).astype(np.float32)
        v = rs.randn(total, h, d).astype(np.float32)
        scale = d ** -0.5

        out, _ = F.flash_attn_unpadded(
            paddle.Tensor(jnp.asarray(q)), paddle.Tensor(jnp.asarray(k)),
            paddle.Tensor(jnp.asarray(v)),
            paddle.Tensor(jnp.asarray(cu)), paddle.Tensor(jnp.asarray(cu)),
            max(lens), max(lens), scale, causal=causal)
        out = np.asarray(out._data)

        for i, (a, b) in enumerate(zip(cu[:-1], cu[1:])):
            qs, ks, vs = q[a:b], k[a:b], v[a:b]
            ref = _xla_attention_bhsd(
                jnp.swapaxes(jnp.asarray(qs)[None], 1, 2).reshape(h, b - a, d),
                jnp.swapaxes(jnp.asarray(ks)[None], 1, 2).reshape(h, b - a, d),
                jnp.swapaxes(jnp.asarray(vs)[None], 1, 2).reshape(h, b - a, d),
                causal, scale)
            ref = np.asarray(jnp.swapaxes(ref, 0, 1))
            np.testing.assert_allclose(out[a:b], ref, rtol=2e-5, atol=2e-5,
                                       err_msg=f"sequence {i}")

    def test_causal_cross_length_bottom_right(self):
        """Decode-style varlen: len_q != len_k must use bottom-right
        alignment (FlashAttention-2 varlen convention), letting the last
        query of each sequence see every key."""
        import paddle_tpu as paddle
        import paddle_tpu.nn.functional as F

        rs = np.random.RandomState(8)
        lq, lk = [1, 2], [8, 5]
        h, d = 2, 16
        cq = np.concatenate([[0], np.cumsum(lq)]).astype(np.int32)
        ck = np.concatenate([[0], np.cumsum(lk)]).astype(np.int32)
        q = rs.randn(sum(lq), h, d).astype(np.float32)
        k = rs.randn(sum(lk), h, d).astype(np.float32)
        v = rs.randn(sum(lk), h, d).astype(np.float32)
        scale = d ** -0.5

        out, _ = F.flash_attn_unpadded(
            paddle.Tensor(jnp.asarray(q)), paddle.Tensor(jnp.asarray(k)),
            paddle.Tensor(jnp.asarray(v)),
            paddle.Tensor(jnp.asarray(cq)), paddle.Tensor(jnp.asarray(ck)),
            max(lq), max(lk), scale, causal=True)
        out = np.asarray(out._data)

        for i in range(len(lq)):
            qs = q[cq[i]:cq[i + 1]]
            ks = k[ck[i]:ck[i + 1]]
            vs = v[ck[i]:ck[i + 1]]
            ref = _xla_attention_bhsd(
                jnp.swapaxes(jnp.asarray(qs)[None], 1, 2).reshape(h, lq[i], d),
                jnp.swapaxes(jnp.asarray(ks)[None], 1, 2).reshape(h, lk[i], d),
                jnp.swapaxes(jnp.asarray(vs)[None], 1, 2).reshape(h, lk[i], d),
                True, scale)
            ref = np.asarray(jnp.swapaxes(ref, 0, 1))
            np.testing.assert_allclose(out[cq[i]:cq[i + 1]], ref, rtol=2e-5,
                                       atol=2e-5, err_msg=f"sequence {i}")


class TestGQAFlash:
    """GQA-native kernel: unexpanded KV via BlockSpec grouping must match
    dense attention over broadcast-expanded KV, forward and backward."""

    def _make(self, b=2, h=4, kvh=2, sq=64, sk=64, d=16):
        r = np.random.RandomState(7)
        q = jnp.asarray(r.randn(b * h, sq, d), jnp.float32)
        k = jnp.asarray(r.randn(b * kvh, sk, d), jnp.float32)
        v = jnp.asarray(r.randn(b * kvh, sk, d), jnp.float32)
        return q, k, v, h // kvh

    def _expand(self, kv, rep):
        bhkv, s, d = kv.shape
        return jnp.repeat(kv.reshape(bhkv, 1, s, d), rep, 1).reshape(
            bhkv * rep, s, d)

    @pytest.mark.parametrize("tiles", [_same(32, 32, 32), _same(32, 64, 16)],
                             ids=["one_sub", "many_sub"])
    @pytest.mark.parametrize("causal", [False, True])
    def test_forward_matches_dense_expanded(self, causal, tiles):
        q, k, v, rep = self._make()
        out, lse = _flash_fwd_bhsd(q, k, v, causal, 0.25, tiles=tiles,
                                   interpret=True, q_per_kv=rep)
        ref = _xla_attention_bhsd(q, self._expand(k, rep),
                                  self._expand(v, rep), causal, 0.25)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-5)

    @pytest.mark.parametrize("tiles", [_same(32, 32, 32), _same(32, 64, 16)],
                             ids=["one_sub", "many_sub"])
    @pytest.mark.parametrize("sq", [64, 100])  # 100: 4 tiles + padded tail
    def test_backward_matches_dense_expanded(self, sq, tiles):
        q, k, v, rep = self._make(sq=sq, sk=sq)
        causal, scale = True, 0.25
        out, lse = _flash_fwd_bhsd(q, k, v, causal, scale, tiles=tiles,
                                   interpret=True, q_per_kv=rep)
        g = jnp.ones_like(out)
        dq, dk, dv = _flash_bwd_bhsd(q, k, v, out, lse, g, causal, scale,
                                     tiles=tiles, interpret=True,
                                     q_per_kv=rep)
        assert dk.shape == k.shape and dv.shape == v.shape

        def ref_loss(q_, k_, v_):
            return _xla_attention_bhsd(
                q_, self._expand(k_, rep), self._expand(v_, rep),
                causal, scale).sum()
        rdq, rdk, rdv = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
        np.testing.assert_allclose(np.asarray(dq), np.asarray(rdq),
                                   rtol=2e-3, atol=2e-4)
        np.testing.assert_allclose(np.asarray(dk), np.asarray(rdk),
                                   rtol=2e-3, atol=2e-4)
        np.testing.assert_allclose(np.asarray(dv), np.asarray(rdv),
                                   rtol=2e-3, atol=2e-4)

    def test_bshd_wrapper_gqa_and_ragged(self):
        r = np.random.RandomState(3)
        b, sq, h, kvh, d = 1, 50, 4, 2, 16   # ragged seq: pads internally
        q = jnp.asarray(r.randn(b, sq, h, d), jnp.float32)
        k = jnp.asarray(r.randn(b, sq, kvh, d), jnp.float32)
        v = jnp.asarray(r.randn(b, sq, kvh, d), jnp.float32)
        out = flash_attention_bshd(q, k, v, causal=True)
        assert out.shape == (b, sq, h, d)
        # parity vs expanded-kv wrapper call
        ke = jnp.repeat(k, h // kvh, axis=2)
        ve = jnp.repeat(v, h // kvh, axis=2)
        ref = flash_attention_bshd(q, ke, ve, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-5)


class TestGQAModelPath:
    def test_llama_gqa_trains_and_matches_expanded_sdpa(self):
        import paddle_tpu as paddle
        from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
        cfg = LlamaConfig(vocab_size=64, hidden_size=32,
                          intermediate_size=64, num_hidden_layers=2,
                          num_attention_heads=4, num_key_value_heads=2,
                          max_position_embeddings=32)
        paddle.seed(0)
        model = LlamaForCausalLM(cfg)
        ids = paddle.Tensor(np.random.RandomState(0).randint(
            0, 64, (2, 16)).astype(np.int32))
        loss = model(ids, labels=ids)
        loss = loss[0] if isinstance(loss, (tuple, list)) else loss
        loss.backward()
        kproj = model.llama.layers[0].self_attn.k_proj
        assert kproj.weight.grad is not None
        # kv projection stays at kv-head width (no hidden expansion)
        assert list(kproj.weight.shape)[-1] == 2 * (32 // 4)


class TestProductionKernelSmoke:
    """Tier-1 pin of the PRODUCTION kernel flavor on CPU (ISSUE r6 CI
    satellite): bf16 operands + f32 accumulation at the tiles the chooser
    hands the trainer's head dim (the forward's 1024-row tile under the
    diagonal's mask; the backward's two resident tiles of 512 rows with
    the whole sequence streamed in 512-row sub-blocks, the causal sweep),
    forward AND backward, under TPU interpret mode
    (pltpu.force_tpu_interpret_mode where this jax ships it, else the
    Pallas interpreter — the same kernels either way). chip_smoke.py's
    kernel phase compiles the same kernels (interpret=False) on the
    chip."""

    def test_bf16_fwd_bwd_interpret_mode(self):
        import contextlib
        from jax.experimental.pallas import tpu as pltpu

        ctx = (pltpu.force_tpu_interpret_mode()
               if hasattr(pltpu, "force_tpu_interpret_mode")
               else contextlib.nullcontext())
        with ctx:
            rs = np.random.RandomState(9)
            bh, s, d = 2, 1024, 128    # production tile/lane geometry
            assert choose_tiles(s, s, d, 2) == Tiles(
                fwd=(1024, 1024, 1024), dq=(512, 1024, 512),
                dkv=(512, 1024, 512))
            scale = d ** -0.5
            q = jnp.asarray(rs.randn(bh, s, d), jnp.bfloat16)
            k = jnp.asarray(rs.randn(bh, s, d), jnp.bfloat16)
            v = jnp.asarray(rs.randn(bh, s, d), jnp.bfloat16)
            out, lse = fa._flash_fwd_bhsd(q, k, v, True, scale,
                                          interpret=True)
            assert out.dtype == jnp.bfloat16
            g = jnp.ones_like(out)
            dq, dk, dv = fa._flash_bwd_bhsd(q, k, v, out, lse, g,
                                            True, scale, interpret=True)
            ref = fa._xla_attention_bhsd(q.astype(jnp.float32),
                                         k.astype(jnp.float32),
                                         v.astype(jnp.float32),
                                         True, scale)
            np.testing.assert_allclose(
                np.asarray(out, np.float32), np.asarray(ref),
                rtol=0.06, atol=0.06)

            def ref_loss(q_, k_, v_):
                return jnp.sum(fa._xla_attention_bhsd(
                    q_, k_, v_, True, scale))
            rdq, rdk, rdv = jax.grad(ref_loss, argnums=(0, 1, 2))(
                q.astype(jnp.float32), k.astype(jnp.float32),
                v.astype(jnp.float32))
            for a, b, nm in ((dq, rdq, "dq"), (dk, rdk, "dk"),
                             (dv, rdv, "dv")):
                assert a.dtype == jnp.bfloat16, nm
                np.testing.assert_allclose(
                    np.asarray(a, np.float32), np.asarray(b),
                    rtol=0.1, atol=0.1, err_msg=nm)


class TestTileChooser:
    """One function chooses every kernel's tiles from the shape, and the
    router's Decision carries them with the grid steps they give."""

    TRAIN = (64, 2048, 2048, 128)      # bh, sq, sk, d of the trainer's cell

    def test_train_shape_grid_steps_and_vmem(self):
        from paddle_tpu.ops.pallas import attention_router as ar
        bh, sq, sk, d = self.TRAIN
        ar.clear_routing_cache()
        dec = ar.route(bh, sq, sk, d, "bfloat16", True, platform="tpu")
        assert dec.tiles == choose_tiles(sq, sk, d, 2)
        assert set(dec.grid_steps) == {"fa_fwd", "fa_bwd_dq", "fa_bwd_dkv"}
        # 8,192 a kernel before the two-level tiles
        assert all(n <= 1024 for n in dec.grid_steps.values()), dec
        assert ar.decision_log()[-1][1].grid_steps == dec.grid_steps
        # and the count is the grid of the calls the entry point makes
        q = jax.ShapeDtypeStruct((bh, sq, d), jnp.bfloat16)
        text = str(jax.make_jaxpr(jax.grad(lambda q_, k_, v_: jnp.sum(
            _flash_attention_bhsd(q_, k_, v_, True, 0.1).astype(
                jnp.float32)), argnums=(0, 1, 2)))(q, q, q))
        # each pallas_call prints its grid first and its name last
        grids = re.findall(r"GridMapping\(grid=\(([\d, ]+)\)", text)
        names = re.findall(r"name=(fa_\w+)", text)
        assert dict(zip(names, (int(np.prod([int(n) for n in g.split(",")]))
                                for g in grids))) == dec.grid_steps
        for kind in ("fwd", "dq", "dkv"):
            res, streamed, sub = getattr(dec.tiles, kind)
            assert streamed % sub == 0 and res % 128 == 0 and sub % 128 == 0
            est = fa.vmem_bytes(kind, (res, streamed, sub), d, 2)
            limit = fa._params(kind, (res, streamed, sub), d, 2,
                               ("arbitrary",)).vmem_limit_bytes
            assert est <= fa._VMEM_BUDGET
            assert est < limit <= fa._VMEM_LIMIT_MAX

    @pytest.mark.parametrize("sq,sk", [(16, 16), (100, 260), (200, 200),
                                       (2048 + 128, 2048 + 128)])
    def test_tiny_and_ragged_sequences_clamp(self, sq, sk):
        tiles = choose_tiles(sq, sk, 128, 2)
        for (res, streamed, sub), s_res, s_str in (
                (tiles.fwd, sq, sk), (tiles.dq, sq, sk), (tiles.dkv, sk, sq)):
            # a lane tile at least, and padding never past an eighth of
            # the sequence once a tile is above that minimum
            for tile, s in ((res, s_res), (sub, s_str)):
                assert tile >= 128 and tile % 128 == 0
                pad = -(-s // tile) * tile - s
                assert tile == 128 or pad * 8 <= s
            assert streamed % sub == 0
            assert streamed == -(-s_str // sub) * sub   # whole, it fits

    def test_streamed_tile_shrinks_to_the_vmem_budget(self):
        long = choose_tiles(1 << 17, 1 << 17, 128, 2)
        for kind in ("fwd", "dq", "dkv"):
            tile = getattr(long, kind)
            assert tile[1] < (1 << 17) and tile[1] % tile[2] == 0
            assert fa.vmem_bytes(kind, tile, 128, 2) <= fa._VMEM_BUDGET
        tight = choose_tiles(2048, 2048, 128, 2, vmem_budget=4 << 20)
        assert tight.fwd[1] < 2048

    def test_backward_builds_no_lane_broadcast_scalars(self):
        """lse and delta reach the kernels as rows: nothing of shape
        f32[bh, S, 128] is built in HBM (67 MB each a layer at the train
        shape before)."""
        bh, s, d = 2, 256, 256     # d != 128: delta's dO * O is not it
        q = jnp.zeros((bh, s, d), jnp.bfloat16)
        lse = jnp.zeros((bh, s), jnp.float32)
        # str() prints the nested jaxprs too: the jitted call's and, under
        # the interpreter, the kernels' own
        bwd = str(jax.make_jaxpr(lambda q_, l_: _flash_bwd_bhsd(
            q_, q_, q_, q_, l_, q_, True, 0.1, interpret=True))(q, lse))
        fwd = str(jax.make_jaxpr(lambda q_: _flash_fwd_bhsd(
            q_, q_, q_, True, 0.1, interpret=True))(q))
        for text in (bwd, fwd):
            assert "pallas_call" in text
            assert f"f32[{bh},1,{s}]" in text          # the scalars, as rows
            assert f"f32[{bh},{s},128]" not in text
        # the check sees what it is looking for when it is there
        wide = str(jax.make_jaxpr(lambda l_: jax.jit(
            lambda x: jnp.broadcast_to(x[..., None], (bh, s, 128)))(l_))(lse))
        assert f"f32[{bh},{s},128]" in wide


class TestMeshPartitioning:
    """GSPMD cannot partition a Mosaic kernel (on a TPU the lowering fails
    with "Mosaic kernels cannot be automatically partitioned"), so under
    an ambient multi-device mesh the kernel runs inside a shard_map over
    batch and heads."""

    def test_shard_map_under_ambient_mesh_matches_dense(self):
        from jax.sharding import NamedSharding, PartitionSpec as P
        from paddle_tpu.nn.functional.attention import _xla_attention
        from paddle_tpu.ops.pallas import flash_attention as fa
        from paddle_tpu.parallel import create_mesh

        assert fa._mesh_spec(2, 2, 2) is None          # no ambient mesh
        mesh = create_mesh(mp=2, sharding=2, devices=jax.devices()[:4])
        rs = np.random.RandomState(5)
        q, k, v, g = (_rand(rs, 2, 128, 2, 128) for _ in range(4))

        def loss(q_, k_, v_):
            return jnp.sum(flash_attention_bshd(q_, k_, v_, causal=True) * g)

        def ref_loss(q_, k_, v_):
            return jnp.sum(_xla_attention(q_, k_, v_, causal=True) * g)

        sh = NamedSharding(mesh, P("sharding", None, "mp", None))
        with jax.set_mesh(mesh):
            assert fa._mesh_spec(2, 2, 2) == P(("sharding",), None, "mp",
                                               None)
            # 3 rows do not split over sharding=2; 1 kv head not over mp=2
            assert fa._mesh_spec(3, 2, 1) == P(None, None, None, None)
            assert "shard_map" in str(jax.make_jaxpr(loss)(q, k, v))
            got, grads = jax.jit(jax.value_and_grad(loss, (0, 1, 2)),
                                 in_shardings=(sh, sh, sh))(q, k, v)
        want, rgrads = jax.value_and_grad(ref_loss, (0, 1, 2))(q, k, v)
        np.testing.assert_allclose(float(got), float(want), rtol=1e-4)
        for a, b in zip(grads, rgrads):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-4)
        assert grads[0].sharding.spec == P("sharding", None, "mp", None)


# --------------------------------------------------------------------------
# the window: a band under the causal diagonal
# --------------------------------------------------------------------------

def _dense_band(q, k, v, window, scale, rep=1):
    """Dense masked attention with the band written out, float64-free but
    independent of the kernels: the reference of every windowed test."""
    ke, ve = jnp.repeat(k, rep, 0), jnp.repeat(v, rep, 0)
    sq, sk = q.shape[1], k.shape[1]
    back = (np.arange(sq)[:, None] + (sk - sq)) - np.arange(sk)[None, :]
    keep = (back >= 0) & (back < window)
    s = jnp.einsum("bqd,bkd->bqk", q, ke) * scale
    p = jax.nn.softmax(jnp.where(keep, s, -1e30), axis=-1)
    return jnp.einsum("bqk,bkd->bqd", p, ve)


WINDOW_TILES = {
    # band-relative streamed axis over one-sub-block tiles: the index maps
    # do the skipping
    "one_sub": _same(64, 64, 64),
    # several sub-blocks a tile, sizes all different between kernels: the
    # in-kernel bounds do
    "many_sub": Tiles(fwd=(128, 256, 32), dq=(32, 128, 64),
                      dkv=(64, 256, 128)),
    # the chooser's own
    "chosen": None,
}

# (seq_q, seq_k, window): smaller than a sub-block; between tile sizes;
# a multiple of the tiles; ragged lengths; one key; seq_k > seq_q
# (bottom-right aligned); a window one short of the sequence
WINDOW_CASES = [
    (256, 256, 20), (256, 256, 100), (256, 256, 128), (384, 384, 129),
    (300, 300, 130), (200, 200, 64), (256, 256, 1), (128, 384, 150),
    (100, 260, 70), (256, 256, 255),
]


class TestWindowedFlash:
    @pytest.mark.parametrize("tiles", list(WINDOW_TILES))
    @pytest.mark.parametrize("sq,sk,window", WINDOW_CASES)
    def test_forward_and_every_gradient_match_dense(self, sq, sk, window,
                                                    tiles):
        rs = np.random.RandomState(sq + sk + window)
        bh, d, scale = 2, 16, 0.25
        q, do = _rand(rs, bh, sq, d), _rand(rs, bh, sq, d)
        k, v = _rand(rs, bh, sk, d), _rand(rs, bh, sk, d)
        kw = dict(tiles=WINDOW_TILES[tiles], interpret=True, window=window)
        out, lse = _flash_fwd_bhsd(q, k, v, True, scale, **kw)
        want = _dense_band(q, k, v, window, scale)
        np.testing.assert_allclose(out, want, rtol=2e-5, atol=2e-5)
        grads = _flash_bwd_bhsd(q, k, v, out, lse, do, True, scale, **kw)
        wants = jax.grad(lambda *a: jnp.sum(
            _dense_band(*a, window, scale) * do), (0, 1, 2))(q, k, v)
        for name, g, w in zip(("dq", "dk", "dv"), grads, wants):
            np.testing.assert_allclose(g, w, rtol=5e-5, atol=5e-5,
                                       err_msg=name)

    @pytest.mark.parametrize("window", [24, 64, 200])
    def test_gqa_eight_to_one(self, window):
        rs = np.random.RandomState(window)
        scale = 0.25
        q, do = _rand(rs, 16, 192, 16), _rand(rs, 16, 192, 16)
        k, v = _rand(rs, 2, 192, 16), _rand(rs, 2, 192, 16)
        kw = dict(tiles=_same(64, 128, 64), interpret=True, q_per_kv=8,
                  window=window)
        out, lse = _flash_fwd_bhsd(q, k, v, True, scale, **kw)
        np.testing.assert_allclose(
            out, _dense_band(q, k, v, window, scale, rep=8), rtol=2e-5,
            atol=2e-5)
        grads = _flash_bwd_bhsd(q, k, v, out, lse, do, True, scale, **kw)
        wants = jax.grad(lambda *a: jnp.sum(
            _dense_band(*a, window, scale, rep=8) * do), (0, 1, 2))(q, k, v)
        for name, g, w in zip(("dq", "dk", "dv"), grads, wants):
            np.testing.assert_allclose(g, w, rtol=5e-5, atol=1e-4,
                                       err_msg=name)

    def test_public_entry_point_and_its_vjp(self):
        """(batch, seq, heads, d) through flash_attention_bshd with GQA and
        a head dim that is padded to the lane width."""
        rs = np.random.RandomState(5)
        q = _rand(rs, 2, 160, 4, 24)
        k, v = _rand(rs, 2, 160, 2, 24), _rand(rs, 2, 160, 2, 24)

        def dense(q_, k_, v_):
            qt, kt, vt = (jnp.swapaxes(x, 1, 2).reshape(-1, 160, 24)
                          for x in (q_, k_, v_))
            out = _dense_band(qt, kt, vt, 48, 24 ** -0.5, rep=2)
            return jnp.swapaxes(out.reshape(2, 4, 160, 24), 1, 2)

        def flash(q_, k_, v_):
            return flash_attention_bshd(q_, k_, v_, causal=True, window=48)

        np.testing.assert_allclose(flash(q, k, v), dense(q, k, v),
                                   rtol=2e-5, atol=2e-5)
        got = jax.grad(lambda *a: jnp.sum(jnp.sin(flash(*a))), (0, 1, 2))(
            q, k, v)
        want = jax.grad(lambda *a: jnp.sum(jnp.sin(dense(*a))), (0, 1, 2))(
            q, k, v)
        for g, w in zip(got, want):
            np.testing.assert_allclose(g, w, rtol=5e-5, atol=5e-5)

    @pytest.mark.parametrize("window", [None, 256, 300, 10 ** 6])
    def test_a_window_that_hides_nothing_is_the_causal_program(self, window):
        """window None, equal to and past the sequence: the same kernels
        under the same names, tiles and grids, and the same numbers."""
        q = jax.ShapeDtypeStruct((4, 256, 128), jnp.bfloat16)

        def program(w):
            text = str(jax.make_jaxpr(jax.grad(lambda q_, k_, v_: jnp.sum(
                _flash_attention_bhsd(q_, k_, v_, True, 0.1, 1, w).astype(
                    jnp.float32)), argnums=(0, 1, 2)))(q, q, q))
            return re.sub(r"0x[0-9a-f]+", "", text)

        assert fa.effective_window(window, True, 256) is None
        assert program(window) == program(None)
        assert sorted(set(re.findall(r"name=(fa\w+)", program(window)))) == [
            "fa_bwd_dkv", "fa_bwd_dq", "fa_fwd"]
        assert choose_tiles(256, 256, 128, 2, window=window) == \
            choose_tiles(256, 256, 128, 2)
        assert choose_tiles(2048, 2048, 128, 2, window=2048) == Tiles(
            fwd=(1024, 2048, 1024), dq=(512, 2048, 512),
            dkv=(512, 2048, 512))

    def test_windowed_calls_carry_their_own_kernel_names(self):
        q = jax.ShapeDtypeStruct((4, 512, 128), jnp.bfloat16)
        text = str(jax.make_jaxpr(jax.grad(lambda q_, k_, v_: jnp.sum(
            _flash_attention_bhsd(q_, k_, v_, True, 0.1, 1, 128).astype(
                jnp.float32)), argnums=(0, 1, 2)))(q, q, q))
        names = re.findall(r"name=(fa\w+)", text)
        assert sorted(set(names)) == ["faw_bwd_dkv", "faw_bwd_dq", "faw_fwd"]
        # and the grids are what Tiles.grid_steps reports
        tiles = choose_tiles(512, 512, 128, 2, window=128)
        grids = re.findall(r"GridMapping\(grid=\(([\d, ]+)\)", text)
        assert dict(zip(names, (int(np.prod([int(n) for n in g.split(",")]))
                                for g in grids))) == tiles.grid_steps(
            4, 512, 512, 128)

    def test_a_window_needs_the_causal_mask_and_a_key(self):
        with pytest.raises(ValueError):
            fa.effective_window(64, False, 256)
        with pytest.raises(ValueError):
            fa.effective_window(0, True, 256)
        q = jnp.zeros((1, 128, 2, 16))
        with pytest.raises(ValueError):
            flash_attention_bshd(q, q, q, causal=False, window=8)

    @pytest.mark.parametrize("seq", [2048, 4096, 16384, 20000])
    @pytest.mark.parametrize("window", [100, 1024, 1500])
    def test_visited_sub_blocks_are_bounded_by_the_band(self, seq, window):
        """Whatever the length, a resident tile visits no more than the
        band plus one sub-block at each edge: (resident + window) / sub + 2
        sub-blocks, from the bounds the kernels' own loops take; and the
        windowed grids never grow with the square of the length."""
        tiles = choose_tiles(seq, seq, 128, 2, window=window)
        for tile, bounds in ((tiles.fwd, fa._key_bounds),
                             (tiles.dq, fa._key_bounds),
                             (tiles.dkv, fa._query_bounds)):
            res, streamed, sub = tile
            n_sub = streamed // sub
            limit = (res + window) / sub + 2
            for i in range(-(-seq // res)):
                visited = 0
                for j in range(-(-seq // streamed)):
                    lo, a, b, hi = bounds(i * res, res, j * streamed, sub,
                                          n_sub, True, 0, seq, window)
                    assert 0 <= lo <= a <= b <= hi <= n_sub
                    visited += hi - lo
                assert 1 <= visited <= limit, (tile, i, visited)
        visited = tiles.visited_pairs(seq, seq, window)
        needed = fa.band_pairs(seq, seq, window)
        assert set(visited) == {"faw_fwd", "faw_bwd_dq", "faw_bwd_dkv"}
        assert all(needed <= v for v in visited.values())
        steps = tiles.grid_steps(1, seq, seq, window)
        for name, tile in (("faw_fwd", tiles.fwd), ("faw_bwd_dq", tiles.dq),
                           ("faw_bwd_dkv", tiles.dkv)):
            per_resident = steps[name] / -(-seq // tile[0])
            assert per_resident <= (tile[0] + window) / tile[1] + 2

    def test_band_pairs_and_the_cells_counts(self):
        assert fa.band_pairs(16384, 16384, 1024) == 16_253_440
        assert fa.band_pairs(16384, 16384) == 134_225_920
        assert fa.band_pairs(4, 6, 2) == 8          # offset 2: two keys each
        assert fa.band_pairs(4, 6) == 3 + 4 + 5 + 6
        # visited by the causal schedule without a window: the triangle at
        # sub-block granularity, more than eight times the band's needs
        causal = choose_tiles(16384, 16384, 128, 2).visited_pairs(
            16384, 16384)
        assert min(causal.values()) > 8 * 16_253_440
        windowed = choose_tiles(16384, 16384, 128, 2, window=1024
                                ).visited_pairs(16384, 16384, 1024)
        assert max(windowed.values()) < 2.1 * 16_253_440

    def test_dense_fallback_applies_the_band(self):
        from paddle_tpu.nn.functional.attention import attention_bshd
        rs = np.random.RandomState(2)
        q = _rand(rs, 2, 48, 4, 8)
        k, v = _rand(rs, 2, 48, 1, 8), _rand(rs, 2, 48, 1, 8)
        got = attention_bshd(q, k, v, is_causal=True, window=5)  # off-TPU
        qt = jnp.swapaxes(q, 1, 2).reshape(8, 48, 8)
        kt, vt = (jnp.swapaxes(x, 1, 2).reshape(2, 48, 8) for x in (k, v))
        want = _dense_band(qt, kt, vt, 5, 8 ** -0.5, rep=4)
        np.testing.assert_allclose(
            got, jnp.swapaxes(want.reshape(2, 4, 48, 8), 1, 2), rtol=2e-5,
            atol=2e-5)
        with pytest.raises(ValueError):
            attention_bshd(q, k, v, is_causal=False, window=5)
