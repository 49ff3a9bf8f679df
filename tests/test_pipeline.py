"""Compiled pipeline-parallel tests (pp over CPU mesh)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

import paddle_tpu as paddle
from paddle_tpu import optimizer
from paddle_tpu.parallel.pipeline import (
    OneFOneBPipeline, PipelinedLM, ZeroBubblePipeline,
    pipeline_forward_interleaved)
from paddle_tpu.parallel.llama_pipeline import LlamaPipeRunner
from jax.sharding import PartitionSpec as P


class TestPipelineForward:
    def _setup(self, pstages=4, m=4):
        mesh = Mesh(np.asarray(jax.devices()[:pstages]), ("pp",))
        rs = np.random.RandomState(0)
        V, D = 64, 32
        embed_w = jnp.asarray(rs.randn(V, D).astype(np.float32) * 0.1)
        stage_w = jnp.asarray(rs.randn(pstages, D, D).astype(np.float32) * 0.1)
        head_w = jnp.asarray(rs.randn(D, V).astype(np.float32) * 0.1)

        def embed_fn(p, tok):
            return p[tok]

        def stage_fn(p, h):
            return jnp.tanh(h @ p) + h

        def head_loss_fn(p, h, lab):
            lp = jax.nn.log_softmax(h @ p, -1)
            return -jnp.mean(jnp.take_along_axis(lp, lab[..., None], -1))

        plm = PipelinedLM(mesh, embed_fn, stage_fn, head_loss_fn,
                          num_microbatches=m)
        return plm, embed_w, stage_w, head_w, stage_fn, head_loss_fn, rs

    def test_matches_sequential(self):
        plm, ew, sw, hw, stage_fn, head_loss_fn, rs = self._setup()
        loss_fn = plm.loss_fn()
        tok = jnp.asarray(rs.randint(0, 64, (8, 16)), jnp.int32)
        lab = jnp.asarray(rs.randint(0, 64, (8, 16)), jnp.int32)
        pl = float(jax.jit(loss_fn)(ew, sw, hw, tok, lab))
        h = ew[tok]
        for i in range(4):
            h = stage_fn(sw[i], h)
        ref = float(head_loss_fn(hw, h, lab))
        assert abs(pl - ref) < 1e-4

    def test_grads_match_sequential(self):
        plm, ew, sw, hw, stage_fn, head_loss_fn, rs = self._setup()
        loss_fn = plm.loss_fn()
        tok = jnp.asarray(rs.randint(0, 64, (8, 16)), jnp.int32)
        lab = jnp.asarray(rs.randint(0, 64, (8, 16)), jnp.int32)
        g = jax.jit(jax.grad(loss_fn, argnums=(0, 1, 2)))(ew, sw, hw, tok, lab)

        def ref(ew_, sw_, hw_):
            h = ew_[tok]
            for i in range(4):
                h = stage_fn(sw_[i], h)
            return head_loss_fn(hw_, h, lab)

        gr = jax.grad(ref, argnums=(0, 1, 2))(ew, sw, hw)
        for a, b in zip(g, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-6)


def _toy(pstages, seed=0):
    """Shared toy LM pieces: embed -> pstages residual stages -> softmax."""
    mesh = Mesh(np.asarray(jax.devices()[:pstages]), ("pp",))
    rs = np.random.RandomState(seed)
    V, D = 64, 32
    embed_w = jnp.asarray(rs.randn(V, D).astype(np.float32) * 0.1)
    stage_w = jnp.asarray(rs.randn(pstages, D, D).astype(np.float32) * 0.1)
    head_w = jnp.asarray(rs.randn(D, V).astype(np.float32) * 0.1)

    def embed_fn(p, tok):
        return p[tok]

    def stage_fn(p, h):
        return jnp.tanh(h @ p) + h

    def head_loss_fn(p, h, lab):
        lp = jax.nn.log_softmax(h @ p, -1)
        return -jnp.mean(jnp.take_along_axis(lp, lab[..., None], -1))

    return mesh, embed_w, stage_w, head_w, embed_fn, stage_fn, head_loss_fn, rs


class Test1F1BPipeline:
    """The hand-scheduled 1F1B backward must match the sequential reference
    at the same bar the fill-drain autodiff path passes."""

    @pytest.mark.parametrize("p,m", [(4, 4), (4, 8), (2, 4)])
    def test_grads_match_sequential(self, p, m):
        (mesh, ew, sw, hw, embed_fn, stage_fn, head_loss_fn,
         rs) = _toy(p)
        pipe = OneFOneBPipeline(mesh, embed_fn, stage_fn, head_loss_fn,
                                num_microbatches=m)
        gf = jax.jit(pipe.loss_and_grad_fn())
        tok = jnp.asarray(rs.randint(0, 64, (8, 16)), jnp.int32)
        lab = jnp.asarray(rs.randint(0, 64, (8, 16)), jnp.int32)
        loss, demb, dstage, dhead = gf(ew, sw, hw, tok, lab)

        def ref(ew_, sw_, hw_):
            h = ew_[tok]
            for i in range(p):
                h = stage_fn(sw_[i], h)
            return head_loss_fn(hw_, h, lab)

        rl, rg = jax.value_and_grad(ref, argnums=(0, 1, 2))(ew, sw, hw)
        assert abs(float(loss) - float(rl)) < 1e-5
        for a, b in zip((demb, dstage, dhead), rg):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-6)

    def test_tied_embed_cotangent_flows(self):
        """With tied_embed, the head's use of the embedding weight must
        contribute to demb (reference SharedLayerDesc, pp_layers.py:76)."""
        (mesh, ew, sw, hw, embed_fn, stage_fn, _,
         rs) = _toy(4)

        def head_loss_tied(hp, ep, h, lab):
            lp = jax.nn.log_softmax((h * hp[None, None]) @ ep.T, -1)
            return -jnp.mean(jnp.take_along_axis(lp, lab[..., None], -1))

        gain = jnp.ones((32,), jnp.float32)
        pipe = OneFOneBPipeline(mesh, embed_fn, stage_fn, head_loss_tied,
                                num_microbatches=4, tied_embed=True)
        gf = jax.jit(pipe.loss_and_grad_fn())
        tok = jnp.asarray(rs.randint(0, 64, (8, 16)), jnp.int32)
        lab = jnp.asarray(rs.randint(0, 64, (8, 16)), jnp.int32)
        loss, demb, dstage, dhead = gf(ew, sw, gain, tok, lab)

        def ref(ew_, sw_, hp_):
            h = ew_[tok]
            for i in range(4):
                h = stage_fn(sw_[i], h)
            return head_loss_tied(hp_, ew_, h, lab)

        rl, rg = jax.value_and_grad(ref, argnums=(0, 1, 2))(ew, sw, gain)
        assert abs(float(loss) - float(rl)) < 1e-5
        np.testing.assert_allclose(np.asarray(demb), np.asarray(rg[0]),
                                   rtol=1e-4, atol=1e-6)
        np.testing.assert_allclose(np.asarray(dstage), np.asarray(rg[1]),
                                   rtol=1e-4, atol=1e-6)
        np.testing.assert_allclose(np.asarray(dhead), np.asarray(rg[2]),
                                   rtol=1e-4, atol=1e-6)

    @pytest.mark.parametrize("p,m", [(4, 4), (4, 8), (2, 4), (4, 2)])
    def test_zero_bubble_grads_match_sequential(self, p, m):
        """The deferred-wgrad (ZB) schedule must hit the same parity bar as
        1F1B — dX-only ticks + one post-scan batched weight vjp."""
        (mesh, ew, sw, hw, embed_fn, stage_fn, head_loss_fn,
         rs) = _toy(p)
        pipe = ZeroBubblePipeline(mesh, embed_fn, stage_fn, head_loss_fn,
                                  num_microbatches=m)
        gf = jax.jit(pipe.loss_and_grad_fn())
        tok = jnp.asarray(rs.randint(0, 64, (8, 16)), jnp.int32)
        lab = jnp.asarray(rs.randint(0, 64, (8, 16)), jnp.int32)
        loss, demb, dstage, dhead = gf(ew, sw, hw, tok, lab)

        def ref(ew_, sw_, hw_):
            h = ew_[tok]
            for i in range(p):
                h = stage_fn(sw_[i], h)
            return head_loss_fn(hw_, h, lab)

        rl, rg = jax.value_and_grad(ref, argnums=(0, 1, 2))(ew, sw, hw)
        assert abs(float(loss) - float(rl)) < 1e-5
        for a, b in zip((demb, dstage, dhead), rg):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-6)

    def test_zero_bubble_tied_embed(self):
        (mesh, ew, sw, hw, embed_fn, stage_fn, _, rs) = _toy(4)

        def head_loss_tied(hp, ep, h, lab):
            lp = jax.nn.log_softmax((h * hp[None, None]) @ ep.T, -1)
            return -jnp.mean(jnp.take_along_axis(lp, lab[..., None], -1))

        gain = jnp.ones((32,), jnp.float32)
        pipe = ZeroBubblePipeline(mesh, embed_fn, stage_fn, head_loss_tied,
                                  num_microbatches=4, tied_embed=True)
        gf = jax.jit(pipe.loss_and_grad_fn())
        tok = jnp.asarray(rs.randint(0, 64, (8, 16)), jnp.int32)
        lab = jnp.asarray(rs.randint(0, 64, (8, 16)), jnp.int32)
        loss, demb, dstage, dhead = gf(ew, sw, gain, tok, lab)

        def ref(ew_, sw_, hp_):
            h = ew_[tok]
            for i in range(4):
                h = stage_fn(sw_[i], h)
            return head_loss_tied(hp_, ew_, h, lab)

        rl, rg = jax.value_and_grad(ref, argnums=(0, 1, 2))(ew, sw, gain)
        assert abs(float(loss) - float(rl)) < 1e-5
        for a, b in zip((demb, dstage, dhead), rg):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-6)

    def test_peak_memory_beats_fill_drain_at_many_microbatches(self):
        """1F1B keeps O(P) live activations vs fill-drain's O(M): at m >> p
        the compiled program's temp allocation must be smaller."""
        p, m = 4, 32
        (mesh, ew, sw, hw, embed_fn, stage_fn, head_loss_fn,
         _) = _toy(p)
        rs = np.random.RandomState(1)
        tok = jnp.asarray(rs.randint(0, 64, (m, 64)), jnp.int32)
        lab = jnp.asarray(rs.randint(0, 64, (m, 64)), jnp.int32)

        pipe = OneFOneBPipeline(mesh, embed_fn, stage_fn, head_loss_fn,
                                num_microbatches=m)
        c_1f1b = jax.jit(pipe.loss_and_grad_fn()).lower(
            ew, sw, hw, tok, lab).compile()

        plm = PipelinedLM(mesh, embed_fn, stage_fn, head_loss_fn,
                          num_microbatches=m, remat=False)
        loss_fn = plm.loss_fn()
        c_fd = jax.jit(jax.value_and_grad(loss_fn, argnums=(0, 1, 2))).lower(
            ew, sw, hw, tok, lab).compile()
        try:
            m1 = c_1f1b.memory_analysis()
            m2 = c_fd.memory_analysis()
            t1, t2 = m1.temp_size_in_bytes, m2.temp_size_in_bytes
        except Exception as e:  # pragma: no cover - backend support varies
            pytest.skip(f"memory_analysis unavailable on this backend: {e}")
        assert t1 < t2, (t1, t2)


class TestInterleavedPipeline:
    """VPP forward (pipeline_forward_interleaved): outputs and autodiff
    grads must match the sequential composition of all P*V chunks."""

    @pytest.mark.parametrize("v,m_mult", [(2, 2), (2, 4), (3, 2)])
    def test_matches_sequential(self, v, m_mult):
        p = 4
        m = m_mult * p
        mesh = Mesh(np.asarray(jax.devices()[:p]), ("pp",))
        rs = np.random.RandomState(0)
        D = 16
        # chunk weights: (p, v, D, D); virtual stage order is c*P + s
        cw = jnp.asarray(rs.randn(p, v, D, D).astype(np.float32) * 0.1)
        x = jnp.asarray(rs.randn(m, 4, D).astype(np.float32))

        def stage_fn(w, h):
            return jnp.tanh(h @ w) + h

        def run(cw_, x_):
            def inner(cw_l, x_l):
                out = pipeline_forward_interleaved(
                    stage_fn, cw_l, x_l, "pp", p_size=p, num_chunks=v,
                    remat=False)
                return out[None]  # (1, M, mb, D): valid on last stage only
            stacked = jax.shard_map(
                inner, mesh=mesh,
                in_specs=(P("pp"), P()), out_specs=P("pp"))(cw_, x_)
            return stacked[-1]

        out = jax.jit(run)(cw, x)

        def seq(cw_, x_):
            h = x_
            for c in range(v):
                for s in range(p):
                    h = stage_fn(cw_[s, c], h)
            return h

        ref = seq(cw, x)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=1e-5)

        # autodiff grads through the interleaved schedule
        def loss_pipe(cw_):
            return jnp.mean(run(cw_, x) ** 2)

        def loss_seq(cw_):
            return jnp.mean(seq(cw_, x) ** 2)

        g = jax.jit(jax.grad(loss_pipe))(cw)
        gr = jax.grad(loss_seq)(cw)
        np.testing.assert_allclose(np.asarray(g), np.asarray(gr),
                                   rtol=1e-4, atol=1e-6)

    def test_rejects_bad_microbatch_count(self):
        p, v = 4, 2
        mesh = Mesh(np.asarray(jax.devices()[:p]), ("pp",))
        cw = jnp.zeros((p, v, 8, 8), jnp.float32)
        x = jnp.zeros((6, 2, 8), jnp.float32)  # 6 % 4 != 0

        def stage_fn(w, h):
            return h @ w

        with pytest.raises(ValueError, match="microbatches"):
            def inner(cw_l, x_l):
                return pipeline_forward_interleaved(
                    stage_fn, cw_l, x_l, "pp", p_size=p, num_chunks=v)[None]
            jax.shard_map(inner, mesh=mesh, in_specs=(P("pp"), P()),
                          out_specs=P("pp"))(cw, x)


class TestLlamaPipeline:
    def test_matches_eager_and_trains(self):
        paddle.seed(0)
        model = paddle.models.llama_tiny(num_hidden_layers=4)
        mesh = Mesh(np.asarray(jax.devices()[:4]), ("pp",))
        opt = optimizer.AdamW(1e-3, parameters=model.parameters())
        runner = LlamaPipeRunner(model, mesh, num_microbatches=2, optimizer=opt)
        ids = jnp.asarray(np.random.RandomState(0).randint(0, 512, (4, 16)),
                          jnp.int32)
        pl = float(runner.loss(ids, ids))
        el, _ = model(paddle.Tensor(ids), labels=paddle.Tensor(ids))
        assert abs(pl - float(el)) < 1e-4
        losses = [float(runner.step(ids, ids)) for _ in range(3)]
        assert losses[-1] < losses[0]

    def test_pp_with_dp_batch_axis(self):
        paddle.seed(0)
        model = paddle.models.llama_tiny(num_hidden_layers=2)
        mesh = Mesh(np.asarray(jax.devices()[:4]).reshape(2, 2), ("pp", "dp"))
        opt = optimizer.AdamW(1e-3, parameters=model.parameters())
        runner = LlamaPipeRunner(model, mesh, num_microbatches=2,
                                 batch_axis="dp", optimizer=opt)
        ids = jnp.asarray(np.random.RandomState(0).randint(0, 512, (8, 16)),
                          jnp.int32)
        pl = float(runner.loss(ids, ids))
        el, _ = model(paddle.Tensor(ids), labels=paddle.Tensor(ids))
        assert abs(pl - float(el)) < 1e-3
        losses = [float(runner.step(ids, ids)) for _ in range(3)]
        assert losses[-1] < losses[0]

    def test_1f1b_schedule_matches_eager_and_trains(self):
        paddle.seed(0)
        model = paddle.models.llama_tiny(num_hidden_layers=4)
        mesh = Mesh(np.asarray(jax.devices()[:4]), ("pp",))
        opt = optimizer.AdamW(1e-3, parameters=model.parameters())
        runner = LlamaPipeRunner(model, mesh, num_microbatches=4,
                                 optimizer=opt, schedule="1F1B")
        ids = jnp.asarray(np.random.RandomState(0).randint(0, 512, (4, 16)),
                          jnp.int32)
        pl = float(runner.loss(ids, ids))
        el, _ = model(paddle.Tensor(ids), labels=paddle.Tensor(ids))
        assert abs(pl - float(el)) < 1e-4
        losses = [float(runner.step(ids, ids)) for _ in range(3)]
        assert losses[-1] < losses[0]

    def test_1f1b_tied_embeddings(self):
        paddle.seed(0)
        model = paddle.models.llama_tiny(num_hidden_layers=2,
                                         tie_word_embeddings=True)
        mesh = Mesh(np.asarray(jax.devices()[:2]), ("pp",))
        opt = optimizer.AdamW(1e-3, parameters=model.parameters())
        runner = LlamaPipeRunner(model, mesh, num_microbatches=2,
                                 optimizer=opt, schedule="1F1B")
        ids = jnp.asarray(np.random.RandomState(0).randint(0, 512, (4, 16)),
                          jnp.int32)
        pl = float(runner.loss(ids, ids))
        el, _ = model(paddle.Tensor(ids), labels=paddle.Tensor(ids))
        assert abs(pl - float(el)) < 1e-4
        losses = [float(runner.step(ids, ids)) for _ in range(3)]
        assert losses[-1] < losses[0]

    def test_tied_embeddings_requires_1f1b(self):
        paddle.seed(0)
        model = paddle.models.llama_tiny(num_hidden_layers=2,
                                         tie_word_embeddings=True)
        mesh = Mesh(np.asarray(jax.devices()[:2]), ("pp",))
        with pytest.raises(NotImplementedError, match="1F1B"):
            LlamaPipeRunner(model, mesh, num_microbatches=2)

    def test_1f1b_with_dp_batch_axis(self):
        paddle.seed(0)
        model = paddle.models.llama_tiny(num_hidden_layers=2)
        mesh = Mesh(np.asarray(jax.devices()[:4]).reshape(2, 2), ("pp", "dp"))
        opt = optimizer.AdamW(1e-3, parameters=model.parameters())
        runner = LlamaPipeRunner(model, mesh, num_microbatches=2,
                                 batch_axis="dp", optimizer=opt,
                                 schedule="1F1B")
        ids = jnp.asarray(np.random.RandomState(0).randint(0, 512, (8, 16)),
                          jnp.int32)
        pl = float(runner.loss(ids, ids))
        el, _ = model(paddle.Tensor(ids), labels=paddle.Tensor(ids))
        assert abs(pl - float(el)) < 1e-3
        losses = [float(runner.step(ids, ids)) for _ in range(3)]
        assert losses[-1] < losses[0]


    def test_vpp_schedule_matches_eager_and_trains(self):
        """VPP through the runner: p=2 stages x 2 chunks over 4 layers —
        loss parity with the sequential model and training decreases it.
        reference: PipelineParallelWithInterleave (pipeline_parallel.py:1174)."""
        paddle.seed(0)
        model = paddle.models.llama_tiny(num_hidden_layers=4)
        mesh = Mesh(np.asarray(jax.devices()[:2]), ("pp",))
        opt = optimizer.AdamW(1e-3, parameters=model.parameters())
        runner = LlamaPipeRunner(model, mesh, num_microbatches=2,
                                 optimizer=opt, schedule="VPP", num_chunks=2)
        ids = jnp.asarray(np.random.RandomState(0).randint(0, 512, (4, 16)),
                          jnp.int32)
        pl = float(runner.loss(ids, ids))
        el, _ = model(paddle.Tensor(ids), labels=paddle.Tensor(ids))
        assert abs(pl - float(el)) < 1e-4
        losses = [float(runner.step(ids, ids)) for _ in range(3)]
        assert losses[-1] < losses[0]

    def test_vpp_grads_match_sequential(self):
        """Autodiff grads through the interleaved runner must match
        differentiating the sequential model (same params)."""
        paddle.seed(0)
        model = paddle.models.llama_tiny(num_hidden_layers=4)
        mesh = Mesh(np.asarray(jax.devices()[:2]), ("pp",))
        runner = LlamaPipeRunner(model, mesh, num_microbatches=2,
                                 schedule="VPP", num_chunks=2)
        ids = jnp.asarray(np.random.RandomState(1).randint(0, 512, (4, 16)),
                          jnp.int32)
        loss_fn = runner._loss_fn
        g = jax.grad(lambda ep, sp, hp: loss_fn(ep, sp, hp, ids, ids),
                     argnums=(0, 1, 2))(
            runner.embed_params, runner.stage_params, runner.head_params)

        # sequential reference grads via the eager tape
        el, _ = model(paddle.Tensor(ids), labels=paddle.Tensor(ids))
        el.backward()
        eg = {k: np.asarray(p.grad._data)
              for k, p in model.named_parameters() if p.grad is not None}
        np.testing.assert_allclose(
            np.asarray(g[0]["weight"]), eg["llama.embed_tokens.weight"],
            rtol=1e-4, atol=1e-5)
        # one stage-param check: layer 0 q_proj lives at [s=0, c=0, j=0]
        got = np.asarray(g[1]["self_attn.q_proj.weight"])[0, 0, 0]
        np.testing.assert_allclose(
            got, eg["llama.layers.0.self_attn.q_proj.weight"],
            rtol=1e-4, atol=1e-5)
        # layer index mapping: virtual stage vs=c*p+s, layer (vs)*Lv + j;
        # [s=1, c=1, j=0] -> vs=3 -> layer 3
        got3 = np.asarray(g[1]["self_attn.q_proj.weight"])[1, 1, 0]
        np.testing.assert_allclose(
            got3, eg["llama.layers.3.self_attn.q_proj.weight"],
            rtol=1e-4, atol=1e-5)

    def test_vpp_rejects_bad_chunking(self):
        paddle.seed(0)
        model = paddle.models.llama_tiny(num_hidden_layers=2)
        mesh = Mesh(np.asarray(jax.devices()[:2]), ("pp",))
        with pytest.raises(AssertionError, match="num_chunks"):
            LlamaPipeRunner(model, mesh, num_microbatches=2,
                            schedule="VPP", num_chunks=2)


    def test_fthenb_grads_match_eager_all_stages(self):
        """Regression: functional_call used to wrap activations with
        stop_gradient=True, planting a lax.stop_gradient barrier at every
        stage boundary — only the LAST stage (and head) trained; embed and
        stage-0 grads were silently zero. All groups must match eager."""
        paddle.seed(0)
        model = paddle.models.llama_tiny(num_hidden_layers=4)
        mesh = Mesh(np.asarray(jax.devices()[:2]), ("pp",))
        runner = LlamaPipeRunner(model, mesh, num_microbatches=2,
                                 schedule="FThenB")
        ids = jnp.asarray(np.random.RandomState(1).randint(0, 512, (4, 16)),
                          jnp.int32)
        g = jax.jit(jax.grad(
            lambda ep, sp, hp: runner._loss_fn(ep, sp, hp, ids, ids),
            argnums=(0, 1)))(runner.embed_params, runner.stage_params,
                             runner.head_params)
        el, _ = model(paddle.Tensor(ids), labels=paddle.Tensor(ids))
        el.backward()
        eg_emb = np.asarray(model.llama.embed_tokens.weight.grad._data)
        np.testing.assert_allclose(np.asarray(g[0]["weight"]), eg_emb,
                                   rtol=1e-4, atol=1e-6)
        gq = np.asarray(g[1]["self_attn.q_proj.weight"])
        for stage, layer in ((0, 0), (1, 2)):
            ref = np.asarray(model.llama.layers[layer]
                             .self_attn.q_proj.weight.grad._data)
            np.testing.assert_allclose(gq[stage, 0], ref,
                                       rtol=1e-4, atol=1e-6,
                                       err_msg=f"stage {stage}")


    def test_1f1b_grads_match_eager_all_stages(self):
        """End-to-end llama 1F1B gradient parity vs the eager model: every
        group (embedding, both stages, head) must match — guards the
        functional_call stop-gradient regression on the hand-scheduled
        backward too."""
        paddle.seed(0)
        model = paddle.models.llama_tiny(num_hidden_layers=4)
        mesh = Mesh(np.asarray(jax.devices()[:2]), ("pp",))
        runner = LlamaPipeRunner(model, mesh, num_microbatches=2,
                                 schedule="1F1B")
        ids = jnp.asarray(np.random.RandomState(1).randint(0, 512, (4, 16)),
                          jnp.int32)
        loss, demb, dstage, dhead = jax.jit(runner._grads_fn)(
            runner.embed_params, runner.stage_params, runner.head_params,
            ids, ids)
        el, _ = model(paddle.Tensor(ids), labels=paddle.Tensor(ids))
        el.backward()
        assert abs(float(loss) - float(el)) < 1e-4
        np.testing.assert_allclose(
            np.asarray(demb["weight"]),
            np.asarray(model.llama.embed_tokens.weight.grad._data),
            rtol=1e-4, atol=1e-6)
        gq = np.asarray(dstage["self_attn.q_proj.weight"])
        for stage, layer in ((0, 0), (1, 2)):
            ref = np.asarray(model.llama.layers[layer]
                             .self_attn.q_proj.weight.grad._data)
            np.testing.assert_allclose(gq[stage, 0], ref, rtol=1e-4,
                                       atol=1e-6, err_msg=f"stage {stage}")
        np.testing.assert_allclose(
            np.asarray(dhead["lm_head"]),
            np.asarray(model.lm_head.weight.grad._data),
            rtol=1e-4, atol=1e-6)
