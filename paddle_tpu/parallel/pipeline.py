"""Compiled pipeline parallelism over the 'pp' mesh axis.

reference capability: fleet PipelineParallel 1F1B/interleaved schedules
(python/paddle/distributed/fleet/meta_parallel/pipeline_parallel.py:575,
pp_utils/p2p_communication.py) and the static pipeline passes
(passes/pipeline_scheduler_pass: FThenB/1F1B/VPP/ZB).

TPU-native design: no per-stage OS processes, no NCCL p2p, no interceptor
actors. The schedule is a lax.scan whose step does
    receive(prev activation via lax.ppermute) → stage_fn → send
inside one shard_map over 'pp'. Stage weights are a stacked array with the
leading (stage) dim sharded on 'pp', so every device runs the same program
on its own stage slice — SPMD pipelining. Autodiff through scan+ppermute
yields the backward pipeline automatically (fill-drain / GPipe semantics;
1F1B's memory shape comes from per-microbatch remat, see `remat`).

Bubble fraction = (P-1)/(M+P-1), identical to the reference's FThenB.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["pipeline_forward", "pipeline_1f1b_grads", "PipelinedLM",
           "OneFOneBPipeline", "ZeroBubblePipeline",
           "InterleavedPipelinedLM"]


def _pvary(x, axes):
    if isinstance(axes, str):
        axes = (axes,)

    def leaf(a):
        missing = tuple(ax for ax in axes if ax not in jax.typeof(a).vma)
        return jax.lax.pcast(a, missing, to="varying") if missing else a
    return jax.tree_util.tree_map(leaf, x)


def pipeline_forward(stage_fn: Callable, stacked_stage_params, inputs_mb,
                     axis_name: str = "pp", *, p_size: int, remat: bool = True,
                     vary_axes=None):
    """Run the fill-drain pipeline INSIDE an existing shard_map region.

    stage_fn(local_stage_params, h) -> h   (homogeneous stages)
    stacked_stage_params: pytree whose leaves have local leading dim 1
        (the stage shard; squeezed before stage_fn)
    inputs_mb: (M, mb, ...) microbatched activations, replicated.
    p_size: static pipeline depth (mesh.shape[axis_name]).
    Returns (M, mb, ...) outputs, valid on the LAST stage (zeros elsewhere).
    """
    my_stage = jax.lax.axis_index(axis_name)
    vary = tuple(vary_axes) if vary_axes else (axis_name,)
    m = inputs_mb.shape[0]
    local_params = jax.tree_util.tree_map(lambda a: a[0], stacked_stage_params)

    fn = stage_fn
    if remat:
        fn = jax.checkpoint(stage_fn)

    perm_fwd = [(i, i + 1) for i in range(p_size - 1)]

    steps = m + p_size - 1
    h0 = jnp.zeros_like(inputs_mb[0])
    out_buf = jnp.zeros((m,) + inputs_mb.shape[1:], inputs_mb.dtype)
    h0 = _pvary(h0, vary)
    out_buf = _pvary(out_buf, vary)

    def step(carry, t):
        recv, outs = carry
        # stage 0 ingests microbatch t (when in range); others use received
        mb_idx = jnp.clip(t, 0, m - 1)
        inp = jnp.where(my_stage == 0,
                        _pvary(inputs_mb[mb_idx], vary), recv)
        h = fn(local_params, inp)
        # own microbatch index at this tick: t - my_stage
        own = t - my_stage
        valid = (own >= 0) & (own < m)
        h = jnp.where(valid, h, jnp.zeros_like(h))
        # last stage records its finished microbatch
        outs = jnp.where((my_stage == p_size - 1) & valid,
                         outs.at[jnp.clip(own, 0, m - 1)].set(h), outs)
        # everyone ships to the next stage (last stage's send is dropped)
        sent = jax.lax.ppermute(h, axis_name, perm_fwd)
        return (sent, outs), None

    (_, out_buf), _ = jax.lax.scan(step, (h0, out_buf), jnp.arange(steps))
    return out_buf


def pipeline_forward_interleaved(stage_fn: Callable, stacked_chunk_params,
                                 inputs_mb, axis_name: str = "pp", *,
                                 p_size: int, num_chunks: int,
                                 remat: bool = True, vary_axes=None):
    """Interleaved (VPP) forward schedule inside an existing shard_map.

    reference semantics: PipelineParallelWithInterleave
    (fleet/meta_parallel/pipeline_parallel.py:1174) — each physical stage s
    holds `num_chunks` model chunks (virtual stages v = c*P + s), so the
    pipeline fill is P-1 ticks of V× smaller chunks: relative bubble shrinks
    by the chunk count. Schedule (local time u = t - s, groups of P
    microbatches): chunk c = (u//P) % V, microbatch i = (u//(V*P))*P + u%P.
    Activations flow s→s+1 within a chunk and wrap P-1→0 between chunks.

    stacked_chunk_params leaves: local shape (1, V, ...) — the (stage,
    chunk) shard. inputs_mb: (M, mb, ...), M % P == 0. Returns (M, mb, ...)
    valid on the last stage. Backward comes from autodiff of the scan
    (fill-drain memory; use the 1F1B schedule for the O(P) memory bound).
    """
    my_stage = jax.lax.axis_index(axis_name)
    vary = tuple(vary_axes) if vary_axes else (axis_name,)
    m = inputs_mb.shape[0]
    p = p_size
    v = num_chunks
    if m % p != 0:
        raise ValueError(f"interleaved schedule needs microbatches {m} % "
                         f"pp {p} == 0")
    local_params = jax.tree_util.tree_map(
        lambda a: _pvary(a[0], vary), stacked_chunk_params)

    fn = stage_fn
    if remat:
        fn = jax.checkpoint(stage_fn)

    # s -> s+1 within a chunk, P-1 -> 0 wrap between chunks
    perm = [(i, i + 1) for i in range(p - 1)] + [(p - 1, 0)]

    n_groups = m // p
    steps = n_groups * v * p + (p - 1) + (v - 1) * p
    h0 = _pvary(jnp.zeros_like(inputs_mb[0]), vary)
    out_buf = _pvary(jnp.zeros((m,) + inputs_mb.shape[1:], inputs_mb.dtype),
                     vary)

    def step(carry, t):
        recv, outs = carry
        u = t - my_stage
        uc = jnp.clip(u, 0, steps)
        c = (uc // p) % v                      # chunk index
        i = (uc // (v * p)) * p + uc % p       # microbatch index
        valid = (u >= 0) & (i < m)
        first_virtual = (my_stage == 0) & (c == 0)
        inp = jnp.where(first_virtual,
                        _pvary(inputs_mb[jnp.clip(i, 0, m - 1)], vary), recv)
        inp = jnp.where(valid, inp, jnp.zeros_like(inp))
        chunk_params = jax.tree_util.tree_map(
            lambda a: jax.lax.dynamic_index_in_dim(a, c, 0, keepdims=False),
            local_params)
        h = fn(chunk_params, inp)
        h = jnp.where(valid, h, jnp.zeros_like(h))
        last_virtual = (my_stage == p - 1) & (c == v - 1)
        outs = jnp.where(last_virtual & valid,
                         outs.at[jnp.clip(i, 0, m - 1)].set(h), outs)
        sent = jax.lax.ppermute(h, axis_name, perm)
        return (sent, outs), None

    (_, out_buf), _ = jax.lax.scan(step, (h0, out_buf), jnp.arange(steps))
    return out_buf


def pipeline_1f1b_grads(embed_fn, stage_fn, head_loss_fn, embed_params,
                        stacked_stage_params, head_params, tokens_mb,
                        labels_mb, axis_name: str = "pp", *, p_size: int,
                        num_microbatches: int, vary_axes=None,
                        tied_embed: bool = False,
                        wgrad_deferred: bool = False):
    """1F1B pipeline schedule: hand-scheduled forward AND backward.

    reference semantics: fleet/meta_parallel/pipeline_parallel.py:575
    (forward_backward_pipeline, non-interleaved 1F1B).

    Unlike `pipeline_forward` (fill-drain + autodiff, which keeps all M
    microbatch boundary activations alive for the backward), this runs the
    backward INSIDE the same scan: each tick a stage does one forward
    (microbatch i = t - s) and one backward (microbatch j = t - 2(P-1) + s),
    so at most 2(P-1)+1 stage-input activations are live per stage — the
    1F1B memory bound O(P) instead of O(M). Stage weight gradients are
    accumulated across microbatches; per-microbatch rematerialization comes
    free because the backward recomputes the stage from its saved input.

    Must run inside shard_map over `axis_name`. Returns
    (loss, demb, dstage_local, dhead) — demb/dhead psum'd over pp; the
    caller psums/means over any batch axis.

    With `tied_embed`, head_loss_fn takes (head_params, embed_params, h,
    labels) and its embed-weight cotangent is added into demb — the
    SharedLayerDesc analog (pp_layers.py:76).

    With `wgrad_deferred` (the zero-bubble analog — reference
    passes/pipeline_scheduler_pass/pipeline_zero_bubble.py ZBH1, which
    splits backward into activation-grad B and weight-grad W and moves W
    into bubbles): tick backwards compute ONLY dX (vjp w.r.t. the stage
    input), recording each microbatch's output cotangent; ALL stage weight
    gradients are then one batched vjp after the scans — bubble-free and
    at full-batch matmul shapes (m× larger MXU tiles than per-tick dW).
    TPU-native cost shape (per-stage-forward units F, with per-microbatch
    remat; dX = dW = F): 1F1B pays 4m+4(p-1) serial tick units, deferred-W
    pays 5m+3(p-1) — the post-scan wgrad re-runs the forward once more, so
    it wins when m < p-1, ties at m = p-1, and trades ~(m-p+1)F of ticks
    for bubble-free full-batch wgrad matmuls otherwise (measured in
    tools/pipeline_tax.py). Memory: the input buffer must hold all M
    microbatch boundaries plus M output cotangents (2m boundary tensors vs
    1F1B's 2p-1).
    """
    my_stage = jax.lax.axis_index(axis_name)
    vary = tuple(vary_axes) if vary_axes else (axis_name,)
    m = num_microbatches
    p = p_size
    # live-activation ring buffer depth: the 1F1B bound, or all M when the
    # deferred wgrad needs every stage input after the scans
    k = m if wgrad_deferred else min(m, 2 * p - 1)
    # Replicated (unvarying) params must be made varying before vjp: jax's
    # vma-aware transpose auto-psums cotangents toward unvarying inputs,
    # which would pre-sum grads across stages and break the per-stage
    # masking/accumulation below.
    embed_params = jax.tree_util.tree_map(
        lambda a: _pvary(a, vary), embed_params)
    head_params = jax.tree_util.tree_map(
        lambda a: _pvary(a, vary), head_params)
    local_params = jax.tree_util.tree_map(lambda a: a[0], stacked_stage_params)
    local_params = jax.tree_util.tree_map(
        lambda a: _pvary(a, vary), local_params)

    perm_fwd = [(i, i + 1) for i in range(p - 1)]
    perm_bwd = [(i + 1, i) for i in range(p - 1)]

    if tied_embed:
        def fwd_and_loss(sp, hp, ep, h_in, lab):
            h_out = stage_fn(sp, h_in)
            return h_out, head_loss_fn(hp, ep, h_out, lab)

        def head_call(hp, ep, h_out, lab):
            return head_loss_fn(hp, ep, h_out, lab)
    else:
        def fwd_and_loss(sp, hp, ep, h_in, lab):
            h_out = stage_fn(sp, h_in)
            return h_out, head_loss_fn(hp, h_out, lab)

        def head_call(hp, ep, h_out, lab):
            del ep  # untied head never reads the embedding (zero cotangent)
            return head_loss_fn(hp, h_out, lab)

    h_shape = jax.eval_shape(
        lambda ep, t: embed_fn(ep, t), embed_params, tokens_mb[0])
    zero_h = jnp.zeros(h_shape.shape, h_shape.dtype)

    zeros_like_tree = lambda tree: jax.tree_util.tree_map(
        lambda a: jnp.zeros(a.shape, a.dtype), tree)

    carry0 = dict(
        recv_f=_pvary(zero_h, vary),
        recv_b=_pvary(zero_h, vary),
        buf=_pvary(jnp.zeros((k,) + h_shape.shape, h_shape.dtype), vary),
        demb=_pvary(zeros_like_tree(embed_params), vary),
        dhead=_pvary(zeros_like_tree(head_params), vary),
        dh0=_pvary(jnp.zeros((m,) + h_shape.shape, h_shape.dtype), vary),
        loss=_pvary(jnp.zeros((), jnp.float32), vary),
    )
    if wgrad_deferred:
        # per-microbatch output cotangents for the post-scan batched wgrad
        carry0["dhout"] = _pvary(
            jnp.zeros((m,) + h_shape.shape, h_shape.dtype), vary)
    else:
        carry0["dstage"] = _pvary(zeros_like_tree(local_params), vary)

    inv_m = jnp.float32(1.0 / m)

    # The schedule runs as THREE scans over one parameterized tick body —
    # fill (fwd only), steady (fwd+bwd+head), drain (bwd only). A single
    # scan over all t would execute the head fwd+bwd and the stage vjp on
    # every tick including fill/drain (masked => still computed in SPMD);
    # phase-splitting drops the head to exactly M executions (its minimum
    # for this design: the last stage's backward of microbatch j happens
    # the tick after its forward, so it cannot batch outside the scan) and
    # removes the stage vjp/fwd from ticks where no stage can need it.
    # Phase boundaries are stage-independent: the earliest backward
    # anywhere is t = 2(P-1)-(P-1) = P-1 (last stage), the last forward
    # anywhere ends at t = (P-1)+M (stage P-1), and the last stage's own
    # backwards — the only ones needing the head — all land in
    # [P-1, M+P-1).
    def tick(carry, t, do_fwd, do_bwd, do_head):
        buf = carry["buf"]
        # ---- forward part: microbatch i at stage s when t == s + i -------
        if do_fwd:
            i_f = t - my_stage
            f_active = (i_f >= 0) & (i_f < m)
            tok_i = tokens_mb[jnp.clip(i_f, 0, m - 1)]
            h_embed = embed_fn(embed_params, tok_i)
            h_in = jnp.where(my_stage == 0, _pvary(h_embed, vary),
                             carry["recv_f"])
            h_in = jnp.where(f_active, h_in, jnp.zeros_like(h_in))
            slot_f = jnp.mod(i_f, k)
            buf = buf.at[slot_f].set(
                jnp.where(f_active, h_in, buf[slot_f]))
            h_out = stage_fn(local_params, h_in)
            h_out = jnp.where(f_active, h_out, jnp.zeros_like(h_out))
            send_f = jax.lax.ppermute(h_out, axis_name, perm_fwd)
        else:
            send_f = carry["recv_f"]

        if not do_bwd:
            out = dict(carry)
            out.update(recv_f=send_f, buf=buf)
            return out, None

        # ---- backward part: microbatch j when t == 2(P-1) - s + j --------
        j = t - 2 * (p - 1) + my_stage
        b_active = (j >= 0) & (j < m)
        h_saved = buf[jnp.mod(j, k)]
        bmask = lambda g: jnp.where(b_active, g, jnp.zeros_like(g))
        demb, dhead, loss = carry["demb"], carry["dhead"], carry["loss"]

        if wgrad_deferred:
            # dX-only tick: vjp w.r.t. the stage INPUT; the stage weight
            # cotangent is deferred to the post-scan batched vjp
            h_out_b, pull_x = jax.vjp(
                lambda h: stage_fn(local_params, h), h_saved)
            if do_head:
                lab_j = labels_mb[jnp.clip(j, 0, m - 1)]
                is_last = my_stage == p - 1
                loss_j, pull_head = jax.vjp(
                    lambda hp, ep, h: head_call(hp, ep, h, lab_j),
                    head_params, embed_params, h_out_b)
                seed_loss = _pvary(
                    jnp.where(is_last & b_active, inv_m, jnp.float32(0)),
                    vary)
                dhp, dhp_emb, dh_out_head = pull_head(seed_loss)
                dhead = jax.tree_util.tree_map(
                    lambda acc, g: acc + bmask(g), dhead, dhp)
                demb = jax.tree_util.tree_map(
                    lambda acc, g: acc + bmask(g), demb, dhp_emb)
                loss = loss + jnp.where(is_last & b_active,
                                        loss_j * inv_m, 0.0)
                dh_out = jnp.where(is_last, dh_out_head, carry["recv_b"])
            else:
                dh_out = carry["recv_b"]
            dh_out = bmask(dh_out)
            (dh_in,) = pull_x(dh_out)
            dhout = carry["dhout"].at[jnp.clip(j, 0, m - 1)].add(dh_out)
            dh0 = carry["dh0"].at[jnp.clip(j, 0, m - 1)].add(
                jnp.where((my_stage == 0) & b_active, dh_in,
                          jnp.zeros_like(dh_in)))
            send_b = jax.lax.ppermute(bmask(dh_in), axis_name, perm_bwd)
            return dict(recv_f=send_f, recv_b=send_b, buf=buf, demb=demb,
                        dhout=dhout, dhead=dhead, dh0=dh0, loss=loss), None

        if do_head:
            lab_j = labels_mb[jnp.clip(j, 0, m - 1)]
            is_last = my_stage == p - 1
            (h_out_b, loss_j), pull = jax.vjp(
                lambda sp, hp, ep, h: fwd_and_loss(sp, hp, ep, h, lab_j),
                local_params, head_params, embed_params, h_saved)
            # cotangent seed: last stage seeds from its own loss, others
            # from the cotangent received from stage s+1
            seed_h = jnp.where(is_last, jnp.zeros_like(carry["recv_b"]),
                               carry["recv_b"])
            seed_h = jnp.where(b_active, seed_h, jnp.zeros_like(seed_h))
            seed_loss = _pvary(
                jnp.where(is_last & b_active, inv_m, jnp.float32(0)), vary)
            dsp, dhp, dhp_emb, dh_in = pull((seed_h, seed_loss))
            dhead = jax.tree_util.tree_map(
                lambda acc, g: acc + bmask(g), dhead, dhp)
            demb = jax.tree_util.tree_map(
                lambda acc, g: acc + bmask(g), demb, dhp_emb)
            loss = loss + jnp.where(is_last & b_active, loss_j * inv_m, 0.0)
        else:
            # drain: the last stage finished all its backwards in the
            # steady phase, so no tick here can need the head/loss
            _, pull = jax.vjp(
                lambda sp, h: stage_fn(sp, h), local_params, h_saved)
            seed_h = jnp.where(b_active, carry["recv_b"],
                               jnp.zeros_like(carry["recv_b"]))
            dsp, dh_in = pull(seed_h)

        dstage = jax.tree_util.tree_map(
            lambda acc, g: acc + bmask(g), carry["dstage"], dsp)
        # record stage 0's input cotangent; the embedding backward runs
        # ONCE, batched, after the scans (a per-tick embed vjp would pay
        # an O(vocab x hidden) scatter every tick)
        dh0 = carry["dh0"].at[jnp.clip(j, 0, m - 1)].add(
            jnp.where((my_stage == 0) & b_active, dh_in,
                      jnp.zeros_like(dh_in)))
        send_b = jax.lax.ppermute(bmask(dh_in), axis_name, perm_bwd)
        return dict(recv_f=send_f, recv_b=send_b, buf=buf, demb=demb,
                    dstage=dstage, dhead=dhead, dh0=dh0, loss=loss), None

    from functools import partial as _partial
    carry = carry0
    if p > 1:
        carry, _ = jax.lax.scan(
            _partial(tick, do_fwd=True, do_bwd=False, do_head=False),
            carry, jnp.arange(0, p - 1))
    carry, _ = jax.lax.scan(
        _partial(tick, do_fwd=True, do_bwd=True, do_head=True),
        carry, jnp.arange(p - 1, m + p - 1))
    if p > 1:
        carry, _ = jax.lax.scan(
            _partial(tick, do_fwd=False, do_bwd=True, do_head=False),
            carry, jnp.arange(m + p - 1, m + 2 * (p - 1)))

    # batched embedding backward: one vjp over all microbatches (stage 0's
    # recorded cotangents; zeros elsewhere, fixed by the psum below)
    def batched_embed(ep):
        return jax.vmap(lambda tk: embed_fn(ep, tk))(tokens_mb)

    _, pull_e = jax.vjp(batched_embed, embed_params)
    (dep,) = pull_e(carry["dh0"])
    carry["demb"] = jax.tree_util.tree_map(
        lambda acc, g: acc + g, carry["demb"], dep)

    if wgrad_deferred:
        # deferred stage wgrad: ONE batched vjp over all M microbatches.
        # buf slots are microbatch-ordered (k == m), every (stage, j) pair
        # was filled during the forward ticks, so this is fully dense —
        # no masking, full-batch matmul shapes, zero pipeline bubble.
        def batched_stage(sp):
            return jax.vmap(lambda h: stage_fn(sp, h))(carry["buf"])

        _, pull_w = jax.vjp(batched_stage, local_params)
        (dstage_acc,) = pull_w(carry["dhout"])
        carry["dstage"] = dstage_acc

    # loss lives on the last stage; grads for replicated params only on
    # their owning stages — psum over pp makes them correct everywhere.
    loss = jax.lax.psum(jnp.where(my_stage == p - 1, carry["loss"], 0.0),
                        axis_name)
    demb = jax.tree_util.tree_map(
        lambda g: jax.lax.psum(g, axis_name), carry["demb"])
    dhead = jax.tree_util.tree_map(
        lambda g: jax.lax.psum(g, axis_name), carry["dhead"])
    dstage = jax.tree_util.tree_map(
        lambda g: g[None], carry["dstage"])  # restore (1, ...) local stage dim
    return loss, demb, dstage, dhead


class OneFOneBPipeline:
    """1F1B-scheduled pipelined LM: returns (loss, grads) directly (the
    backward is part of the schedule, not autodiff of the forward).

    Same parameter layout as PipelinedLM. With `tied_embed=True`,
    head_loss_fn(head_params, embed_params, h, labels) may read the
    embedding weight (tied softmax) and its gradient flows into the
    embedding — reference SharedLayerDesc (pp_layers.py:76).
    """

    wgrad_deferred = False  # ZeroBubblePipeline flips this

    def __init__(self, mesh: Mesh, embed_fn, stage_fn, head_loss_fn,
                 num_microbatches: int, axis_name: str = "pp",
                 batch_axis: str | None = None, tied_embed: bool = False):
        self.mesh = mesh
        self.embed_fn = embed_fn
        self.stage_fn = stage_fn
        self.head_loss_fn = head_loss_fn
        self.m = num_microbatches
        self.axis = axis_name
        self.batch_axis = batch_axis
        self.tied_embed = tied_embed

    def loss_and_grad_fn(self):
        axis = self.axis
        m = self.m
        mesh = self.mesh
        batch_axis = self.batch_axis
        p_size = mesh.shape[axis]
        tied = self.tied_embed
        deferred = self.wgrad_deferred

        def spmd_grads(embed_params, stage_params, head_params, tokens,
                       labels):
            def inner(embed_p, stage_p, head_p, tok, lab):
                b = tok.shape[0]
                tok_mb = tok.reshape((m, b // m) + tok.shape[1:])
                lab_mb = lab.reshape((m, b // m) + lab.shape[1:])
                vary = (axis,) + ((batch_axis,) if batch_axis else ())
                loss, demb, dstage, dhead = pipeline_1f1b_grads(
                    self.embed_fn, self.stage_fn, self.head_loss_fn,
                    embed_p, stage_p, head_p, tok_mb, lab_mb, axis,
                    p_size=p_size, num_microbatches=m, vary_axes=vary,
                    tied_embed=tied, wgrad_deferred=deferred)
                if batch_axis is not None:
                    loss = jax.lax.pmean(loss, batch_axis)
                    demb, dstage, dhead = jax.tree_util.tree_map(
                        lambda g: jax.lax.pmean(g, batch_axis),
                        (demb, dstage, dhead))
                return loss, demb, dstage, dhead

            data_spec = P(batch_axis) if batch_axis is not None else P()
            in_specs = (
                jax.tree_util.tree_map(lambda _: P(), embed_params),
                jax.tree_util.tree_map(lambda _: P(axis), stage_params),
                jax.tree_util.tree_map(lambda _: P(), head_params),
                data_spec, data_spec,
            )
            out_specs = (
                P(),
                jax.tree_util.tree_map(lambda _: P(), embed_params),
                jax.tree_util.tree_map(lambda _: P(axis), stage_params),
                jax.tree_util.tree_map(lambda _: P(), head_params),
            )
            return jax.shard_map(inner, mesh=mesh, in_specs=in_specs,
                                 out_specs=out_specs)(
                embed_params, stage_params, head_params, tokens, labels)

        return spmd_grads


class ZeroBubblePipeline(OneFOneBPipeline):
    """Deferred-weight-grad pipeline schedule — the TPU-native zero-bubble.

    reference capability: pipeline_zero_bubble.py ZBH1/ZBVPP (split
    backward into activation-grad B and weight-grad W, schedule W into
    pipeline bubbles). In this SPMD-scan design there are no per-device
    idle slots to fill — so instead of reordering W within ticks, W leaves
    the pipeline entirely: ticks compute only dX, and every stage's weight
    gradient is ONE post-scan batched vjp at full-batch matmul shapes.
    See pipeline_1f1b_grads(wgrad_deferred=True) for the measured cost
    model (wins at m <= p-1 microbatches or when per-microbatch matmuls
    underutilize the MXU; 1F1B wins the serial-flop count at m >> p).
    """

    wgrad_deferred = True


class PipelinedLM:
    """End-to-end pipelined LM training step.

    embed_fn(embed_params, tokens) -> h           (run on every stage; cheap)
    stage_fn(stage_params, h) -> h                (the pipelined body)
    head_loss_fn(head_params, h, labels) -> loss  (evaluated on last stage)

    Parameters layout:
      embed/head params: replicated
      stage params: leaves stacked with leading dim = pp_size, sharded on 'pp'
    """

    def __init__(self, mesh: Mesh, embed_fn, stage_fn, head_loss_fn,
                 num_microbatches: int, axis_name: str = "pp",
                 batch_axis: str | None = None, remat: bool = True):
        self.mesh = mesh
        self.embed_fn = embed_fn
        self.stage_fn = stage_fn
        self.head_loss_fn = head_loss_fn
        self.m = num_microbatches
        self.axis = axis_name
        self.batch_axis = batch_axis  # optional dp axis: batch sharded
        self.remat = remat

    def _pipeline_forward(self, stage_p, h_mb, p_size, vary):
        """The schedule hook — subclasses swap the forward program."""
        return pipeline_forward(self.stage_fn, stage_p, h_mb, self.axis,
                                p_size=p_size, remat=self.remat,
                                vary_axes=vary)

    def loss_fn(self):
        axis = self.axis
        m = self.m
        mesh = self.mesh
        batch_axis = self.batch_axis

        p_size = mesh.shape[axis]

        def spmd_loss(embed_params, stage_params, head_params, tokens, labels):
            def inner(embed_p, stage_p, head_p, tok, lab):
                my_stage = jax.lax.axis_index(axis)
                # microbatch the tokens: (B, S) -> (M, B/M, S)
                b = tok.shape[0]
                tok_mb = tok.reshape((m, b // m) + tok.shape[1:])
                lab_mb = lab.reshape((m, b // m) + lab.shape[1:])
                h_mb = jax.vmap(lambda t: self.embed_fn(embed_p, t))(tok_mb)
                vary = (axis,) + ((batch_axis,) if batch_axis else ())
                out = self._pipeline_forward(stage_p, h_mb, p_size, vary)
                losses = jax.vmap(
                    lambda h, l: self.head_loss_fn(head_p, h, l))(out, lab_mb)
                # only the last stage holds real outputs; other stages
                # contribute 0 and the (pp,) partials are summed outside —
                # avoids an in-region psum (robust across vma modes)
                local = jnp.where(my_stage == p_size - 1,
                                  jnp.mean(losses), 0.0)
                if batch_axis is not None:
                    return local.reshape(1, 1)
                return local.reshape(1)

            data_spec = P(batch_axis) if batch_axis is not None else P()
            out_spec = P(axis, batch_axis) if batch_axis is not None else P(axis)
            in_specs = (
                jax.tree_util.tree_map(lambda _: P(), embed_params),
                jax.tree_util.tree_map(lambda _: P(axis), stage_params),
                jax.tree_util.tree_map(lambda _: P(), head_params),
                data_spec, data_spec,
            )
            partials = jax.shard_map(inner, mesh=mesh, in_specs=in_specs,
                                     out_specs=out_spec)(
                embed_params, stage_params, head_params, tokens, labels)
            if batch_axis is not None:
                return jnp.mean(jnp.sum(partials, axis=0))  # sum pp, mean dp
            return jnp.sum(partials)

        return spmd_loss


class InterleavedPipelinedLM(PipelinedLM):
    """Interleaved (VPP) pipelined LM: each physical stage holds
    `num_chunks` model chunks, shrinking the pipeline fill relative to
    fill-drain by the chunk count. Backward comes from autodiff of the
    interleaved scan. reference: PipelineParallelWithInterleave
    (fleet/meta_parallel/pipeline_parallel.py:1174).

    Parameter layout: stage params stacked (pp, num_chunks, Lv, ...) with
    the leading dim sharded on 'pp' — element [s, c] holds virtual stage
    v = c*pp + s. Everything else (microbatching, loss masking, specs)
    is PipelinedLM's; only the forward program differs.
    """

    def __init__(self, mesh: Mesh, embed_fn, stage_fn, head_loss_fn,
                 num_microbatches: int, num_chunks: int,
                 axis_name: str = "pp", batch_axis: str | None = None,
                 remat: bool = True):
        super().__init__(mesh, embed_fn, stage_fn, head_loss_fn,
                         num_microbatches, axis_name, batch_axis, remat)
        self.v = num_chunks

    def _pipeline_forward(self, stage_p, h_mb, p_size, vary):
        return pipeline_forward_interleaved(
            self.stage_fn, stage_p, h_mb, self.axis, p_size=p_size,
            num_chunks=self.v, remat=self.remat, vary_axes=vary)
