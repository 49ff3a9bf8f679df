"""Scan-over-layers Llama train step for compile-light large models.

reference capability: the reference trains deep stacks as per-layer ops in
one program; on TPU an unrolled 24+ layer trace produces an HLO whose size
scales with depth (slow/failing compiles). Here the decoder stack is a
single lax.scan over stacked per-layer parameters — HLO size is O(1) in
depth, XLA compiles one layer body, and per-layer rematerialization
(jax.checkpoint on the body) gives the standard activation-memory trade.

Numerics match the imperative LlamaForCausalLM
(tests/test_models.py::TestScannedLlama).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..parallel.functional import (functional_call, rmsnorm_lm_loss,
                                   rmsnorm_lm_loss_chunked,
                                   split_stacked_layer_params)

__all__ = ["build_scanned_llama"]


def build_scanned_llama(model, remat: bool = True, dtype=None,
                        remat_policy: str | None = None,
                        loss_chunk_mb: int = 256):
    """Split a LlamaForCausalLM's state into (embed, stacked layers, head)
    and return (params, loss_fn) where loss_fn(params, ids, labels) is a
    pure scalar LM loss whose decoder stack is one lax.scan.

    params = {"embed": {...}, "layers": {name: (L, ...)}, "head": {...}}.
    """
    cfg = model.config
    state = {k: v._data for k, v in model.state_dict().items()}
    if dtype is not None:
        from ..framework import dtypes as _dt
        dt = _dt.convert_dtype(dtype)
        state = {k: v.astype(dt) if jnp.issubdtype(v.dtype, jnp.floating)
                 else v for k, v in state.items()}

    layers, other = split_stacked_layer_params(state)

    params = {
        "embed": {"weight": other["llama.embed_tokens.weight"]},
        "layers": layers,
        "head": {"norm": other["llama.norm.weight"]},
    }
    tied = "lm_head.weight" not in other
    if not tied:
        params["head"]["lm_head"] = other["lm_head.weight"]

    template = model.llama.layers[0]
    eps = cfg.rms_norm_eps

    def layer_body(h, lp):
        h = functional_call(template, lp, h)
        return h, None

    if remat:
        if remat_policy is None:
            body = jax.checkpoint(layer_body)
        else:
            # named XLA remat policy: 'dots' keeps matmul outputs and
            # recomputes only the cheap elementwise pieces in the backward —
            # full remat re-runs the layer's MXU work, which on TPU costs
            # far more than the HBM it saves at moderate depth
            policies = {
                "dots": jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
                "nothing": jax.checkpoint_policies.nothing_saveable,
                "everything": jax.checkpoint_policies.everything_saveable,
            }
            if remat_policy not in policies:
                raise ValueError(
                    f"remat_policy={remat_policy!r}; pick from "
                    f"{sorted(policies)}")
            body = jax.checkpoint(layer_body, policy=policies[remat_policy])
    else:
        body = layer_body

    vocab = cfg.vocab_size

    def loss_fn(p, ids, labels):
        h = jnp.take(p["embed"]["weight"], ids, axis=0)
        h, _ = jax.lax.scan(body, h, p["layers"])
        w = (p["embed"]["weight"].T if tied
             else p["head"]["lm_head"])  # nn.Linear weight: (hidden, vocab)
        b, s = ids.shape
        # the fp32 (b, s, vocab) softmax buffer dominates HBM at LM scale;
        # chunk the loss once it would exceed loss_chunk_mb (see
        # rmsnorm_lm_loss_chunked) — below that the fused path is cheaper
        # (the chunk scan + checkpoint recompute cost ~5-15% step time, so
        # callers with HBM headroom raise the threshold to stay fused)
        if b * s * vocab * 4 > loss_chunk_mb * 1024 * 1024:
            loss_fn.lm_loss_path = "chunked"
            return rmsnorm_lm_loss_chunked(p["head"]["norm"], w, h, labels,
                                           eps)
        loss_fn.lm_loss_path = "fused"
        return rmsnorm_lm_loss(p["head"]["norm"], w, h, labels, eps)

    # which loss flavor ran, for bench labeling — set at first trace
    loss_fn.lm_loss_path = None
    return params, loss_fn
