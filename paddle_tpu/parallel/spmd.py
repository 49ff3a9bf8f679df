"""GSPMD mesh trainer: hybrid parallelism as sharding rules.

reference capability collapsed here (SURVEY.md §2.3): fleet's
TP layers + DP reducer + ZeRO sharding optimizers + semi-auto SPMD rules →
one jitted train step whose parameters/optimizer-states/activations carry
NamedShardings. XLA inserts all collectives (grad psum over dp, activation
all-reduce over mp, reshard for sp) on ICI.

Mesh axes follow the reference's fixed order pp→mp→sep→sharding→dp
(fleet/base/topology.py:301) so configs translate 1:1.
"""

from __future__ import annotations

import re
from typing import Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..framework.core import Tensor
from ..observability import span as _span
from .functional import make_loss_fn

__all__ = ["create_mesh", "shard_params_by_rules", "SpmdTrainer",
           "LLAMA_SHARDING_RULES", "GPT_SHARDING_RULES", "DP_ONLY_RULES"]


def create_mesh(dp=1, mp=1, pp=1, sep=1, sharding=1, devices=None) -> Mesh:
    """Build the hybrid mesh (axis order = reference fleet order)."""
    if devices is None:
        devices = jax.devices()
    need = dp * mp * pp * sep * sharding
    if len(devices) < need:
        raise ValueError(f"need {need} devices, have {len(devices)}")
    grid = np.asarray(devices[:need]).reshape(pp, mp, sep, sharding, dp)
    return Mesh(grid, ("pp", "mp", "sep", "sharding", "dp"))


# -- sharding rules: (param-name regex → PartitionSpec) ----------------------
# The analog of the reference's per-op SPMD rules + fleet TP layer choices,
# but declarative: Megatron column-parallel weights shard their output dim
# on mp, row-parallel weights their input dim.

LLAMA_SHARDING_RULES = [
    (r".*embed_tokens\.weight$", P("mp", None)),           # vocab-parallel
    (r".*(q_proj|k_proj|v_proj|gate_proj|up_proj)\.weight$", P(None, "mp")),
    (r".*(o_proj|down_proj)\.weight$", P("mp", None)),
    (r".*lm_head\.weight$", P(None, "mp")),
    (r".*norm.*\.weight$", P()),                            # replicated
    (r".*", P()),
]

GPT_SHARDING_RULES = [
    (r".*(wte|wpe)\.weight$", P("mp", None)),
    (r".*qkv_proj\.weight$", P(None, "mp")),
    (r".*qkv_proj\.bias$", P("mp")),
    (r".*out_proj\.weight$", P("mp", None)),
    (r".*fc1\.weight$", P(None, "mp")),
    (r".*fc1\.bias$", P("mp")),
    (r".*fc2\.weight$", P("mp", None)),
    (r".*", P()),
]

DP_ONLY_RULES = [(r".*", P())]


def spec_for(name: str, rules) -> P:
    for pat, spec in rules:
        if re.match(pat, name):
            return spec
    return P()


def _pad_spec(spec: P, ndim: int) -> P:
    parts = list(spec) + [None] * (ndim - len(list(spec)))
    return P(*parts[:ndim])


def shard_params_by_rules(params: dict, mesh: Mesh, rules) -> dict:
    """name->array dict sharded onto mesh per rules (ZeRO: pass rules that
    shard dim 0 on 'sharding'/'dp')."""
    out = {}
    for name, arr in params.items():
        a = arr._data if isinstance(arr, Tensor) else arr
        spec = _pad_spec(spec_for(name, rules), a.ndim)
        # drop axes that don't divide (tiny test shapes)
        fixed = []
        for dim, s in enumerate(spec):
            if s is None:
                fixed.append(None)
                continue
            size = mesh.shape[s] if isinstance(s, str) else int(
                np.prod([mesh.shape[x] for x in s]))
            fixed.append(s if a.shape[dim] % size == 0 else None)
        out[name] = jax.device_put(a, NamedSharding(mesh, P(*fixed)))
    return out


def _with_zero_axis(spec: P, shape, mesh: Mesh, axis: str = "sharding") -> P:
    """Add the ZeRO 'sharding' axis to the first unsharded, divisible dim.

    reference capability: fleet/meta_parallel/sharding partitions flat param
    shards by rank (group_sharded_stage3.py:85); here the partition is a
    dimension sharding GSPMD understands, so gather-on-use / reduce-scatter
    come out of the compiler instead of hand-written collectives."""
    n = mesh.shape[axis]
    parts = list(spec) + [None] * (len(shape) - len(list(spec)))
    for dim, s in enumerate(parts):
        if s is None and shape[dim] % n == 0 and shape[dim] >= n:
            parts[dim] = axis
            return P(*parts)
    return P(*parts)


class SpmdTrainer:
    """Compiled hybrid-parallel training loop.

    - params + optimizer state live as sharded jax arrays (donated each step)
    - batch sharded on dp (+sep for the sequence dim)
    - loss/grads computed in one jit; XLA handles every collective
    - sharding_stage (ZeRO over the 'sharding' mesh axis, reference
      DygraphShardingOptimizer:53 / group_sharded_stage3.py:85):
        1 = optimizer states partitioned (update math runs sharded, params
            all-gathered by the compiler after the update)
        2 = + gradients reduce-scattered onto the sharding axis
        3 = + parameters partitioned, gathered on use by GSPMD
      All three keep the partitioning INSIDE the jitted step via
      in/out_shardings + with_sharding_constraint — no post-hoc device_put.
    """

    def __init__(self, model, optimizer, mesh: Mesh, rules=None, loss_fn=None,
                 batch_spec: P | None = None, remat: bool = False,
                 dtype=None, sharding_stage: int = 0):
        self.model = model
        self.optimizer = optimizer
        self.mesh = mesh
        self.rules = rules or DP_ONLY_RULES
        self.sharding_stage = int(sharding_stage)
        if (self.sharding_stage and "sharding" in mesh.axis_names
                and mesh.shape["sharding"] > 1):
            self._zero_axis = "sharding"
        else:
            self._zero_axis = None
        state = model.state_dict()
        if dtype is not None:
            from ..framework import dtypes as _dt
            dt = _dt.convert_dtype(dtype)
            for t in state.values():
                if jnp.issubdtype(t._data.dtype, jnp.floating):
                    t._data = t._data.astype(dt)
        self.param_names = list(state.keys())
        self.params = shard_params_by_rules(state, mesh, self.rules)
        # ZeRO grad/opt-state partition specs, derived from the param specs
        self._zero_specs = {}
        for name, a in self.params.items():
            base = a.sharding.spec
            if self._zero_axis is not None:
                self._zero_specs[name] = _with_zero_axis(
                    base, a.shape, mesh, self._zero_axis)
            else:
                self._zero_specs[name] = base
        if self._zero_axis is not None and self.sharding_stage >= 3:
            self.params = {
                name: jax.device_put(
                    a, NamedSharding(mesh, self._zero_specs[name]))
                for name, a in self.params.items()}
        # optimizer states shard like their params (ZeRO>=1: partitioned)
        self.opt_state = {}
        for name, a in self.params.items():
            st = optimizer.init_state(a)
            if self._zero_axis is not None:
                state_sh = NamedSharding(mesh, self._zero_specs[name])
            else:
                state_sh = a.sharding
            self.opt_state[name] = {
                k: jax.device_put(v, state_sh) if v.shape == a.shape
                else jax.device_put(v, NamedSharding(mesh, P()))
                for k, v in st.items()}
        self.step_count = 0
        self._loss = make_loss_fn(model, loss_fn)
        if batch_spec is None:
            batch_spec = P(("dp",)) if "dp" in mesh.axis_names else P(None)
        self.batch_spec = batch_spec
        self.remat = remat
        self._compiled = None

    def _build(self, batch_tree):
        loss_pure = self._loss
        if self.remat:
            inner = loss_pure
            loss_pure = jax.checkpoint(
                lambda p, b, k: inner(p, b, k))
        opt = self.optimizer
        grad_clip = getattr(opt, "_grad_clip", None)

        def apply_clip(grads):
            """Functional mirror of nn.ClipGradBy* for the compiled path
            (the eager path clips in Optimizer.step)."""
            from ..nn.clip import (ClipGradByGlobalNorm, ClipGradByNorm,
                                   ClipGradByValue)
            if grad_clip is None:
                return grads
            leaves, treedef = jax.tree_util.tree_flatten(grads)
            if isinstance(grad_clip, ClipGradByGlobalNorm):
                total = sum(jnp.sum(g.astype(jnp.float32) ** 2) for g in leaves)
                gn = jnp.sqrt(total)
                scale = grad_clip.clip_norm / jnp.maximum(gn, grad_clip.clip_norm)
                leaves = [(g * scale).astype(g.dtype) for g in leaves]
            elif isinstance(grad_clip, ClipGradByNorm):
                def per(g):
                    n = jnp.sqrt(jnp.sum(g.astype(jnp.float32) ** 2))
                    s = jnp.minimum(grad_clip.clip_norm / jnp.maximum(n, 1e-12), 1.0)
                    return (g * s).astype(g.dtype)
                leaves = [per(g) for g in leaves]
            elif isinstance(grad_clip, ClipGradByValue):
                leaves = [jnp.clip(g, grad_clip.min, grad_clip.max) for g in leaves]
            return jax.tree_util.tree_unflatten(treedef, leaves)

        mesh = self.mesh
        zero_specs = self._zero_specs
        stage = self.sharding_stage if self._zero_axis is not None else 0

        def train_step(params, opt_state, batch, rng_key, step, lr):
            loss, grads = jax.value_and_grad(loss_pure)(params, batch, rng_key)
            # component scope of the update (observability/catalog.py
            # TRACE_SCOPES); the model enters its own in forward()
            with jax.named_scope("pt.opt"):
                grads = apply_clip(grads)
                if stage >= 2:
                    # ZeRO-2: dp grad psum becomes reduce-scatter; each
                    # device keeps only its slice of every gradient
                    grads = {
                        name: jax.lax.with_sharding_constraint(
                            g, NamedSharding(mesh, zero_specs[name]))
                        for name, g in grads.items()}
                new_params, new_opt = opt.tree_update(params, grads,
                                                      opt_state, lr, step)
            return loss, new_params, new_opt

        param_shardings = {k: v.sharding for k, v in self.params.items()}
        opt_shardings = {k: {kk: vv.sharding for kk, vv in v.items()}
                         for k, v in self.opt_state.items()}
        batch_sh = jax.tree_util.tree_map(
            lambda a: NamedSharding(self.mesh, _pad_spec(self.batch_spec,
                                                         jnp.ndim(a))),
            batch_tree)
        return jax.jit(
            train_step,
            in_shardings=(param_shardings, opt_shardings, batch_sh, None,
                          None, None),
            out_shardings=(NamedSharding(self.mesh, P()), param_shardings,
                           opt_shardings),
            donate_argnums=(0, 1),
        )

    def _batch_arrays(self, batch):
        batch_arrays = jax.tree_util.tree_map(
            lambda t: t._data if isinstance(t, Tensor) else jnp.asarray(t),
            batch, is_leaf=lambda v: isinstance(v, Tensor))
        if self._compiled is None:
            self._compiled = self._build(batch_arrays)
        return batch_arrays

    def step_memory(self, batch):
        """XLA's buffer assignment for the compiled step at this batch, in
        bytes per device: arguments, outputs, donated aliases, temporaries,
        and `peak`, the most the step holds at one time by the compiler's
        own count (the figure it holds to the chip's memory; 0 where the
        backend gives none). A step holds at most argument + output -
        alias + temp; `peak` can be well under that sum (2.1 GiB under for
        models/granite_moe_hybrid.py at 8k tokens, 0.05 for GPT). On a TPU
        device.memory_stats() counts live arrays only — a running
        program's temporaries (the activations) show up nowhere else.
        Changes no state. Lowers the step again; the lowering is the one
        step() made, so the compile is a hit in jax's persistent cache
        where one is set up (tests/test_spmd_trainer.py)."""
        from ..framework.random import get_rng_state
        batch_arrays = self._batch_arrays(batch)
        # the scalars are made outside the mesh, as step() makes them: made
        # under set_mesh they carry a sharding, the lowering differs by
        # those two annotations, and neither compile cache is hit
        step = jnp.asarray(self.step_count, jnp.int32)
        lr = jnp.asarray(self.optimizer.get_lr(), jnp.float32)
        with jax.set_mesh(self.mesh):
            compiled = self._compiled.lower(
                self.params, self.opt_state, batch_arrays,
                get_rng_state()[0], step, lr).compile()
        mem = compiled.memory_analysis()
        out = {k: int(getattr(mem, f"{k}_size_in_bytes"))
               for k in ("argument", "output", "alias", "temp")}
        out["peak"] = int(getattr(mem, "peak_memory_in_bytes", 0))
        return out

    def step(self, batch, rng_key=None):
        """batch: (x, y) of Tensors or arrays. Returns float loss.

        Host spans (observability/tracing.py; live when the tracer is on or
        a profiler session runs, else three flag reads): `trainer.step`
        with `step_num` — the step view of a device trace keys on it —
        around `trainer.stage` (batch, rng key, the two scalars) and
        `trainer.dispatch` (the call of the compiled step)."""
        with _span("trainer.step", step_num=self.step_count + 1):
            with _span("trainer.stage"):
                batch_arrays = self._batch_arrays(batch)
                if rng_key is None:
                    from ..framework.random import next_key
                    rng_key = next_key()
                self.step_count += 1
                # step/lr as device scalars so changing them never retraces
                lr = jnp.asarray(self.optimizer.get_lr(), jnp.float32)
                step = jnp.asarray(self.step_count, jnp.int32)
            # the mesh is ambient while the step traces: code that GSPMD
            # cannot partition (Pallas kernels) finds it there and
            # shard_maps itself
            with _span("trainer.dispatch"), jax.set_mesh(self.mesh):
                loss, self.params, self.opt_state = self._compiled(
                    self.params, self.opt_state, batch_arrays, rng_key,
                    step, lr)
        return loss

    def sync_to_model(self):
        """Write trained arrays back into the imperative model's tensors.
        Copies (not aliases): the live self.params buffers are donated by the
        next step(), which would leave the model pointing at deleted arrays."""
        state = self.model.state_dict()
        for name, t in state.items():
            if name in self.params:
                t._data = self.params[name].copy()
