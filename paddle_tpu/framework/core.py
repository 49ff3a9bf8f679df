"""Core imperative Tensor + autograd engine, TPU-native.

This is the TPU-first replacement for the reference's eager stack:

- ``Tensor`` plays the role of ``paddle::Tensor`` / eager `Tensor`
  (reference: paddle/phi/api/include/tensor.h, paddle/fluid/pybind/eager_method.cc)
  but wraps a ``jax.Array`` so every op lowers through XLA.
- The autograd engine replaces the C++ GradNode graph + ``egr::RunBackward``
  (reference: paddle/fluid/eager/backward.cc:105, grad_node_info.h). Instead of
  hand-written per-op grad nodes generated from backward.yaml, we record one
  ``jax.vjp`` closure per executed op ("Node") and run a reverse topological
  walk keyed on monotonically increasing node ids.
- Kernel dispatch (reference: paddle/phi/core/kernel_factory.h:316) collapses
  into XLA: ops are pure jax functions, the "kernel registry" is jax itself.

Design notes (TPU-first):
- Eager ops execute immediately on-device via jax; under `paddle_tpu.jit.to_static`
  the same Tensors wrap tracers, so one code path serves eager and compiled mode.
- `jax.vjp` at op granularity stores residuals exactly like TensorWrapper saved
  inputs in the reference — but XLA owns the memory (BFC allocator), replacing
  AutoGrowthBestFitAllocator (reference: paddle/phi/core/memory/allocation/).
"""

from __future__ import annotations

import weakref
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from . import dtypes as _dtypes
from . import staging as _staging

__all__ = [
    "Tensor",
    "Parameter",
    "EagerParamBase",
    "no_grad",
    "enable_grad",
    "set_grad_enabled",
    "is_grad_enabled",
    "execute",
    "to_tensor",
    "grad_enabled",
]

# ---------------------------------------------------------------------------
# global autograd mode + trace context
# ---------------------------------------------------------------------------

_GRAD_ENABLED = True

# Active jit.to_static trace context (or None). While tracing, Tensor data may
# be jax tracers; buffer mutations are routed through buffer_update() so the
# compiled function can carry them as explicit outputs (the functional
# equivalent of the reference's in-place running-stat updates).
_TRACE_CTX = None


class TraceContext:
    def __init__(self):
        self.mutations = {}  # id(tensor) -> tensor (latest value in ._data)

    def __enter__(self):
        global _TRACE_CTX
        self._prev = _TRACE_CTX
        _TRACE_CTX = self
        return self

    def __exit__(self, *exc):
        global _TRACE_CTX
        _TRACE_CTX = self._prev
        return False


def in_trace():
    return _TRACE_CTX is not None


def buffer_update(t, arr):
    """Mutate a buffer tensor (e.g. BN running stats) in a trace-safe way."""
    if _TRACE_CTX is not None:
        _TRACE_CTX.mutations[id(t)] = t
    t._data = arr


def is_grad_enabled() -> bool:
    """Mirror of paddle.is_grad_enabled (reference: python/paddle/base/dygraph/base.py)."""
    return _GRAD_ENABLED


def grad_enabled() -> bool:
    return _GRAD_ENABLED


class set_grad_enabled:
    """Context manager / function toggling grad recording."""

    def __init__(self, mode: bool):
        global _GRAD_ENABLED
        self.prev = _GRAD_ENABLED
        _GRAD_ENABLED = bool(mode)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        global _GRAD_ENABLED
        _GRAD_ENABLED = self.prev
        return False


class _NoGrad:
    """paddle.no_grad: usable as decorator and context manager."""

    def __call__(self, func=None):
        if func is None:
            return self
        import functools

        @functools.wraps(func)
        def wrapper(*a, **k):
            with _NoGrad():
                return func(*a, **k)

        return wrapper

    def __enter__(self):
        global _GRAD_ENABLED
        self._prev = _GRAD_ENABLED
        _GRAD_ENABLED = False
        return self

    def __exit__(self, *exc):
        global _GRAD_ENABLED
        _GRAD_ENABLED = self._prev
        return False


def no_grad(func=None):
    ng = _NoGrad()
    if func is not None:
        return ng(func)
    return ng


class enable_grad(_NoGrad):
    def __enter__(self):
        global _GRAD_ENABLED
        self._prev = _GRAD_ENABLED
        _GRAD_ENABLED = True
        return self


# ---------------------------------------------------------------------------
# autograd graph
# ---------------------------------------------------------------------------

_node_counter = 0


class Node:
    """One recorded op: the analog of a GradNodeBase + its Edges.

    reference: paddle/fluid/eager/grad_node_info.h:197 (GradNodeBase),
    :53 (Edge). Here the "grad kernel" is the jax.vjp closure, which XLA
    has already specialized to the forward's shapes/dtypes.
    """

    __slots__ = (
        "id",
        "name",
        "vjp_fn",
        "fwd_fn",
        "tape_vjp_fn",
        "in_arrays",
        "in_dtypes",
        "inputs",
        "in_nodes",
        "out_refs",
        "out_shapes",
        "out_dtypes",
        "out_treedef",
        "__weakref__",
    )

    def __init__(self, name, vjp_fn, inputs, out_tensors, out_treedef):
        global _node_counter
        _node_counter += 1
        self.id = _node_counter
        self.name = name
        self.vjp_fn = vjp_fn
        # create_graph support: the recorded forward (set by execute) lets
        # the backward walk re-derive a vjp AS TAPE OPS; custom nodes
        # (PyLayer) instead provide tape_vjp_fn running their python
        # backward on live tape tensors (reference: GeneralGrad +
        # double_grad kernels, paddle/fluid/eager/backward.cc:105)
        self.fwd_fn = None
        self.tape_vjp_fn = None
        self.in_arrays = None   # recorded diff input arrays (create_graph)
        self.in_dtypes = None   # post-AMP-cast dtypes fwd_fn was traced at
        self.inputs = inputs  # list[Tensor] — differentiable inputs
        # snapshot producer nodes NOW: in-place rebinds may later repoint a
        # tensor's ._node at a different node (x.add_() aliasing)
        self.in_nodes = [t._node for t in inputs]
        self.out_refs = [weakref.ref(t) for t in out_tensors]
        self.out_shapes = [t._data.shape for t in out_tensors]
        self.out_dtypes = [t._data.dtype for t in out_tensors]
        self.out_treedef = out_treedef


def _collect_topo(root_node):
    """DFS from root, return nodes sorted by id descending (reverse topo).

    Node ids increase monotonically with execution order, so descending id
    order is a valid reverse-topological order — same trick as the in-degree
    queue in egr::RunBackward (reference: paddle/fluid/eager/backward.cc:105)
    but without needing an explicit in-degree map.
    """
    seen = set()
    stack = [root_node]
    order = []
    while stack:
        node = stack.pop()
        if node is None or node.id in seen:
            continue
        seen.add(node.id)
        order.append(node)
        for n in node.in_nodes:
            if n is not None:
                stack.append(n)
    order.sort(key=lambda n: n.id, reverse=True)
    return order


def _run_backward(tensors, grad_tensors=None, retain_graph=False, capture=None,
                  create_graph=False):
    """Reverse-mode walk. reference: paddle/fluid/eager/backward.cc:105.

    If `capture` is a dict {id(tensor): tensor}, accumulated cotangents for
    those tensors are returned in a dict instead of / in addition to being
    deposited into `.grad` (serves paddle.grad / GeneralGrad,
    reference: paddle/fluid/eager/backward.cc GeneralGrad).

    With create_graph=True every cotangent is itself a live tape Tensor and
    each node's backward runs through execute() (re-deriving the vjp from
    the node's recorded forward), so the returned gradients can be
    differentiated again — the reference's double-grad path
    (test/legacy_test/test_imperative_double_grad.py)."""
    if not isinstance(tensors, (list, tuple)):
        tensors = [tensors]
    if grad_tensors is None:
        grad_tensors = [None] * len(tensors)
    elif not isinstance(grad_tensors, (list, tuple)):
        grad_tensors = [grad_tensors]

    # pending cotangents keyed by tensor identity (raw arrays normally;
    # live tape Tensors under create_graph)
    pending: dict[int, Any] = {}
    keep: dict[int, Tensor] = {}

    roots = []
    for t, g in zip(tensors, grad_tensors):
        if t.stop_gradient:
            raise RuntimeError(
                "backward() on a tensor with stop_gradient=True has no effect"
            )
        if g is None:
            if t._data.size != 1:
                raise RuntimeError(
                    "grad can be implicitly created only for scalar outputs"
                )
            g_arr = jnp.ones_like(t._data)
            if create_graph:
                g_arr = Tensor(g_arr, stop_gradient=True)
        elif create_graph:
            # keep the caller's Tensor intact: its own history must stay
            # differentiable through the second backward
            g_arr = g if isinstance(g, Tensor) else Tensor(jnp.asarray(g))
        else:
            g_arr = g._data if isinstance(g, Tensor) else jnp.asarray(g)
        _accum(pending, keep, t, g_arr)
        if t._node is not None:
            roots.append(t._node)

    captured = {} if capture is not None else None

    # leaf roots: just deposit grad
    if not roots:
        for t in tensors:
            g = pending.pop(id(t), None)
            if g is not None:
                g = _apply_grad_hooks(t, g)
            if capture is not None and id(t) in capture:
                captured[id(t)] = g
            else:
                _deposit_leaf_grad(t, g)
        return captured

    # leaf grads accumulate here and deposit once at the end, so gradient
    # hooks observe the COMPLETE gradient (a leaf consumed by several ops
    # receives one hook call, not one per contribution)
    leaf_pending: dict[int, Any] = {}
    leaf_keep: dict[int, Tensor] = {}

    nodes = []
    seen = set()
    for r in roots:
        for n in _collect_topo(r):
            if n.id not in seen:
                seen.add(n.id)
                nodes.append(n)
    nodes.sort(key=lambda n: n.id, reverse=True)

    for node in nodes:
        cots = []
        has_any = False
        for ref, shape, dtype in zip(node.out_refs, node.out_shapes, node.out_dtypes):
            t = ref()
            c = None
            if t is not None:
                c = pending.pop(id(t), None)
                keep.pop(id(t), None)
                # cotangent for t is complete here (all consumer nodes have
                # higher ids and were already processed) — hook + capture point
                if c is not None:
                    c = _apply_grad_hooks(t, c)
                if c is not None and capture is not None and id(t) in capture:
                    captured[id(t)] = c
            if c is None:
                c = jnp.zeros(shape, dtype)
                if create_graph:
                    c = Tensor(c, stop_gradient=True)
            else:
                has_any = True
                if c.dtype != dtype:
                    # mixed-precision graphs (AMP): a downstream op may hand
                    # back an fp32 cotangent for a bf16 output; jax.vjp
                    # requires the exact recorded dtype
                    c = c.astype(dtype)
            cots.append(c)
        if not has_any:
            continue
        if create_graph:
            in_cots = _node_backward_recorded(node, cots)
        else:
            cot_tree = jax.tree_util.tree_unflatten(node.out_treedef, cots)
            in_cots = node.vjp_fn(cot_tree)
        _maybe_check_nan(in_cots, node.name + "_grad")
        if not retain_graph:
            node.vjp_fn = None
            node.fwd_fn = None
            node.tape_vjp_fn = None  # PyLayer: free ctx + saved activations
            node.in_arrays = None
        for t, rec_node, c in zip(node.inputs, node.in_nodes, in_cots):
            if rec_node is None:
                _accum(leaf_pending, leaf_keep, t, c)
            else:
                _accum(pending, keep, t, c)

    # anything left pending whose node was unreachable: treat as leaf
    for tid, c in pending.items():
        t = keep.get(tid)
        if t is None and capture is not None:
            t = capture.get(tid)
        if t is not None and (t._node is None or (capture is not None
                                                  and tid in capture)):
            _accum(leaf_pending, leaf_keep, t, c)

    # flush complete leaf gradients: hooks fire once, then capture/deposit
    for tid, c in leaf_pending.items():
        t = leaf_keep[tid]
        c = _apply_grad_hooks(t, c)
        if capture is not None and tid in capture:
            captured[tid] = captured[tid] + c if tid in captured else c
        else:
            _deposit_leaf_grad(t, c)
    return captured


def _accum(pending, keep, t, g):
    tid = id(t)
    if tid in pending:
        pending[tid] = pending[tid] + g
    else:
        pending[tid] = g
        keep[tid] = t


def _node_backward_recorded(node, cot_tensors):
    """One node's backward as RECORDED ops: gradients come out as live tape
    Tensors whose history covers both the node's primal inputs and the
    incoming cotangents, so a second backward differentiates through them.
    reference: the generated double_grad kernels + GeneralGrad
    (paddle/fluid/eager/backward.cc:105)."""
    if node.tape_vjp_fn is not None:  # PyLayer: user backward on live tensors
        return node.tape_vjp_fn(cot_tensors)
    fwd = node.fwd_fn
    if fwd is None:
        raise RuntimeError(
            f"create_graph=True: node '{node.name}' was recorded without a "
            "re-differentiable forward (its graph was already freed by an "
            "earlier backward without retain_graph)")
    k = len(node.inputs)
    for t, rec in zip(node.inputs, node.in_arrays):
        if t._data is not rec:
            # the recompute would evaluate at the MUTATED value and silently
            # disagree with the recorded residuals (torch raises the same way
            # for in-place modification of needed variables)
            raise RuntimeError(
                f"create_graph=True: an input of '{node.name}' was modified "
                "in-place after the forward; its second-order gradient "
                "would be computed at the new value. Clone the tensor "
                "before mutating it.")
    treedef = node.out_treedef
    in_dtypes = node.in_dtypes

    def grad_op(*args):
        primals, cots = args[:k], args[k:]
        # re-apply the recorded (possibly AMP-cast) trace dtypes: fwd_fn
        # was traced over post-cast arrays and the cotangents carry the
        # recorded output dtypes
        primals = tuple(
            p.astype(dt) if p.dtype != dt else p
            for p, dt in zip(primals, in_dtypes))
        _, vjp_fn = jax.vjp(fwd, *primals)
        return tuple(vjp_fn(jax.tree_util.tree_unflatten(treedef, list(cots))))

    try:
        out = execute(grad_op, *node.inputs, *cot_tensors,
                      _name=node.name + "_grad")
    except Exception as e:
        msg = str(e)
        import traceback as _tb
        tb_text = "".join(_tb.format_exception(type(e), e, e.__traceback__))
        if "custom_vjp" in msg or "custom_jvp" in msg \
                or "pallas" in tb_text.lower():
            # the recorded forward contains a kernel whose backward is not
            # itself differentiable (e.g. a raw pallas_call custom_vjp) and
            # no dense _ho_fwd was registered for it
            raise RuntimeError(
                f"create_graph=True through '{node.name}': this op's "
                f"backward is not re-differentiable "
                f"({type(e).__name__}: {msg[:240]}). Re-run the forward on "
                "the op's dense/XLA fallback for higher-order gradients — "
                "for attention, set FLAGS_flash_attention_backend=xla."
            ) from e
        raise
    return out if isinstance(out, (list, tuple)) else (out,)


def _apply_grad_hooks(t, g):
    """Run a tensor's registered gradient hooks over its complete cotangent.
    reference: paddle/fluid/eager/hooks.h (TensorHook::operator())."""
    hooks = t.__dict__.get("_grad_hooks") if hasattr(t, "__dict__") else None
    if not hooks:
        return g
    live = isinstance(g, Tensor)  # create_graph: keep the tape alive
    for hook in list(hooks.values()):
        r = hook(g if live else Tensor(g, stop_gradient=True))
        if r is None:
            continue
        if live:
            g = r if isinstance(r, Tensor) else Tensor(jnp.asarray(r))
        else:
            g = r._data if isinstance(r, Tensor) else jnp.asarray(r)
    return g


def _deposit_leaf_grad(t, g):
    if g is None or t.stop_gradient:
        return
    if isinstance(g, Tensor):  # create_graph walk: .grad stays detached
        g = g._data
    if t._grad is None:
        t._grad = Tensor(g, stop_gradient=True)
    else:
        t._grad = Tensor(t._grad._data + g, stop_gradient=True)


# ---------------------------------------------------------------------------
# op execution + recording
# ---------------------------------------------------------------------------


_STAGING_SCOPE = None  # set by framework.staging.StagingScope (graph breaks)


def _unwrap(x):
    if isinstance(x, Tensor):
        d = x._data
        if isinstance(d, _staging.StagedBox):
            return d.real if d.real is not None else d._materialize()
        return d
    return x


# AMP cast hook installed by paddle_tpu.amp (kept as a function pointer to
# avoid a circular import). Signature: (name, arrays) -> arrays.
_amp_cast_hook = None
_op_observer_hook = None  # amp.debugging operator-stats collection

def _maybe_check_nan(out, name):
    """FLAGS_check_nan_inf: scan op outputs for NaN/Inf when enabled.
    reference: paddle/fluid/eager/nan_inf_utils.h CheckTensorHasNanOrInf —
    there a per-kernel device scan; here one jnp.isfinite reduce per output
    (eager only: traced values are abstract, and jit programs get checked
    at their eager call sites)."""
    from . import flags as _flags
    if not _flags.flag_value("check_nan_inf") or _TRACE_CTX is not None:
        return out
    for leaf in jax.tree_util.tree_leaves(out):
        if (hasattr(leaf, "dtype")
                and jnp.issubdtype(leaf.dtype, jnp.inexact)
                and not bool(jnp.all(jnp.isfinite(leaf)))):
            msg = (f"Operator '{name}' output contains NaN or Inf "
                   f"(FLAGS_check_nan_inf is set)")
            if _flags.flag_value("check_nan_inf_level") == 0:
                raise RuntimeError(msg)
            import warnings
            warnings.warn(msg, RuntimeWarning)
    return out


def execute(f: Callable, *inputs, _name: str = None, _ho_fwd: Callable = None,
            **static_kwargs):
    """Run pure jax function `f(*arrays, **static_kwargs)`, recording a vjp
    Node if any Tensor input requires grad.

    This is the single dispatch point replacing the reference's generated
    `*_ad_func` forward functions (paddle/fluid/eager/auto_code_generator/
    generator/eager_gen.py) — one generic recorder instead of 1600 generated
    C++ grad-node classes, because jax.vjp derives the backward for free.
    AMP auto-cast (reference: paddle/fluid/eager/amp_auto_cast.h) hooks in
    here too, as does the NaN/Inf scanner.
    """
    if _STAGING_SCOPE is not None and _STAGING_SCOPE.active:
        # graph-break staged mode: defer the op into the prefix DAG
        return _STAGING_SCOPE.stage(f, inputs, _name, static_kwargs)

    arrs = [_unwrap(x) for x in inputs]
    if _amp_cast_hook is not None:
        arrs = _amp_cast_hook(_name or getattr(f, "__name__", "op"), arrs)
    if _op_observer_hook is not None:  # amp.debugging op stats: POST-cast
        # dtypes, so the table shows the precision ops actually ran in
        _op_observer_hook(_name or getattr(f, "__name__", "op"), arrs)

    diff_idx = []
    if _GRAD_ENABLED:
        for i, x in enumerate(inputs):
            if isinstance(x, Tensor) and not x.stop_gradient and not jnp.issubdtype(
                x._data.dtype, jnp.integer
            ) and x._data.dtype != jnp.bool_:
                diff_idx.append(i)

    if _TRACE_CTX is not None:
        # Inside a to_static trace: don't record per-op vjp nodes (the whole
        # graph gets one outer vjp); express stop_gradient barriers directly
        # in the traced graph so the outer vjp respects them.
        for i, x in enumerate(inputs):
            if (isinstance(x, Tensor) and x.stop_gradient
                    and jnp.issubdtype(jnp.asarray(arrs[i]).dtype, jnp.inexact)):
                arrs[i] = jax.lax.stop_gradient(arrs[i])
        out = f(*arrs, **static_kwargs)
        return _wrap_outputs(out, stop_gradient=not diff_idx)

    if not diff_idx:
        out = f(*arrs, **static_kwargs)
        _maybe_check_nan(out, _name or getattr(f, "__name__", "op"))
        return _wrap_outputs(out, stop_gradient=True)

    const = list(arrs)

    def _close_over_consts(fn):
        def g(*diff_arrs):
            full = list(const)
            for i, a in zip(diff_idx, diff_arrs):
                full[i] = a
            return fn(*full, **static_kwargs)
        return g

    g = _close_over_consts(f)
    diff_arrs = [arrs[i] for i in diff_idx]
    out, vjp_fn = jax.vjp(g, *diff_arrs)
    _maybe_check_nan(out, _name or getattr(f, "__name__", "op"))

    flat, treedef = jax.tree_util.tree_flatten(out)
    # only record if at least one output is inexact (differentiable)
    if not any(jnp.issubdtype(jnp.asarray(o).dtype, jnp.inexact) for o in flat):
        return _wrap_outputs(out, stop_gradient=True)

    out_tensors = [Tensor(o, stop_gradient=False) for o in flat]
    node = Node(
        _name or getattr(f, "__name__", "op"),
        vjp_fn,
        [inputs[i] for i in diff_idx],
        out_tensors,
        treedef,
    )
    # create_graph: re-derivable vjp over the same consts. An op whose
    # primal path uses a custom_vjp Pallas kernel (not differentiable past
    # first order) may hand a mathematically-equal dense `_ho_fwd`; the
    # recorded forward is then the dense one, so higher-order grads work
    # while the first-order path keeps the fast kernel.
    node.fwd_fn = g if _ho_fwd is None else _close_over_consts(_ho_fwd)
    # pre-cast originals (mutation detection) + post-cast trace dtypes
    node.in_arrays = [inputs[i]._data for i in diff_idx]
    node.in_dtypes = [a.dtype for a in diff_arrs]
    for t in out_tensors:
        t._node = node
    return jax.tree_util.tree_unflatten(treedef, out_tensors)


def _wrap_outputs(out, stop_gradient=True):
    return jax.tree_util.tree_map(
        lambda o: Tensor(o, stop_gradient=stop_gradient), out
    )


# ---------------------------------------------------------------------------
# Tensor
# ---------------------------------------------------------------------------


class Tensor:
    """Imperative tensor over jax.Array.

    API parity model: paddle.Tensor (reference: paddle/phi/api/include/tensor.h
    + python monkey patches in python/paddle/base/dygraph/tensor_patch_methods.py).
    `stop_gradient` defaults True like paddle; Parameters set it False.
    """

    __slots__ = ("_data", "stop_gradient", "_grad", "_node", "name", "persistable", "__weakref__", "__dict__")

    def __init__(self, data, dtype=None, place=None, stop_gradient=True, name=None):
        if isinstance(data, Tensor):
            data = data._data
        if not isinstance(data, jax.Array):
            if dtype is not None:
                data = jnp.asarray(data, dtype=_dtypes.convert_dtype(dtype))
            else:
                data = _dtypes.asarray_default(data)
        elif dtype is not None:
            dt = _dtypes.convert_dtype(dtype)
            if data.dtype != dt:
                data = data.astype(dt)
        self._data = data
        self.stop_gradient = stop_gradient
        self._grad = None
        self._node = None
        self.name = name
        self.persistable = False

    # -- basic properties ---------------------------------------------------
    @property
    def shape(self):
        return list(self._data.shape)

    @property
    def ndim(self):
        return self._data.ndim

    @property
    def dim(self):
        return self._data.ndim

    @property
    def size(self):
        return int(self._data.size)

    @property
    def dtype(self):
        return self._data.dtype

    @property
    def place(self):
        devs = getattr(self._data, "devices", None)
        if devs is None:
            return "unknown"
        try:
            return str(next(iter(self._data.devices())))
        except Exception:
            return "unknown"

    @property
    def grad(self):
        return self._grad

    @grad.setter
    def grad(self, value):
        if value is not None and not isinstance(value, Tensor):
            value = Tensor(value)
        self._grad = value

    @property
    def is_leaf(self):
        return self._node is None

    @property
    def T(self):
        from ..tensor import linalg

        return linalg.transpose_last2(self) if self.ndim >= 2 else self

    # -- conversion ---------------------------------------------------------
    def numpy(self):
        return np.asarray(self._data)

    def __array__(self, dtype=None):
        a = np.asarray(self._data)
        return a.astype(dtype) if dtype is not None else a

    def __jax_array__(self):
        return self._data

    def item(self, *args):
        return self._data.item(*args)

    def tolist(self):
        return np.asarray(self._data).tolist()

    def astype(self, dtype):
        dt = _dtypes.convert_dtype(dtype)
        return execute(lambda a: a.astype(dt), self, _name="cast")

    cast = astype

    def detach(self):
        data = self._data
        if _TRACE_CTX is not None and jnp.issubdtype(data.dtype, jnp.inexact):
            data = jax.lax.stop_gradient(data)
        return Tensor(data, stop_gradient=True)

    def detach_(self):
        self._node = None
        self.stop_gradient = True
        return self

    def clone(self):
        return execute(lambda a: a + 0 if jnp.issubdtype(a.dtype, jnp.inexact) else jnp.array(a), self, _name="clone")

    def numel(self):
        return int(self._data.size)

    def element_size(self):
        return self._data.dtype.itemsize

    # -- autograd -----------------------------------------------------------
    def backward(self, grad_tensor=None, retain_graph=False):
        _run_backward(self, grad_tensor, retain_graph=retain_graph)

    def clear_grad(self):
        self._grad = None

    def clear_gradient(self, set_to_zero=False):
        if set_to_zero and self._grad is not None:
            self._grad = Tensor(jnp.zeros_like(self._grad._data))
        else:
            self._grad = None

    def register_hook(self, hook):
        """Call hook(grad) when this tensor's gradient is computed during
        backward; a non-None return value replaces the gradient.
        reference: tensor_patch_methods.py register_hook /
        paddle/fluid/eager/hooks.h TensorHook. Returns a removable handle."""
        if self.stop_gradient:
            raise RuntimeError(
                "register_hook on a tensor with stop_gradient=True is "
                "meaningless (no gradient will ever be computed)")
        hooks = self.__dict__.setdefault("_grad_hooks", {})
        hid = self.__dict__.get("_grad_hook_next", 0)
        self.__dict__["_grad_hook_next"] = hid + 1  # ids never reused, so a
        # stale handle's second remove() can't delete a later hook
        hooks[hid] = hook

        class _HookHandle:
            def remove(_self):
                hooks.pop(hid, None)
                return True

        return _HookHandle()

    # -- in-place helpers ---------------------------------------------------
    def _rebind(self, new: "Tensor"):
        """In-place semantics (x.add_(y)): rebind data + node, keeping this
        Python object. Functional under the hood (no aliasing), which keeps
        autograd sound — the reference needs inplace version counters
        (paddle/fluid/eager/autograd_meta.h) for the same safety."""
        self._data = new._data
        self._node = new._node
        if self._node is not None:
            # repoint the node's weakref output to self so cotangents route here
            for i, ref in enumerate(self._node.out_refs):
                if ref() is new:
                    self._node.out_refs[i] = weakref.ref(self)
        self.stop_gradient = new.stop_gradient and self.stop_gradient
        return self

    def set_value(self, value):
        if isinstance(value, Tensor):
            arr = value._data
        else:
            arr = jnp.asarray(value)
        self._data = arr.astype(self._data.dtype).reshape(self._data.shape)
        return self

    def copy_(self, other, blocking=True):
        return self.set_value(other)

    def fill_(self, value):
        self._data = jnp.full_like(self._data, value)
        return self

    def zero_(self):
        self._data = jnp.zeros_like(self._data)
        return self

    # -- python protocol ----------------------------------------------------
    def __len__(self):
        if self.ndim == 0:
            raise TypeError("len() of a 0-d tensor")
        return self._data.shape[0]

    def __repr__(self):
        sg = self.stop_gradient
        return (
            f"Tensor(shape={self.shape}, dtype={_dtypes.dtype_name(self.dtype)}, "
            f"stop_gradient={sg},\n       {np.asarray(self._data)})"
        )

    def __bool__(self):
        return bool(self._data)

    def __int__(self):
        return int(self._data)

    def __float__(self):
        return float(self._data)

    def __index__(self):
        return int(self._data)

    def __format__(self, spec):
        if self.ndim == 0:
            return format(self.item(), spec)
        return repr(self)

    def __iter__(self):
        for i in range(len(self)):
            yield self[i]

    def __getitem__(self, idx):
        idx = _index_unwrap(idx)
        return execute(lambda a: a[idx], self, _name="getitem")

    def __setitem__(self, idx, value):
        idx = _index_unwrap(idx)
        v = value._data if isinstance(value, Tensor) else value
        new = execute(
            lambda a, v=v: a.at[idx].set(v if not isinstance(v, jax.Array) else v.astype(a.dtype)),
            self,
            _name="setitem",
        )
        self._rebind(new)

    def __hash__(self):
        return id(self)

    def dims(self):
        return self.shape

    def cpu(self):
        return self

    def cuda(self, *a, **k):
        return self

    def to(self, *args, **kwargs):
        # to(dtype) / to(device) / to(device, dtype)
        out = self
        for a in list(args) + list(kwargs.values()):
            if isinstance(a, str) and a in _dtypes.NAME2DTYPE:
                out = out.astype(a)
            elif hasattr(a, "dtype") or a in (None,):
                pass
        return out

    def pin_memory(self):
        return self


def _index_unwrap(idx):
    if isinstance(idx, Tensor):
        return idx._data
    if isinstance(idx, tuple):
        return tuple(_index_unwrap(i) for i in idx)
    if isinstance(idx, list):
        return jnp.asarray(idx)
    return idx


_live_parameters = weakref.WeakValueDictionary()
_param_counter = 0


def live_parameters():
    """All live Parameters in creation order — used by jit.to_static to lift
    closure-captured params into traced inputs."""
    return [p for _, p in sorted(_live_parameters.items())]


class Parameter(Tensor):
    """Trainable tensor: stop_gradient=False, tracked by Layer.

    reference: python/paddle/base/framework.py EagerParamBase."""

    def __init__(self, data, dtype=None, name=None, trainable=True):
        global _param_counter
        super().__init__(data, dtype=dtype, stop_gradient=not trainable)
        self.name = name
        self.persistable = True
        _param_counter += 1
        self._param_uid = _param_counter
        _live_parameters[_param_counter] = self

    @property
    def trainable(self):
        return not self.stop_gradient

    @trainable.setter
    def trainable(self, v):
        self.stop_gradient = not v

    def initialize(self):
        """Draw the initial value a `paddle.LazyGuard` put off (reference:
        EagerParamBase.initialize). A no-op for any other parameter."""
        pending = self.__dict__.pop("_lazy_initializer", None)
        if pending is not None:
            init, shape, dtype = pending
            self._data = init._init(shape, dtype)

    def __repr__(self):
        return "Parameter containing:\n" + super().__repr__()


EagerParamBase = Parameter


def to_tensor(data, dtype=None, place=None, stop_gradient=True):
    """paddle.to_tensor (reference: python/paddle/tensor/creation.py:to_tensor)."""
    if isinstance(data, Tensor) and dtype is None:
        t = Tensor(data._data, stop_gradient=stop_gradient)
        return t
    return Tensor(data, dtype=dtype, stop_gradient=stop_gradient)
