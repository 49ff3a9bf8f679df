"""nn.Layer base class. reference: python/paddle/nn/layer/layers.py.

Holds Parameters (Tensors with stop_gradient=False) in registries so the
imperative API works eagerly while jit.to_static can lift the same layer into
a pure function (params/buffers become traced inputs/outputs) for XLA.
"""

from __future__ import annotations

import collections
from typing import Callable, Iterator

import jax.numpy as jnp
import numpy as np

from ...framework import dtypes as _dt
from ...framework.core import Parameter, Tensor

__all__ = ["Layer", "LayerList", "ParameterList", "Sequential", "LayerDict"]

_lazy_init = False      # True inside `with paddle.LazyGuard():`


class HookRemoveHelper:
    _next_id = 0

    def __init__(self, hooks):
        self._hooks = hooks
        self._id = HookRemoveHelper._next_id
        HookRemoveHelper._next_id += 1

    def remove(self):
        self._hooks.pop(self._id, None)


class Layer:
    def __init__(self, name_scope=None, dtype="float32"):
        self.training = True
        self._dtype = dtype
        self._parameters = collections.OrderedDict()
        self._sub_layers = collections.OrderedDict()
        self._buffers = collections.OrderedDict()
        self._non_persistable_buffer_names = set()
        self._forward_pre_hooks = collections.OrderedDict()
        self._forward_post_hooks = collections.OrderedDict()
        self._name_scope = name_scope or type(self).__name__.lower()
        self._casted_dtype = None

    # -- construction -------------------------------------------------------
    def create_parameter(self, shape, attr=None, dtype=None, is_bias=False,
                         default_initializer=None):
        """reference: python/paddle/nn/layer/layers.py:create_parameter —
        default init: XavierUniform-ish for weights, zeros for bias (matches
        LayerHelper defaults)."""
        from .. import initializer as I

        dtype = _dt.convert_dtype(dtype or self._dtype)
        init = default_initializer
        trainable = True
        name = None
        lr = 1.0
        if attr is False:
            return None
        if attr is not None and not isinstance(attr, (bool,)):
            init = getattr(attr, "initializer", None) or init
            trainable = getattr(attr, "trainable", True)
            name = getattr(attr, "name", None)
            lr = getattr(attr, "learning_rate", 1.0)
        if init is None:
            # set_global_initializer overrides the built-in defaults for
            # params whose ParamAttr carries no explicit initializer
            # (reference: nn/initializer/set_global_initializer)
            init = I._global_bias_init if is_bias else I._global_weight_init
        if init is None:
            init = I.Constant(0.0) if is_bias else I.XavierUniform()
        shape = tuple(int(s) for s in shape)
        if _lazy_init:
            # paddle.LazyGuard: zeros now, the draw when (if) the caller
            # asks for it with Parameter.initialize()
            p = Parameter(jnp.zeros(shape, dtype), name=name,
                          trainable=trainable)
            p._lazy_initializer = (init, shape, dtype)
        else:
            p = Parameter(init._init(shape, dtype), name=name,
                          trainable=trainable)
        p.optimize_attr = {"learning_rate": lr}
        return p

    def add_parameter(self, name, parameter):
        if parameter is not None and not isinstance(parameter, Parameter):
            raise TypeError("add_parameter expects a Parameter")
        self._parameters[name] = parameter
        return parameter

    def add_sublayer(self, name, sublayer):
        self._sub_layers[str(name)] = sublayer
        return sublayer

    def register_buffer(self, name, tensor, persistable=True):
        self._buffers[name] = tensor
        if tensor is not None:
            tensor.persistable = persistable
        if not persistable:
            self._non_persistable_buffer_names.add(name)
        return tensor

    # -- attribute magic ----------------------------------------------------
    def __setattr__(self, name, value):
        params = self.__dict__.get("_parameters")
        layers = self.__dict__.get("_sub_layers")
        buffers = self.__dict__.get("_buffers")
        if isinstance(value, Parameter):
            if params is None:
                raise RuntimeError("call super().__init__() first")
            for d in (layers, buffers):
                if d is not None:
                    d.pop(name, None)
            params[name] = value
        elif isinstance(value, Layer):
            if layers is None:
                raise RuntimeError("call super().__init__() first")
            for d in (params, buffers):
                if d is not None:
                    d.pop(name, None)
            layers[name] = value
        elif buffers is not None and name in buffers:
            buffers[name] = value
        else:
            object.__setattr__(self, name, value)

    def __getattr__(self, name):
        for key in ("_parameters", "_sub_layers", "_buffers"):
            d = self.__dict__.get(key)
            if d is not None and name in d:
                return d[name]
        raise AttributeError(f"{type(self).__name__!r} has no attribute {name!r}")

    def __delattr__(self, name):
        for key in ("_parameters", "_sub_layers", "_buffers"):
            d = self.__dict__.get(key)
            if d is not None and name in d:
                del d[name]
                return
        object.__delattr__(self, name)

    def __dir__(self):
        return list(super().__dir__()) + list(self._parameters) + \
            list(self._sub_layers) + list(self._buffers)

    # -- traversal ----------------------------------------------------------
    def named_parameters(self, prefix="", include_sublayers=True):
        seen = set()
        for name, layer in self.named_sublayers(prefix=prefix, include_self=True):
            for pname, p in layer._parameters.items():
                if p is None or id(p) in seen:
                    continue
                seen.add(id(p))
                yield (name + "." + pname if name else pname), p
            if not include_sublayers:
                break

    def parameters(self, include_sublayers=True):
        return [p for _, p in self.named_parameters(include_sublayers=include_sublayers)]

    def named_sublayers(self, prefix="", include_self=False, layers_set=None):
        if layers_set is None:
            layers_set = set()
        if id(self) in layers_set:
            return
        layers_set.add(id(self))
        if include_self:
            yield prefix, self
        for name, layer in self._sub_layers.items():
            if layer is None:
                continue
            sub_prefix = prefix + "." + name if prefix else name
            yield from layer.named_sublayers(prefix=sub_prefix, include_self=True,
                                             layers_set=layers_set)

    def sublayers(self, include_self=False):
        return [l for _, l in self.named_sublayers(include_self=include_self)]

    def children(self):
        return [l for l in self._sub_layers.values() if l is not None]

    def named_children(self):
        return [(n, l) for n, l in self._sub_layers.items() if l is not None]

    def named_buffers(self, prefix="", include_sublayers=True):
        seen = set()
        for name, layer in self.named_sublayers(prefix=prefix, include_self=True):
            for bname, b in layer._buffers.items():
                if b is None or id(b) in seen:
                    continue
                seen.add(id(b))
                yield (name + "." + bname if name else bname), b

    def buffers(self, include_sublayers=True):
        return [b for _, b in self.named_buffers(include_sublayers=include_sublayers)]

    # -- mode ---------------------------------------------------------------
    def train(self):
        for layer in self.named_sublayers(include_self=True):
            layer[1].training = True
        return self

    def eval(self):
        for layer in self.named_sublayers(include_self=True):
            layer[1].training = False
        return self

    def apply(self, fn):
        for layer in self.sublayers(include_self=True):
            fn(layer)
        return self

    def to(self, device=None, dtype=None, blocking=None):
        if dtype is not None:
            dt = _dt.convert_dtype(dtype)
            for _, p in self.named_parameters():
                if jnp.issubdtype(p._data.dtype, jnp.floating):
                    p._data = p._data.astype(dt)
            for _, b in self.named_buffers():
                if b is not None and jnp.issubdtype(b._data.dtype, jnp.floating):
                    b._data = b._data.astype(dt)
        return self

    def astype(self, dtype):
        return self.to(dtype=dtype)

    def float(self):
        return self.to(dtype="float32")

    def half(self):
        return self.to(dtype="float16")

    def bfloat16(self):
        return self.to(dtype="bfloat16")

    # -- state dict ---------------------------------------------------------
    def state_dict(self, destination=None, include_sublayers=True,
                   structured_name_prefix="", use_hook=True):
        dest = destination if destination is not None else collections.OrderedDict()
        for name, p in self.named_parameters(prefix=structured_name_prefix.rstrip(".")):
            dest[name] = p
        for name, b in self.named_buffers(prefix=structured_name_prefix.rstrip(".")):
            if name.split(".")[-1] not in self._non_persistable_buffer_names:
                dest[name] = b
        return dest

    def set_state_dict(self, state_dict, use_structured_name=True):
        own = self.state_dict()
        missing, unexpected = [], []
        for name, t in own.items():
            if name in state_dict:
                v = state_dict[name]
                arr = v._data if isinstance(v, Tensor) else jnp.asarray(np.asarray(v))
                t._data = arr.astype(t._data.dtype).reshape(t._data.shape)
            else:
                missing.append(name)
        for name in state_dict:
            if name not in own:
                unexpected.append(name)
        return missing, unexpected

    load_dict = set_state_dict
    set_dict = set_state_dict

    # -- hooks + call -------------------------------------------------------
    def register_forward_pre_hook(self, hook):
        helper = HookRemoveHelper(self._forward_pre_hooks)
        self._forward_pre_hooks[helper._id] = hook
        return helper

    def register_forward_post_hook(self, hook):
        helper = HookRemoveHelper(self._forward_post_hooks)
        self._forward_post_hooks[helper._id] = hook
        return helper

    def forward(self, *inputs, **kwargs):
        raise NotImplementedError

    def __call__(self, *inputs, **kwargs):
        for hook in list(self._forward_pre_hooks.values()):
            result = hook(self, inputs)
            if result is not None:
                inputs = result if isinstance(result, tuple) else (result,)
        out = self.forward(*inputs, **kwargs)
        for hook in list(self._forward_post_hooks.values()):
            result = hook(self, inputs, out)
            if result is not None:
                out = result
        return out

    def full_name(self):
        return self._name_scope

    def extra_repr(self):
        return ""

    def __repr__(self):
        extra = self.extra_repr()
        lines = []
        for name, layer in self._sub_layers.items():
            mod_str = repr(layer)
            mod_str = "\n  ".join(mod_str.split("\n"))
            lines.append(f"({name}): {mod_str}")
        main = type(self).__name__ + "(" + extra
        if lines:
            main += "\n  " + "\n  ".join(lines) + "\n"
        return main + ")"

    def clear_gradients(self):
        for p in self.parameters():
            p.clear_grad()


class Sequential(Layer):
    """reference: python/paddle/nn/layer/container.py:Sequential."""

    def __init__(self, *layers):
        super().__init__()
        if len(layers) == 1 and isinstance(layers[0], collections.OrderedDict):
            for name, layer in layers[0].items():
                self.add_sublayer(name, layer)
        else:
            for i, layer in enumerate(layers):
                if isinstance(layer, tuple):
                    self.add_sublayer(layer[0], layer[1])
                else:
                    self.add_sublayer(str(i), layer)

    def __getitem__(self, idx):
        if isinstance(idx, slice):
            return Sequential(*list(self._sub_layers.values())[idx])
        keys = list(self._sub_layers.keys())
        return self._sub_layers[keys[idx]]

    def __len__(self):
        return len(self._sub_layers)

    def __iter__(self):
        return iter(self._sub_layers.values())

    def forward(self, input):
        for layer in self._sub_layers.values():
            input = layer(input)
        return input


class LayerList(Layer):
    def __init__(self, sublayers=None):
        super().__init__()
        if sublayers is not None:
            for i, layer in enumerate(sublayers):
                self.add_sublayer(str(i), layer)

    def __getitem__(self, idx):
        if isinstance(idx, slice):
            return LayerList(list(self._sub_layers.values())[idx])
        return self._sub_layers[str(idx if idx >= 0 else len(self) + idx)]

    def __setitem__(self, idx, layer):
        self._sub_layers[str(idx)] = layer

    def __len__(self):
        return len(self._sub_layers)

    def __iter__(self):
        return iter(self._sub_layers.values())

    def append(self, layer):
        self.add_sublayer(str(len(self)), layer)
        return self

    def insert(self, index, layer):
        layers = list(self._sub_layers.values())
        layers.insert(index, layer)
        self._sub_layers.clear()
        for i, l in enumerate(layers):
            self._sub_layers[str(i)] = l

    def extend(self, layers):
        for l in layers:
            self.append(l)
        return self


class LayerDict(Layer):
    def __init__(self, sublayers=None):
        super().__init__()
        if sublayers is not None:
            self.update(sublayers)

    def __getitem__(self, key):
        return self._sub_layers[key]

    def __setitem__(self, key, layer):
        self.add_sublayer(key, layer)

    def __delitem__(self, key):
        del self._sub_layers[key]

    def __len__(self):
        return len(self._sub_layers)

    def __iter__(self):
        return iter(self._sub_layers)

    def __contains__(self, key):
        return key in self._sub_layers

    def keys(self):
        return self._sub_layers.keys()

    def values(self):
        return self._sub_layers.values()

    def items(self):
        return self._sub_layers.items()

    def update(self, sublayers):
        items = sublayers.items() if isinstance(sublayers, (dict, collections.OrderedDict)) else sublayers
        for k, v in items:
            self[k] = v

    def pop(self, key):
        layer = self._sub_layers[key]
        del self._sub_layers[key]
        return layer

    def clear(self):
        self._sub_layers.clear()


class ParameterList(Layer):
    def __init__(self, parameters=None):
        super().__init__()
        if parameters is not None:
            for i, p in enumerate(parameters):
                self.add_parameter(str(i), p)

    def __getitem__(self, idx):
        return self._parameters[str(idx)]

    def __len__(self):
        return len(self._parameters)

    def __iter__(self):
        return iter(self._parameters.values())

    def append(self, parameter):
        self.add_parameter(str(len(self._parameters)), parameter)
        return self
