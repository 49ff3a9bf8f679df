"""paddle_tpu — a TPU-native deep learning framework with PaddlePaddle's
capabilities, built from scratch on JAX/XLA/Pallas.

Public surface mirrors `import paddle` (reference: python/paddle/__init__.py);
the implementation is an original TPU-first design: imperative tensors over
jax.Array, autograd via recorded jax.vjp nodes, jit.to_static = XLA step
compilation, distributed = GSPMD over jax.sharding.Mesh.
"""

from __future__ import annotations

__version__ = "0.1.0"

from .framework import dtypes as _dtypes
from .framework.core import (  # noqa: F401
    Tensor,
    Parameter,
    EagerParamBase,
    no_grad,
    enable_grad,
    set_grad_enabled,
    is_grad_enabled,
    to_tensor,
)
from .framework.dtypes import (  # noqa: F401
    bool_ as bool8,
    uint8, int8, int16, int32, int64,
    float16, bfloat16, float32, float64,
    complex64, complex128,
    set_default_dtype, get_default_dtype,
)

bool = _dtypes.bool_  # paddle.bool

from .framework.flags import set_flags, get_flags  # noqa: F401
from .framework.random import seed, get_rng_state, set_rng_state  # noqa: F401
from .framework.device import (  # noqa: F401
    set_device, get_device, device_count,
    CPUPlace, TPUPlace, CUDAPlace, CUDAPinnedPlace, XPUPlace,
    is_compiled_with_cuda, is_compiled_with_xpu, is_compiled_with_tpu,
)

from .tensor import *  # noqa: F401,F403
from .tensor import creation as _creation  # ensure registration

from . import nn  # noqa: F401
from . import optimizer  # noqa: F401
from . import autograd  # noqa: F401
from . import io  # noqa: F401
from . import amp  # noqa: F401
from . import jit  # noqa: F401
from . import metric  # noqa: F401
from . import vision  # noqa: F401
from . import distributed  # noqa: F401
from . import incubate  # noqa: F401
from . import pir  # noqa: F401  (PIR-lite compiler layer; ref: paddle.pir)
from . import static  # noqa: F401
from . import device  # noqa: F401
from . import distribution  # noqa: F401
from . import framework as base  # noqa: F401
from . import models  # noqa: F401
from . import ops  # noqa: F401
from . import parallel  # noqa: F401
from . import fft  # noqa: F401
from . import signal  # noqa: F401
from . import sparse  # noqa: F401
from . import audio  # noqa: F401
from . import text  # noqa: F401
from . import inference  # noqa: F401
from . import profiler  # noqa: F401
from . import observability  # noqa: F401
from . import resilience  # noqa: F401
from . import quantization  # noqa: F401
from .framework import io_file as _io_file
from .framework.io_file import save, load  # noqa: F401
from .framework.param_attr import ParamAttr, L1Decay, L2Decay  # noqa: F401
from . import regularizer  # noqa: F401
from .hapi import Model, summary  # noqa: F401
from .autograd import grad  # noqa: F401

# paddle.disable_static / enable_static: we are always "dygraph" (eager over
# XLA); static mode is served by jit.to_static. Kept as no-ops for parity.
_static_mode = False


def disable_static(place=None):
    global _static_mode
    _static_mode = False


def enable_static():
    global _static_mode
    _static_mode = True


def in_dynamic_mode():
    return not _static_mode


def disable_signal_handler():
    pass


def is_grad_enabled_():
    return is_grad_enabled()


class LazyGuard:
    """Layers built inside create their parameters as zeros and put the
    initializers' draws off until `Parameter.initialize()`: a model whose
    weights are about to be loaded or installed pays for no draw
    (reference: python/paddle/base/lazy_init.py LazyGuard)."""

    def __enter__(self):
        from .nn.layer import layers
        self._was, layers._lazy_init = layers._lazy_init, True
        return self

    def __exit__(self, *a):
        from .nn.layer import layers
        layers._lazy_init = self._was
        return False
from . import geometric  # noqa: F401
from . import utils  # noqa: F401
from . import hub  # noqa: F401,E402
from . import sysconfig  # noqa: F401,E402
from . import version  # noqa: F401,E402
from . import onnx  # noqa: F401,E402
from .hapi import callbacks  # noqa: F401,E402
from .hapi.flops import flops  # noqa: F401,E402
from .distributed.parallel import DataParallel  # noqa: F401,E402
# `from .tensor import *` above bound the name `linalg` to the tensor
# SUBMODULE, and `from . import linalg` would keep that binding (the import
# system only falls back to loading package.linalg when the attribute is
# absent) — import the top-level namespace module explicitly and rebind.
import importlib as _importlib  # noqa: E402
linalg = _importlib.import_module(".linalg", __name__)
from . import generation  # noqa: E402,F401


def batch(reader, batch_size, drop_last=False):
    """Batch a sample generator. reference: python/paddle/reader/decorator.py
    paddle.batch (legacy reader API)."""
    def batched():
        b = []
        for item in reader():
            b.append(item)
            if len(b) == batch_size:
                yield b
                b = []
        if b and not drop_last:
            yield b
    return batched


def iinfo(dtype):
    import jax.numpy as jnp
    from .framework import dtypes as _dt
    return jnp.iinfo(_dt.convert_dtype(dtype))


def finfo(dtype):
    import jax.numpy as jnp
    from .framework import dtypes as _dt
    return jnp.finfo(_dt.convert_dtype(dtype))


def create_parameter(shape, dtype="float32", name=None, attr=None,
                     is_bias=False, default_initializer=None):
    """reference: python/paddle/tensor/creation.py create_parameter."""
    import numpy as _np
    from .framework.core import Parameter
    from .framework import dtypes as _dt
    import jax.numpy as _jnp
    if default_initializer is not None:
        t = Parameter(_jnp.zeros(tuple(shape), _dt.convert_dtype(dtype)))
        default_initializer(t)
        return t
    if is_bias:
        data = _jnp.zeros(tuple(shape), _dt.convert_dtype(dtype))
        return Parameter(data)
    # reference default: Xavier uniform — reuse the real initializer
    from .nn.initializer import XavierUniform
    t = Parameter(_jnp.zeros(tuple(shape), _dt.convert_dtype(dtype)))
    XavierUniform()(t)
    return t


Tensor.create_parameter = staticmethod(create_parameter)  # method parity


def set_printoptions(precision=None, threshold=None, edgeitems=None,
                     sci_mode=None, linewidth=None):
    """reference: base/framework.py set_printoptions (numpy-backed here)."""
    import numpy as _np
    kw = {}
    if precision is not None:
        kw["precision"] = precision
    if threshold is not None:
        kw["threshold"] = threshold
    if edgeitems is not None:
        kw["edgeitems"] = edgeitems
    if linewidth is not None:
        kw["linewidth"] = linewidth
    if sci_mode is not None:
        kw["suppress"] = not sci_mode
    _np.set_printoptions(**kw)


# accelerator RNG state: one generator on TPU (ref get/set_cuda_rng_state)
get_cuda_rng_state = get_rng_state
set_cuda_rng_state = set_rng_state


# paddle.dtype / paddle.shape parity (reference: base/framework.py)
from .framework import dtypes as _dtypes_mod
dtype = _dtypes_mod.DType if hasattr(_dtypes_mod, "DType") else type(
    _dtypes_mod.convert_dtype("float32"))
from .tensor.attribute import shape  # noqa: F401,E402

# fp8 dtypes: single source of truth is the registry (framework.dtypes),
# which also resolves the "float8_e4m3fn"/"float8_e5m2" cast names
from .framework.dtypes import float8_e4m3fn, float8_e5m2  # noqa: F401,E402


def check_shape(shape_v):
    """reference: base/framework.py check_shape — validate a shape spec."""
    if isinstance(shape_v, Tensor):
        return
    for s in shape_v:
        if isinstance(s, Tensor):
            continue
        if not isinstance(s, int) or (s < 0 and s != -1):
            raise ValueError(f"invalid dim {s!r} in shape {shape_v}")
