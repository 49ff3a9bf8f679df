"""Device/place API. reference: python/paddle/device/__init__.py, paddle/phi/common/place.h.

On TPU there is one first-class device family; Place collapses to a thin
wrapper over jax.Device. CUDAPlace/XPUPlace aliases exist for API parity and
map to the accelerator.
"""

from __future__ import annotations

import jax

_current_device = None


class Place:
    def __init__(self, kind: str, device_id: int = 0):
        self.kind = kind
        self.device_id = device_id

    def __repr__(self):
        return f"Place({self.kind}:{self.device_id})"

    def __eq__(self, other):
        return isinstance(other, Place) and (self.kind, self.device_id) == (
            other.kind,
            other.device_id,
        )


class CPUPlace(Place):
    def __init__(self):
        super().__init__("cpu", 0)


class TPUPlace(Place):
    def __init__(self, device_id=0):
        super().__init__("tpu", device_id)


class CUDAPlace(Place):  # parity alias: maps to the accelerator
    def __init__(self, device_id=0):
        super().__init__("tpu", device_id)


class CUDAPinnedPlace(CPUPlace):
    pass


class XPUPlace(TPUPlace):
    pass


def set_device(device: str):
    """paddle.set_device('tpu') / ('cpu') / ('tpu:0'). Raises when the
    named device does not exist: asking for an accelerator never yields
    the CPU, and an out-of-range index never yields another chip."""
    global _current_device
    name, _, idx = device.partition(":")
    idx = int(idx) if idx else 0
    name = {"gpu": "tpu", "cuda": "tpu", "xpu": "tpu"}.get(name, name)
    if name == "cpu":
        devs = jax.devices("cpu")
    else:
        devs = [d for d in jax.devices() if d.platform != "cpu"]
        if not devs:
            raise RuntimeError(
                f"set_device({device!r}): no accelerator is attached "
                f"(jax.default_backend() == {jax.default_backend()!r})")
    if not 0 <= idx < len(devs):
        raise ValueError(
            f"set_device({device!r}): index {idx} out of range, "
            f"{len(devs)} {name} device(s) present")
    _current_device = devs[idx]
    jax.config.update("jax_default_device", _current_device)
    return get_device()


def get_device() -> str:
    d = _current_device or jax.devices()[0]
    plat = "tpu" if d.platform not in ("cpu",) else "cpu"
    return f"{plat}:{d.id}" if plat != "cpu" else "cpu"


def device_count() -> int:
    return len(jax.devices())


def is_compiled_with_cuda() -> bool:
    return False


def is_compiled_with_xpu() -> bool:
    return False


def is_compiled_with_tpu() -> bool:
    return True


def cuda_device_count() -> int:
    return 0
