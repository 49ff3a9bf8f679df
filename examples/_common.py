"""Shared example plumbing: CPU-mesh bootstrap for laptop/CI runs."""

import os
import sys

# the repo is used in-place (no pip install): make paddle_tpu importable
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def setup_devices(n_devices):
    """n_devices > 0: force the CPU platform with that many virtual devices
    (the examples' default, so they run anywhere). n_devices == 0: leave
    jax alone and use whatever is attached — the chips, where there are
    any. Must run before anything else touches jax."""
    if n_devices and int(n_devices) > 0:
        flags = os.environ.get("XLA_FLAGS", "")
        if "host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + f" --xla_force_host_platform_device_count={n_devices}"
            ).strip()
        import jax
        jax.config.update("jax_platforms", "cpu")
    import jax
    return jax.devices()
