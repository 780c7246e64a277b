"""Small helpers around the JAX API surface the engines share: the
packing dtype under the x64 switch, the ``devices`` option of the
sharded entry points, and the persistent compilation cache that entry
points turn on before their first compile.
"""

from __future__ import annotations

import os
from typing import List, Optional, Sequence, Union

import jax

__all__ = ["resolve_devices", "resolve_pack_dtype", "enable_compile_cache",
           "COMPILE_CACHE_DIR"]

# The fixed in-checkout cache path used when JAX_COMPILATION_CACHE_DIR is
# not set. The path is part of the cache key, so it never varies by run.
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for an entry point and
    return its directory. Call before the first compile; library import
    never calls it.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is set here. Otherwise the cache lives at
    :data:`COMPILE_CACHE_DIR` (``<checkout>/.jax_cache``, gitignored).
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    return COMPILE_CACHE_DIR


def resolve_pack_dtype(dtype=None):
    """Default a packing dtype to the active jax x64 setting; reject a
    float64 request that ``jnp.asarray`` would silently downcast. The
    one canonical copy for every pack path (``repro.sim.rounds``,
    ``repro.sim.scenarios``, ``repro.core.jaxsim``)."""
    import numpy as np
    if dtype is None:
        return np.float64 if jax.config.jax_enable_x64 else np.float32
    if np.dtype(dtype) == np.float64 and not jax.config.jax_enable_x64:
        raise ValueError(
            "dtype=float64 requested with jax x64 disabled — jnp.asarray "
            "would silently downcast to float32; wrap the call in "
            "`with jax.enable_x64(True):`")
    return np.dtype(dtype)


# The devices argument accepted across the repo's sharded entry points:
# a device count, an explicit device sequence, or None (single-device).
Devices = Optional[Union[int, Sequence["jax.Device"]]]


def resolve_devices(devices: Devices) -> Optional[List["jax.Device"]]:
    """Normalize a ``devices`` option to a device list, or ``None``.

    ``None`` means single-device execution; an int ``n`` takes the first
    ``n`` visible devices; an explicit sequence is used as-is. A resolved
    list of fewer than two devices collapses to ``None`` — sharding over
    one device buys nothing, and single-device callers keep their plain
    (bit-identical) path. On a CPU-only host, multiple XLA devices exist
    only when ``XLA_FLAGS=--xla_force_host_platform_device_count=n`` was
    set before jax initialized — the error message says so, because that
    is the whole trick to harvesting multi-core from one process.
    """
    if devices is None:
        return None
    if isinstance(devices, int):
        if devices < 1:
            raise ValueError(f"devices must be >= 1, got {devices}")
        avail = jax.devices()
        if devices > len(avail):
            raise ValueError(
                f"devices={devices} requested but only {len(avail)} jax "
                f"device(s) visible; on CPU set XLA_FLAGS="
                f"--xla_force_host_platform_device_count={devices} in the "
                f"environment before jax is imported")
        devs = list(avail[:devices])
    else:
        devs = list(devices)
    return devs if len(devs) > 1 else None
