"""TPU/PJRT device introspection.

TPU-native counterpart of the reference's ``gpu/`` API shim tree (~770 LoC of
CUDA/HIP spelling unification, error-check macros, and handle plumbing —
SURVEY §2/L1): on TPU the PJRT client owns devices, streams, allocators and
error handling, so the shim reduces to an introspection surface used by
miniapps and diagnostics.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

from .types import Backend, Device


@dataclasses.dataclass
class DeviceInfo:
    index: int
    platform: str          # 'tpu' | 'cpu' | ...
    kind: str              # e.g. 'TPU v5 lite'
    memory_bytes: Optional[int]


def devices(backend: Optional[Backend] = None) -> list[DeviceInfo]:
    """Visible devices, optionally filtered by backend."""
    import jax

    out = []
    for d in jax.devices():
        if backend is Backend.MC and d.platform != "cpu":
            continue
        if backend is Backend.TPU and d.platform == "cpu":
            continue
        mem = None
        try:
            stats = d.memory_stats()
            if stats:
                mem = stats.get("bytes_limit")
        except Exception:
            pass
        out.append(DeviceInfo(index=d.id, platform=d.platform,
                              kind=getattr(d, "device_kind", d.platform),
                              memory_bytes=mem))
    return out


def default_device() -> Device:
    import jax

    return Device.CPU if jax.devices()[0].platform == "cpu" else Device.TPU


def cpu_subprocess_env(n_virtual_devices: Optional[int] = None) -> dict:
    """Environment for a subprocess that must come up on the CPU
    (``JAX_PLATFORMS=cpu``), optionally with an n-device virtual CPU
    platform."""
    import os
    import re

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    if n_virtual_devices is not None:
        flag = f"--xla_force_host_platform_device_count={n_virtual_devices}"
        flags = env.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" in flags:
            flags = re.sub(r"--xla_force_host_platform_device_count=\d+",
                           flag, flags)
        else:
            flags = (flags + " " + flag).strip()
        env["XLA_FLAGS"] = flags
    return env


def memory_in_use(device_index: int = 0) -> Optional[int]:
    """Live HBM bytes on a device (PJRT allocator stats), if reported."""
    import jax

    try:
        stats = jax.devices()[device_index].memory_stats()
        return stats.get("bytes_in_use") if stats else None
    except Exception:
        return None
