"""Shared plumbing for the CLI report modules (strategy_report,
ring_report): the CPU-mesh bootstrap and the XLA memory-analysis
readout both reports need."""

from __future__ import annotations

import os
from typing import Optional


def force_cpu_mesh_env(device_count: int = 8) -> None:
    """Pin this process to a virtual multi-device CPU platform.

    Must run before the first jax backend use.  Sets JAX_PLATFORMS
    (and jax.config — jax reads the variable only at import, which may
    already have happened) and injects the host-platform device count
    unless an XLA_FLAGS already carries one."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    flags = os.environ.get("XLA_FLAGS", "")
    if "host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags +
            f" --xla_force_host_platform_device_count={device_count}"
        ).strip()
    import jax
    jax.config.update("jax_platforms",
                      os.environ.get("JAX_PLATFORMS", "cpu"))


def memory_analysis_bytes(compiled) -> Optional[dict]:
    """Per-device {temp, argument} bytes from a compiled executable's
    XLA memory analysis, or None when the backend doesn't expose it."""
    try:
        m = compiled.memory_analysis()
        if m is None:
            return None
        return {"temp": int(getattr(m, "temp_size_in_bytes", 0)),
                "argument": int(getattr(m, "argument_size_in_bytes", 0))}
    except Exception:
        return None
