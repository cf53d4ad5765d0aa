"""Device/platform configuration helpers: turn a ``--device-spec`` into
JAX configuration before the first backend use."""

from __future__ import annotations

import os

DEVICE_SPEC_ENV = "DLROVER_TPU_DEVICE_SPEC"


def cpu_spec_count(spec: str) -> int:
    """``"cpu"`` -> 1, ``"cpu:N"`` -> N (single source of the syntax)."""
    return int(spec.split(":", 1)[1]) if ":" in spec else 1


def configure_devices(spec: str = ""):
    """Apply a device spec like ``"cpu:8"`` (virtual 8-device CPU mesh,
    multi-process capable) or ``"tpu"`` (default backend). Configuration
    only: it must run before jax creates a backend, and in a
    multi-process job before ``jax.distributed.initialize``, so it never
    touches a device itself (``check_devices`` does, afterwards). No-op
    for empty spec."""
    spec = spec or os.getenv(DEVICE_SPEC_ENV, "")
    if not spec or spec.startswith("tpu"):
        return
    if not spec.startswith("cpu"):
        raise ValueError(f"unknown device spec: {spec}")
    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", cpu_spec_count(spec))
    jax.config.update("jax_cpu_collectives_implementation", "gloo")


def check_devices(spec: str = ""):
    """Asked for the chip: anything else (a CPU the backend quietly
    settled for) is an error, before anything is built. This brings the
    backend up, so in a multi-process job it runs after
    ``jax.distributed.initialize``."""
    spec = spec or os.getenv(DEVICE_SPEC_ENV, "")
    if not spec.startswith("tpu"):
        return
    import jax

    platform = jax.devices()[0].platform
    if platform != "tpu":
        raise RuntimeError(
            f"device spec {spec!r} asks for a TPU but JAX came up "
            f"on {platform!r}"
        )
