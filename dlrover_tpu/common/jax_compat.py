"""The few JAX calls this repo wraps: the Pallas compile/interpret
choice and the pickling of AOT executables for the on-disk cache.
Everything else is called on ``jax`` directly."""

from __future__ import annotations

import os
import pickle


def pallas_interpret_mode() -> bool:
    """True when Pallas kernels must run under the interpreter on this
    backend (anything without a Mosaic TPU compiler). The embedding
    hot-tier gather/scatter kernels pass this to ``pallas_call`` so
    tier-1 runs everywhere: compiled on TPU, interpreted on the CPU
    backend — same kernel, same numerics. ``DLROVER_TPU_PALLAS``
    overrides (``compile``/``interpret``) for debugging. A backend
    that fails to come up raises here; it is never read as "not a
    TPU"."""
    forced = os.getenv("DLROVER_TPU_PALLAS", "")
    if forced == "interpret":
        return True
    if forced == "compile":
        return False
    import jax

    return jax.devices()[0].platform != "tpu"


def serialize_compiled(compiled) -> "bytes | None":
    """Pickle an AOT ``jax.stages.Compiled`` for the on-disk executable
    cache, together with the ids of the devices it runs on (loading
    needs them: an executable built for 2 of 8 devices does not load
    onto all 8). None when the program contains something unpicklable
    (custom pytree nodes in the in/out trees) — callers degrade to
    memory-only caching."""
    from jax.experimental import serialize_executable as se

    try:
        ids = [
            d.id for d in compiled.runtime_executable().local_devices()
        ]
        return pickle.dumps((se.serialize(compiled), ids))
    except Exception:
        return None


def deserialize_compiled(blob: bytes):
    """Inverse of ``serialize_compiled``; None on any failure (version
    skew, a device that is gone, truncated file) — a stale disk entry
    must read as a miss, never an error."""
    import jax
    from jax.experimental import serialize_executable as se

    try:
        payload, ids = pickle.loads(blob)
        by_id = {d.id: d for d in jax.devices()}
        return se.deserialize_and_load(
            *payload, execution_devices=[by_id[i] for i in ids]
        )
    except Exception:
        return None
