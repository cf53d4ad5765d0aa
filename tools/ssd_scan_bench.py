"""Time the Mamba-2 chunked scan alone on the chip, and hold its kernels to
the plain statement there.

``CALLS`` chained scans in one jitted program (a call's ``y`` is the next
one's ``x``; backwards, a call's ``dx`` is the cotangent of the ``y`` before
it), each as the mixer makes it: the scan, the ``D`` skip and the rounding to
the activation dtype. Forward, and forward + backward (``jax.vjp`` a call:
every call's ``ddt``, ``da``, ``dB``, ``dC`` and ``dD`` are results, nothing
is summed over the calls), host clock around ``block_until_ready``:
milliseconds a call. A shape is ``B x T x H x P x G x N x chunk``. Variants:

- ``kernel``: what ``mamba2_mixer`` lowers where the shapes allow
  (``ssd_kernels.fits``): ``ssd_scan_fwd`` and ``ssd_scan_bwd``, a head's
  decay squares and a group's states in VMEM;
- ``plain``: the rule switched off: ``jax.checkpoint(ssd_chunked)`` and the
  skip after it, differentiated by JAX, which is what every call ran before
  the kernels;
- ``calls:<n>``: not a variant: the chained calls of every program (12);
- ``f32``: not a variant: float32 activations for the variants after it.

With both ``kernel`` and ``plain`` among the variants one call's ``y`` and
cotangents are compared too (largest difference over the largest plain
value), which no CPU run can do for the compiled kernels.

    PYTHONPATH=. python tools/ssd_scan_bench.py 1x8192x64x64x8x128x128 plain kernel
"""
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from dlrover_tpu.ops import mamba2, ssd_kernels

FITS = ssd_kernels.fits
CALLS = 12
REPEATS = 3
ROUNDS = 5
NAMES = ["y", "dx", "ddt", "da", "dB", "dC", "dD"]


def inputs(B, T, H, P, G, N, dtype, seed=0):
    """A scan's operands at the sizes the mixer's convolution and
    projections leave them: ``x``, ``B``, ``C`` after a SiLU, steps drawn
    log-uniformly from [1e-3, 1e-1], ``a`` in [-16, -1], ``D`` near one."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x, Bm, Cm = (
        jax.nn.silu(jax.random.normal(k, s)).astype(dtype)
        for k, s in zip(ks, [(B, T, H, P), (B, T, G, N), (B, T, G, N)])
    )
    dt = jnp.exp(
        jax.random.uniform(ks[3], (B, T, H)) * np.log(100.0) + np.log(1e-3)
    )
    a = -jax.random.uniform(ks[4], (H,), minval=1.0, maxval=16.0)
    D = 1.0 + 0.1 * jax.random.normal(ks[5], (H,))
    return x, dt, a, Bm, Cm, D


def scan(x, dt, a, Bm, Cm, D, chunk):
    """The scan as ``mamba2_mixer`` makes it, either way."""
    if ssd_kernels.fits(x, dt, Bm, Cm, chunk):
        return ssd_kernels.ssd(x, dt, a, Bm, Cm, D, chunk)
    y = jax.checkpoint(mamba2.ssd_chunked, static_argnums=(5,))(
        x, dt, a, Bm, Cm, chunk
    )
    return (y + D[:, None] * x.astype(jnp.float32)).astype(x.dtype)


def _select(variant: str):
    if variant not in ("kernel", "plain"):
        raise SystemExit(f"unknown variant {variant!r}")
    ssd_kernels.fits = FITS if variant == "kernel" else lambda *a, **k: False


def _programs(chunk):
    def forward(x, *rest):
        for _ in range(CALLS):
            x = (0.5 * scan(x, *rest, chunk)).astype(x.dtype)
        return x

    def both(x, *rest):
        backs = []
        for _ in range(CALLS):
            y, back = jax.vjp(lambda *a: scan(*a, chunk), x, *rest)
            x = (0.5 * y).astype(x.dtype)
            backs.append(back)
        dy, others = jnp.ones_like(y), []
        for back in reversed(backs):
            dx, *other = back(dy)
            dy = (0.5 * dx).astype(dy.dtype)
            others.append(other)
        return dx, others

    return jax.jit(forward), jax.jit(both)


def _time(fn, *args):
    t0 = time.perf_counter()
    jax.block_until_ready(fn(*args))  # compiles
    first = time.perf_counter() - t0
    rounds = []
    for _ in range(ROUNDS):
        t0 = time.perf_counter()
        for _ in range(REPEATS):
            out = fn(*args)
        jax.block_until_ready(out)
        rounds.append((time.perf_counter() - t0) / REPEATS / CALLS * 1e3)
    return {
        "median_ms": float(np.median(rounds)), "min_ms": min(rounds),
        "first_call_s": round(first, 2),
    }


def _one_call(args, chunk):
    """One call's result and cotangents, float32 on the host."""
    def once(*a):  # a function of its own: ``jit`` keeps no other way's
        return scan(*a, chunk)

    y, back = jax.vjp(jax.jit(once), *args)
    dy = jnp.cos(y.astype(jnp.float32)).astype(y.dtype)
    return [np.asarray(v, np.float32) for v in (y, *jax.jit(back)(dy))]


def _rel(got, want):
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)), 1e-30))


def main(argv):
    global CALLS
    B, T, H, P, G, N, chunk = (int(v) for v in argv[0].split("x"))
    dev = jax.devices()[0]
    out = {
        "device": {"platform": dev.platform, "kind": dev.device_kind},
        "shape": argv[0], "variants": {},
    }
    dtype, held = jnp.bfloat16, {}
    for variant in argv[1:] or ["kernel", "plain"]:
        if variant.startswith("calls:"):
            CALLS = int(variant.split(":")[1])
            continue
        if variant == "f32":
            dtype = jnp.float32
            continue
        _select(variant)
        args = inputs(B, T, H, P, G, N, dtype)
        fwd, both = _programs(chunk)
        name = f"{variant}.{jnp.dtype(dtype).name}"
        out["variants"][name] = {
            "calls": CALLS, "fwd": _time(fwd, *args),
            "fwd_bwd": _time(both, *args),
        }
        held[name] = _one_call(args, chunk)
        print(json.dumps({name: out["variants"][name]}), flush=True)
    for name in [n for n in held if n.startswith("kernel.")]:
        plain = held.get(name.replace("kernel.", "plain."))
        if plain is not None:
            out.setdefault("kernel_against_plain", {})[name] = {
                n: _rel(x, y) for n, x, y in zip(NAMES, held[name], plain)
            }
    _select("kernel")
    print(json.dumps(out))


if __name__ == "__main__":
    main(sys.argv[1:])
