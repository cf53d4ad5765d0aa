"""Time the convolution stretch before a scan alone on the chip, and hold
its kernels to the plain statement there.

``LAYERS`` chained calls of ``ops/mamba2.conv_silu`` (a site's output is
the next one's input) in one jitted program, forward and forward +
backward, host clock around ``block_until_ready``: milliseconds a site.
Variants of one shape ``BxTxC`` (``bias`` among the arguments: the
convolution has one, as the Mamba-2 layer's):

- ``kernel``: what ``conv_silu`` lowers where ``conv_kernels.fits`` takes
  the input: ``conv_silu_fwd`` and ``conv_silu_bwd``;
- ``plain``: the rule switched off: ``silu(causal_conv1d(...))`` as plain
  ``jax.numpy`` under ``jax.checkpoint``, which is what every site ran
  before the kernels;
- ``block:<bt>x<bc>``: the kernels at time blocks of ``bt`` steps and
  channel blocks of ``bc``; ``rows:<n>``: at sub-blocks of ``n`` steps
  (diagnostics: the rule states neither).

With both ``kernel`` and ``plain`` among the variants, the output and
every cotangent of one call are compared too (largest difference over
the largest plain value), which no CPU run can do for the compiled
kernels.

    PYTHONPATH=. python tools/conv_kernel_bench.py 1x8192x8192 kernel plain
    PYTHONPATH=. python tools/conv_kernel_bench.py 1x8192x6144 bias kernel plain
"""
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from dlrover_tpu.common import trace_counts
from dlrover_tpu.ops import conv_kernels as kernels
from dlrover_tpu.ops import mamba2

FITS = kernels.fits
BLOCKS = (kernels._TIME_BLOCKS, kernels._CHANNEL_BLOCKS, kernels._ROWS)
TAPS = 4
LAYERS = 3
REPEATS = 5
ROUNDS = 5


def _inputs(B, T, C, bias, seed=0, dtype=jnp.bfloat16):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = jax.random.normal(ks[0], (B, T, C)).astype(dtype)
    # as the layers draw it: a fan-in of the taps
    w = jax.random.normal(ks[1], (TAPS, C)) * TAPS**-0.5
    b = 0.1 * jax.random.normal(ks[2], (C,)) if bias else None
    return x, w, b


def _select(variant: str):
    kernels.fits = FITS
    kernels._TIME_BLOCKS, kernels._CHANNEL_BLOCKS, kernels._ROWS = BLOCKS
    if variant == "plain":
        kernels.fits = lambda *a: False
    elif variant.startswith("block:"):
        bt, bc = variant.split(":")[1].split("x")
        kernels._TIME_BLOCKS, kernels._CHANNEL_BLOCKS = (int(bt),), (int(bc),)
    elif variant.startswith("rows:"):
        kernels._ROWS = int(variant.split(":")[1])
    elif variant != "kernel":
        raise SystemExit(f"unknown variant {variant!r}")


def _programs(bias):
    def stack(x, w, b):
        for _ in range(LAYERS):
            x = mamba2.conv_silu(x, w, b)
        return x

    def loss(*a):
        return jnp.sum(stack(*a).astype(jnp.float32) ** 2)

    return jax.jit(stack), jax.jit(
        jax.grad(loss, argnums=(0, 1, 2) if bias else (0, 1))
    )


def _time(fn, *args):
    jax.block_until_ready(fn(*args))  # compiles
    rounds = []
    for _ in range(ROUNDS):
        t0 = time.perf_counter()
        for _ in range(REPEATS):
            out = fn(*args)
        jax.block_until_ready(out)
        rounds.append((time.perf_counter() - t0) / REPEATS / LAYERS * 1e3)
    return {"median_ms": float(np.median(rounds)), "min_ms": min(rounds)}


def _one_call(args):
    """One call's output and cotangents, float32 on the host."""
    def once(*a):  # a new function a variant: jit keeps what it traced
        return mamba2.conv_silu(*a)

    o, vjp = jax.vjp(jax.jit(once), *args)
    grads = vjp(jnp.cos(o.astype(jnp.float32)).astype(o.dtype))
    return [
        np.asarray(x, np.float32) for x in (o, *grads) if x is not None
    ]


def _rel(got, want):
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)), 1e-30))


def main(argv):
    B, T, C = (int(x) for x in argv[0].split("x"))
    bias = "bias" in argv[1:]
    variants = [v for v in argv[1:] if v != "bias"] or ["kernel", "plain"]
    dev = jax.devices()[0]
    args = _inputs(B, T, C, bias)
    out = {
        "device": {"platform": dev.platform, "kind": dev.device_kind},
        "shape": argv[0], "bias": bias, "layers": LAYERS, "variants": {},
    }
    held = {}
    for variant in variants:
        _select(variant)
        before = trace_counts.snapshot()
        fwd, both = _programs(bias)
        t0 = time.perf_counter()
        out["variants"][variant] = {
            "fwd": _time(fwd, *args), "fwd_bwd": _time(both, *args),
            "counts": dict(+trace_counts.since(before)),
            "wall_s": round(time.perf_counter() - t0, 1),
        }
        if variant in ("kernel", "plain"):
            held[variant] = _one_call(args)
        print(json.dumps({variant: out["variants"][variant]}), flush=True)
    if len(held) == 2:
        out["kernel_against_plain"] = {
            n: _rel(a, b) for n, a, b in zip(
                ["o", "dx", "dw", "db"], held["kernel"], held["plain"]
            )
        }
    _select("kernel")
    print(json.dumps(out))


if __name__ == "__main__":
    main(sys.argv[1:])
