"""Time the gated norm after a scan alone on the chip, and hold its kernels
to the plain statement there.

``LAYERS`` chained calls of ``ops/mamba2.gated_norm`` (a site's output is
the next one's ``o``) in one jitted program, forward and forward +
backward, host clock around ``block_until_ready``: milliseconds a site.
One shape ``BxTxC``, then the form as the mixers state it:

- ``head``: ``head_gated_rmsnorm``, a sigmoid a group (the Ling cell's);
- ``outside``: ``gated_group_rmsnorm(norm_before_gate=True)``, SiLU a
  channel outside the norm (the Qwen3-Next cell's);
- ``inside``: ``gated_group_rmsnorm``, SiLU a channel inside the norm (the
  Nemotron cell's);

``group:<n>`` the group's width (128), and the variants:

- ``kernel``: what ``gated_norm`` lowers where ``gated_norm_kernels.fits``
  takes the input: ``gated_norm_fwd`` and ``gated_norm_bwd``;
- ``plain``: the rule switched off: the statement under ``jax.checkpoint``,
  which is what every site ran before the kernels;
- ``block:<bt>``: the kernels at row blocks of ``bt``; ``rows:<n>``: at
  sub-blocks of ``n`` rows; ``tiles:<n>``: ``n`` lane tiles a trip of the
  inner loop; any of them joined by commas (diagnostics: the rule states
  none).

With both ``kernel`` and ``plain`` among the variants, the output and
every cotangent of one call are compared too (largest difference over the
largest plain value), which no CPU run can do for the compiled kernels.

    PYTHONPATH=. python tools/gated_norm_bench.py 1x8192x4096 head kernel plain
    PYTHONPATH=. python tools/gated_norm_bench.py 1x8192x4096 inside group:512
"""
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from dlrover_tpu.common import trace_counts
from dlrover_tpu.ops import gated_norm_kernels as kernels
from dlrover_tpu.ops import mamba2
from dlrover_tpu.ops.gated_delta import head_gated_rmsnorm

FITS = kernels.fits
BLOCKS = (kernels._ROW_BLOCKS, kernels._ROWS, kernels._TILES)
FORMS = ("head", "outside", "inside")
EPS = 1e-6
LAYERS = 3
REPEATS = 5
ROUNDS = 5


def _inputs(B, T, C, form, width, seed=0, dtype=jnp.bfloat16):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    o = jax.random.normal(ks[0], (B, T, C)).astype(dtype)
    z = jax.random.normal(
        ks[1], (B, T, C // width if form == "head" else C)
    ).astype(dtype)
    w = 1.0 + 0.1 * jax.random.normal(ks[2], (width if form == "head" else C,))
    return o, z, w


def _site(form, width):
    """One site as its mixer calls it."""
    def site(o, z, w):
        groups = o.shape[-1] // width
        if form == "head":
            def statement(o, z, w):
                return head_gated_rmsnorm(o, z, w, EPS).astype(o.dtype)
        else:
            def statement(o, z, w):
                return mamba2.gated_group_rmsnorm(
                    o, z, w, groups, EPS, norm_before_gate=form == "outside"
                ).astype(o.dtype)
        return mamba2.gated_norm(
            statement, o, z, w, width, EPS, inside=form == "inside"
        )

    return site


def _select(variant: str):
    kernels.fits = FITS
    kernels._ROW_BLOCKS, kernels._ROWS, kernels._TILES = BLOCKS
    for word in variant.split(","):
        if word == "plain":
            kernels.fits = lambda *a: False
        elif word.startswith("block:"):
            kernels._ROW_BLOCKS = (int(word.split(":")[1]),)
        elif word.startswith("rows:"):
            kernels._ROWS = int(word.split(":")[1])
        elif word.startswith("tiles:"):
            kernels._TILES = int(word.split(":")[1])
        elif word != "kernel":
            raise SystemExit(f"unknown variant {variant!r}")
    # the kernels' calls sit under a jit of their own, which keeps what it
    # traced at another block
    kernels._fwd_call.clear_cache()
    kernels._bwd_call.clear_cache()


def _programs(site):
    def stack(o, z, w):
        for _ in range(LAYERS):
            o = site(o, z, w)
        return o

    def loss(*a):
        return jnp.sum(stack(*a).astype(jnp.float32) ** 2)

    return jax.jit(stack), jax.jit(jax.grad(loss, argnums=(0, 1, 2)))


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
        rounds.append((time.perf_counter() - t0) / REPEATS / LAYERS * 1e3)
    return {
        "median_ms": float(np.median(rounds)), "min_ms": min(rounds),
        "first_call_s": round(first, 2),
    }


def _one_call(site, args):
    """One call's output and cotangents, float32 on the host."""
    def once(*a):  # a new function a variant: jit keeps what it traced
        return site(*a)

    o, vjp = jax.vjp(jax.jit(once), *args)
    grads = vjp(jnp.cos(o.astype(jnp.float32)).astype(o.dtype))
    return [np.asarray(x, np.float32) for x in (o, *grads)]


def _rel(got, want):
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)), 1e-30))


def main(argv):
    B, T, C = (int(x) for x in argv[0].split("x"))
    words = argv[1:]
    form = next((w for w in words if w in FORMS), "head")
    width = next(
        (int(w.split(":")[1]) for w in words if w.startswith("group:")), 128
    )
    dtype = jnp.float32 if "f32" in words else jnp.bfloat16
    variants = [
        w for w in words
        if w not in FORMS and w != "f32" and not w.startswith("group:")
    ] or ["kernel", "plain"]
    dev = jax.devices()[0]
    args = _inputs(B, T, C, form, width, dtype=dtype)
    site = _site(form, width)
    out = {
        "device": {"platform": dev.platform, "kind": dev.device_kind},
        "shape": argv[0], "form": form, "group": width,
        "dtype": jnp.dtype(dtype).name, "layers": LAYERS, "variants": {},
    }
    held = {}
    for variant in variants:
        _select(variant)
        before = trace_counts.snapshot()
        fwd, both = _programs(site)
        t0 = time.perf_counter()
        out["variants"][variant] = {
            "fwd": _time(fwd, *args), "fwd_bwd": _time(both, *args),
            "counts": dict(+trace_counts.since(before)),
            "wall_s": round(time.perf_counter() - t0, 1),
        }
        if variant in ("kernel", "plain"):
            held[variant] = _one_call(site, args)
        print(json.dumps({variant: out["variants"][variant]}), flush=True)
    if len(held) == 2:
        out["kernel_against_plain"] = {
            n: _rel(a, b) for n, a, b in zip(
                ["y", "do", "dz", "dw"], held["kernel"], held["plain"]
            )
        }
    _select("kernel")
    print(json.dumps(out))


if __name__ == "__main__":
    main(sys.argv[1:])
