"""Time the gated delta rule's chunked form alone on the chip, and hold
its kernels to the plain statement there.

``LAYERS`` chained calls of ``gated_delta_chunked`` (a layer's output is
the next one's values) in one jitted program, forward and forward +
backward, host clock around ``block_until_ready``: milliseconds a call.
Variants of one shape ``BxHkxHvxTxDxC``:

- ``kernel``: what ``gated_delta_chunked`` lowers where the shapes allow
  (``gated_delta_kernels.fits``): the chunk-local work in the
  ``gdn_chunk_*`` kernels;
- ``plain``: the rule switched off: ``_wy`` and ``_read_out`` as plain
  ``jax.numpy`` under ``jax.checkpoint``, which is what every call ran
  before the kernels;
- ``chunks:<m>``: the kernels at ``m`` chunks a program;
- ``inverse:default``: the kernels with the inverse's products at default
  precision (one bfloat16 pass, not the six of ``highest``): NOT the
  rule, only what its full-precision products cost;
- ``parts``: each of the four kernels and the serial pass, forward and
  backward, alone (one call a program; a backward call is its kernel or
  loop without the forward, which nothing reads);
- ``vector``: not a variant of its own: the decay of every variant named
  with it is a vector over the key's channels (Kimi Delta Attention: ``g``
  [B, T, H, D] in (-5, 0) as the layer draws it at its start, ``Hk == Hv``),
  so ``kernel`` is the ``gdn_channel_*`` kernels and ``plain`` is
  ``_chunked_channel``.

With both ``kernel`` and ``plain`` among the variants, the output and
every cotangent of one call are compared too (largest difference over
the largest plain value), which no CPU run can do for the compiled
kernels.

    python tools/gdn_kernel_bench.py 1x16x32x8192x128x64 kernel plain chunks:1
    python tools/gdn_kernel_bench.py 1x32x32x8192x128x64 vector kernel plain parts
"""
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from dlrover_tpu.common import trace_counts
from dlrover_tpu.ops import gated_delta
from dlrover_tpu.ops import gated_delta_kernels as kernels

FITS = kernels.fits
CHUNKS = kernels._CHUNKS_A_PROGRAM
HIGHEST = kernels._HI
LAYERS = 3
REPEATS = 5
ROUNDS = 5


def _inputs(B, Hk, Hv, T, D, vector, seed=0, dtype=jnp.bfloat16):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = gated_delta.l2norm(jax.random.normal(ks[0], (B, T, Hk, D)))
    k = gated_delta.l2norm(jax.random.normal(ks[1], (B, T, Hk, D)))
    v = jax.random.normal(ks[2], (B, T, Hv, D))
    beta = jax.nn.sigmoid(jax.random.normal(ks[3], (B, T, Hv)))
    # decay rates as the layer's at its start: A = U(0, 16], softplus ~ 1
    A = 16.0 * (1.0 - jax.random.uniform(ks[4], (Hv,)))
    g = -A * jax.nn.softplus(jax.random.normal(ks[5], (B, T, Hv)) + 1.0)
    if vector:
        # the share of the bound a step decays by, log-uniform a channel
        # over ``DT_SHARE`` where the projection reads 0, as the layer's
        lo, hi = gated_delta.DT_SHARE
        share = jnp.exp(
            jax.random.uniform(ks[4], (Hk * D,)) * jnp.log(hi / lo)
            + jnp.log(lo)
        )
        g = -5.0 * jax.nn.sigmoid(
            jax.random.normal(ks[5], (B, T, Hk * D))
            + jnp.log(share / (1.0 - share))
        ).reshape(B, T, Hk, D)
    return (
        (q * D**-0.5).astype(dtype), k.astype(dtype), v.astype(dtype),
        beta, g,
    )


def _select(variant: str):
    kernels.fits, kernels._CHUNKS_A_PROGRAM = FITS, CHUNKS
    kernels._HI = HIGHEST
    if variant == "inverse:default":
        kernels._HI = None
    elif variant == "plain":
        kernels.fits = lambda *a, **k: False
    elif variant.startswith("chunks:"):
        kernels._CHUNKS_A_PROGRAM = (int(variant.split(":")[1]),)
    elif variant != "kernel":
        raise SystemExit(f"unknown variant {variant!r}")


def _programs(C: int):
    def stack(q, k, v, beta, g):
        for _ in range(LAYERS):
            o = gated_delta.gated_delta_chunked(q, k, v, beta, g, C)
            v = o.astype(v.dtype)
        return o

    def loss(*a):
        return jnp.sum(stack(*a) ** 2)

    return jax.jit(stack), jax.jit(jax.grad(loss, argnums=range(5)))


def _time(fn, *args, calls=LAYERS):
    jax.block_until_ready(fn(*args))  # compiles
    rounds = []
    for _ in range(ROUNDS):
        t0 = time.perf_counter()
        for _ in range(REPEATS):
            out = fn(*args)
        jax.block_until_ready(out)
        rounds.append((time.perf_counter() - t0) / REPEATS / calls * 1e3)
    return {"median_ms": float(np.median(rounds)), "min_ms": min(rounds)}


def _parts(C: int, args):
    q, k, v, beta, g = args
    B, T, Hk, D = q.shape
    Hv = v.shape[2]
    n, r = T // C, Hv // Hk
    rows = (n, B, Hk, 1, r * C)

    def per_head(x):
        return jnp.transpose(
            x.reshape(B, n, C, Hk, r), (1, 0, 3, 4, 2)
        ).reshape(rows)

    q, k = q.reshape(B, T, Hk * D), k.reshape(B, T, Hk * D)
    v = v.reshape(B, T, Hv * D)
    if g.ndim == 4:
        g, read = g.reshape(B, T, Hk * D), kernels.read_out_channel

        def wy(k, v, beta, g):
            U, W, Kl, a = kernels.wy_channel(k, v, beta, g, Hk, C)
            return U, W, Kl, None, a
    else:
        beta, g, read = per_head(beta), per_head(g), kernels.read_out

        def wy(k, v, beta, g):
            return kernels.wy(k, v, beta, g, Hk, r, C)

    def cotangents(outs):
        return jax.tree.map(lambda x: jnp.ones(x.shape, x.dtype), outs)

    def backward(fn):
        return jax.jit(lambda *a: jax.vjp(fn, *a)[1](
            cotangents(jax.eval_shape(fn, *a))
        ))

    made = jax.jit(wy)(k, v, beta, g)
    passed = jax.jit(gated_delta.chunk_state_pass)(*made)
    stretches = {
        "wy": (wy, (k, v, beta, g)),
        "pass": (gated_delta.chunk_state_pass, made),
        "read": (read, (q, k, g, *passed)),
    }
    out = {}
    for name, (fn, a) in stretches.items():
        out[f"{name}_fwd"] = _time(jax.jit(fn), *a, calls=1)["median_ms"]
        out[f"{name}_bwd"] = _time(backward(fn), *a, calls=1)["median_ms"]
    return out


def _one_call(C: int, args):
    """One call's output and cotangents, float32 on the host."""
    def once(*a):
        return gated_delta.gated_delta_chunked(*a, C)

    o, vjp = jax.vjp(jax.jit(once), *args)
    grads = vjp(jnp.cos(o))
    return [np.asarray(x, np.float32) for x in (o, *grads)]


def _rel(got, want):
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)), 1e-30))


def main(argv):
    B, Hk, Hv, T, D, C = (int(x) for x in argv[0].split("x"))
    variants = [v for v in argv[1:] if v != "vector"] or ["kernel", "plain"]
    vector = "vector" in argv[1:]
    dev = jax.devices()[0]
    args = _inputs(B, Hk, Hv, T, D, vector)
    out = {
        "device": {"platform": dev.platform, "kind": dev.device_kind},
        "shape": argv[0], "decay": "channel" if vector else "head",
        "layers": LAYERS, "variants": {},
    }
    held = {}
    for variant in variants:
        if variant == "parts":
            _select("kernel")
            out["parts_ms"] = _parts(C, args)
            print(json.dumps({"parts_ms": out["parts_ms"]}), flush=True)
            continue
        _select(variant)
        before = trace_counts.snapshot()
        fwd, both = _programs(C)
        t0 = time.perf_counter()
        out["variants"][variant] = {
            "fwd": _time(fwd, *args), "fwd_bwd": _time(both, *args),
            "counts": dict(+trace_counts.since(before)),
            "wall_s": round(time.perf_counter() - t0, 1),
        }
        if variant in ("kernel", "plain"):
            held[variant] = _one_call(C, args)
        print(json.dumps({variant: out["variants"][variant]}), flush=True)
    if len(held) == 2:
        names = ["o", "dq", "dk", "dv", "dbeta", "dg"]
        out["kernel_against_plain"] = {
            n: _rel(a, b)
            for n, a, b in zip(names, held["kernel"], held["plain"])
        }
    _select("kernel")
    print(json.dumps(out))


if __name__ == "__main__":
    main(sys.argv[1:])
