"""Time the delta rule's serial pass over the chunk states alone on the
chip, and hold its kernels to the plain statement there.

``CALLS`` chained calls of ``gated_delta.chunk_state_pass`` in one jitted
program (a call's ``V'`` is the next one's ``U``; backwards, a call's
``dU`` is the cotangent of the ``V'`` before it), forward and forward +
backward (``jax.vjp`` a call: every call's ``dW``, ``dK`` and decay
cotangents are results, nothing is summed over the calls), host clock
around ``block_until_ready``: milliseconds a call. A shape is the pass's
own, ``n x b x g x r x C x d_k x d_v`` (chunks, batch, key heads, value
heads a key head, chunk, head widths); with ``channel`` among the
arguments the decay is a vector over the key's channels (``delta`` None,
``a`` [n, b, g, r, d_k]: Kimi Delta Attention's, ``r`` 1). Variants:

- ``kernel``: what ``chunk_state_pass`` lowers where the shapes allow
  (``gated_delta_kernels.fits``): ``delta_state_pass`` and
  ``delta_state_pass_rev``, the state in VMEM across a head's chunks;
- ``plain``: the rule switched off: the ``lax.scan`` over the chunk states
  and, backwards, the reversed scan and the einsums over all chunks after
  it, which is what every call ran before the kernels;
- ``heads:<p>`` and ``chunks:<m>``, joined by commas: the kernels at ``p``
  key heads and ``m`` chunks a program (diagnostics);
- ``calls:<n>``: not a variant: the chained calls of every program (12;
  fewer where the plain way's twelve do not fit the chip's memory).

With both ``kernel`` and ``plain`` among the variants one call's results
and cotangents are compared too (largest difference over the largest
plain value), which no CPU run can do for the compiled kernels.

    PYTHONPATH=. python tools/gdn_pass_bench.py 128x1x16x2x64x128x128 plain kernel
    PYTHONPATH=. python tools/gdn_pass_bench.py 128x1x32x1x64x128x128 channel plain kernel
    PYTHONPATH=. python tools/gdn_pass_bench.py 256x1x30x1x64x96x192 plain kernel heads:2,chunks:4
"""
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from dlrover_tpu.ops import gated_delta
from dlrover_tpu.ops import gated_delta_kernels as kernels

FITS = kernels.fits
PASS_BLOCK = kernels._pass_block
CALLS = 12
REPEATS = 3
ROUNDS = 5


def inputs(n, b, g, r, C, dk, dv, channel, seed=0, dtype=jnp.bfloat16):
    """``chunk_state_pass``'s arguments at the sizes ``wy`` leaves them:
    keys of unit length, ``W`` rows of half that, decays in (0, 1] (a row
    over the key's channels and no ``delta`` where ``channel``). The
    families' tests hold the kernels to the plain scan on these too
    (``tests/pass_parity.py``)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    f32 = jnp.float32
    U = jax.random.normal(ks[0], (n, b, g, r, C, dv), f32)
    W = 0.5 * gated_delta.l2norm(jax.random.normal(ks[1], (n, b, g, r, C, dk)))
    K = gated_delta.l2norm(jax.random.normal(ks[2], (n, b, g, C, dk)))
    if channel:
        a = jnp.exp(-2.0 * jax.random.uniform(ks[3], (n, b, g, r, dk)))
        return U, W.astype(dtype), K.astype(dtype), None, a
    step = -jax.random.uniform(ks[3], (n, b, g, r, C)) * 0.1
    left = jnp.cumsum(step[..., ::-1], -1)[..., ::-1] - step
    a = jnp.exp(jnp.sum(step, -1))
    return U, W.astype(dtype), K.astype(dtype), jnp.exp(left), a


def _select(variant: str):
    kernels.fits, kernels._pass_block = FITS, PASS_BLOCK
    if variant == "plain":
        kernels.fits = lambda *a, **k: False
    elif variant != "kernel":
        want = dict(x.split(":") for x in variant.split(","))
        if set(want) - {"heads", "chunks"}:
            raise SystemExit(f"unknown variant {variant!r}")

        def block(n, g, *rest):
            p, m = PASS_BLOCK(n, g, *rest)
            return int(want.get("heads", p)), int(want.get("chunks", m))

        kernels._pass_block = block


def _programs():
    f32 = jnp.float32

    def forward(U, W, K, delta, a):
        states = []
        for _ in range(CALLS):
            Vn, S_in = gated_delta.chunk_state_pass(U, W, K, delta, a)
            U = Vn.astype(f32)
            states.append(S_in)
        return Vn, states

    def both(U, W, K, delta, a):
        backs = []
        for _ in range(CALLS):
            (Vn, S_in), back = jax.vjp(
                gated_delta.chunk_state_pass, U, W, K, delta, a
            )
            U = Vn.astype(f32)
            backs.append(back)
        dVn = jnp.ones_like(Vn)
        dS_in = jnp.full(S_in.shape, 1e-3, S_in.dtype)
        rest = []
        for back in reversed(backs):
            dU, *others = back((dVn, dS_in))
            dVn = (0.5 * dU).astype(Vn.dtype)
            rest.append(others)
        return dU, rest

    return jax.jit(forward), jax.jit(both)


def _time(fn, *args):
    jax.block_until_ready(fn(*args))  # compiles
    rounds = []
    for _ in range(ROUNDS):
        t0 = time.perf_counter()
        for _ in range(REPEATS):
            out = fn(*args)
        jax.block_until_ready(out)
        rounds.append((time.perf_counter() - t0) / REPEATS / CALLS * 1e3)
    return {"median_ms": float(np.median(rounds)), "min_ms": min(rounds)}


def _one_call(args):
    """One call's results and cotangents, float32 on the host."""
    def once(*a):  # a function of its own: ``jit`` keeps no other way's
        return gated_delta.chunk_state_pass(*a)

    (Vn, S_in), back = jax.vjp(jax.jit(once), *args)
    f32 = jnp.float32
    grads = jax.jit(back)((
        jnp.cos(Vn.astype(f32)).astype(Vn.dtype),
        (0.1 * jnp.sin(S_in.astype(f32))).astype(S_in.dtype),
    ))
    return [
        np.asarray(x, np.float32) for x in (Vn, S_in, *grads) if x is not None
    ]


def _rel(got, want):
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)), 1e-30))


def main(argv):
    n, b, g, r, C, dk, dv = (int(x) for x in argv[0].split("x"))
    global CALLS
    channel = "channel" in argv[1:]
    variants = [v for v in argv[1:] if v != "channel"]
    for v in [v for v in variants if v.startswith("calls:")]:
        CALLS = int(v.split(":")[1])
        variants.remove(v)
    variants = variants or ["kernel", "plain"]
    dev = jax.devices()[0]
    args = inputs(n, b, g, r, C, dk, dv, channel)
    out = {
        "device": {"platform": dev.platform, "kind": dev.device_kind},
        "shape": argv[0], "decay": "channel" if channel else "head",
        "calls": CALLS, "variants": {},
    }
    held = {}
    for variant in variants:
        _select(variant)
        fwd, both = _programs()
        t0 = time.perf_counter()
        out["variants"][variant] = {
            "fwd": _time(fwd, *args), "fwd_bwd": _time(both, *args),
            "wall_s": round(time.perf_counter() - t0, 1),
        }
        if variant in ("kernel", "plain"):
            held[variant] = _one_call(args)
        print(json.dumps({variant: out["variants"][variant]}), flush=True)
    if len(held) == 2:
        names = ["Vn", "S_in", "dU", "dW", "dK"]
        names += ["da"] if channel else ["ddelta", "da"]
        out["kernel_against_plain"] = {
            n: _rel(x, y)
            for n, x, y in zip(names, held["kernel"], held["plain"])
        }
    _select("kernel")
    print(json.dumps(out))


if __name__ == "__main__":
    main(sys.argv[1:])
