"""Time the int8-AdamW step of one leaf alone on the chip, and hold the
one-pass kernel to the plain statement there.

``REPEATS`` chained steps (a step's parameter and moments are the next
one's, donated) of ``adamw_8bit``'s ``update_and_apply`` on one float32
leaf with decay and a scale, host clock around ``block_until_ready``:
milliseconds a step, and GB/s at the 14 bytes an element one pass needs
(gradient read, parameter read and written, two codes read and written;
the scales are 1/128 of that), to be read against the chip's 819 GB/s.
Variants of one shape ``AxBx...``:

- ``kernel``: what the entry lowers where ``quantized_optim.takes_kernel``
  takes the leaf: ``q8_adam_step``, one ``pallas_call``;
- ``plain``: the rule switched off: ``update`` + ``optax.apply_updates``
  as plain ``jax.numpy``, XLA's passes over the leaf, which is what every
  leaf ran before the kernel and what a ``BLOCKS`` leaf (``2048x25024``)
  runs under either name;
- ``tiles:<n>`` / ``strip:<n>``: the kernel at ``n`` (8, 128) tiles a grid
  step, or a strip of the loop (diagnostics: the rule states neither).

``bf16`` among the variants makes the gradient bfloat16 for those after
it.

With both ``kernel`` and ``plain`` among the variants, one step's
parameter, codes and scales are compared too (codes that differ are
rounding ties between two compilers' divides and roots), which no CPU run
can do for the compiled kernel.

    PYTHONPATH=. python tools/q8_update_bench.py 64x2048x1024 kernel plain
    PYTHONPATH=. python tools/q8_update_bench.py 2048x25024 plain
"""
import json
import math
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from dlrover_tpu.common import trace_counts
from dlrover_tpu.ops import quantized_optim as q8

TAKES, TILES, STRIP = q8.takes_kernel, q8._STEP_TILES, q8._STRIP
GRAD = [jnp.float32]
BYTES = 14  # an element, one pass
REPEATS = 8
ROUNDS = 5


def _select(variant: str):
    q8.takes_kernel, q8._STEP_TILES, q8._STRIP = TAKES, TILES, STRIP
    q8._q8_adam_step.clear_cache()  # the jit keeps what it traced
    if variant == "plain":
        q8.takes_kernel = lambda *a: False
    elif variant.startswith("tiles:"):
        q8._STEP_TILES = int(variant.split(":")[1])
    elif variant.startswith("strip:"):
        q8._STRIP = int(variant.split(":")[1])
    elif variant != "kernel":
        raise SystemExit(f"unknown variant {variant!r}")


def _program():
    tx = q8.adamw_8bit(3e-4, weight_decay=0.01, min_quantized_size=4096)

    def step(p, st, g):  # a new function a variant: jit keeps what it traced
        return tx.update_and_apply(g, st, p, scale=jnp.float32(0.9))

    return tx, jax.jit(step, donate_argnums=(0, 1))


def _inputs(shape, tx, seed=0):
    kp, kg = jax.random.split(jax.random.PRNGKey(seed))
    p = 0.02 * jax.random.normal(kp, shape, jnp.float32)
    # rows of unlike sizes, as a layer's gradient has them
    g = jax.random.normal(kg, shape, jnp.float32) * jnp.exp(
        jax.random.normal(kg, (*shape[:-1], 1))
    )
    return p, tx.init(p), (1e-3 * g).astype(GRAD[0])


def _time(step, p, st, g):
    p, st = jax.block_until_ready(step(p, st, g))  # compiles
    rounds = []
    for _ in range(ROUNDS):
        t0 = time.perf_counter()
        for _ in range(REPEATS):
            p, st = step(p, st, g)
        jax.block_until_ready(p)
        rounds.append((time.perf_counter() - t0) / REPEATS * 1e3)
    return {"median_ms": float(np.median(rounds)), "min_ms": min(rounds)}


def _three_steps(shape):
    """Parameter, codes and scales after three steps, on the host."""
    tx, step = _program()
    p, st, g = _inputs(shape, tx, seed=1)
    for i in range(3):
        p, st = step(p, st, ((1.0 + i) * g).astype(g.dtype))
    return [
        np.asarray(x) for x in (
            p, st.mu.codes, st.nu.codes, st.mu.scales, st.nu.scales,
        )
    ]


def main(argv):
    shape = tuple(int(x) for x in argv[0].split("x"))
    variants = argv[1:] or ["kernel", "plain"]
    GRAD[0] = jnp.float32
    dev = jax.devices()[0]
    n = math.prod(shape)
    out = {
        "device": {"platform": dev.platform, "kind": dev.device_kind},
        "shape": argv[0], "layout": q8._layout_for(shape),
        "bytes_an_element": BYTES, "variants": {},
    }
    held = {}
    for variant in variants:
        if variant == "bf16":
            GRAD[0] = jnp.bfloat16
            continue
        _select(variant)
        before = trace_counts.snapshot()
        tx, step = _program()
        t0 = time.perf_counter()
        timed = _time(step, *_inputs(shape, tx))
        name = variant if GRAD[0] == jnp.float32 else f"bf16.{variant}"
        out["variants"][name] = {
            **timed,
            "gb_per_s": BYTES * n / timed["median_ms"] / 1e6,
            "counts": dict(+trace_counts.since(before)),
            "wall_s": round(time.perf_counter() - t0, 1),
        }
        if variant in ("kernel", "plain") and GRAD[0] == jnp.float32:
            held[variant] = _three_steps(shape)
        print(json.dumps({name: out["variants"][name]}), flush=True)
    if len(held) == 2:
        (pk, mk, vk, msk, vsk), (pp, mp, vp, msp, vsp) = (
            held["kernel"], held["plain"]
        )
        out["kernel_against_plain"] = {
            "p_max_diff": float(np.max(np.abs(pk - pp))),
            "p_max": float(np.max(np.abs(pp))),
            "mu_codes_differ": int(np.sum(mk != mp)),
            "nu_codes_differ": int(np.sum(vk != vp)),
            "codes_largest_step": int(max(
                np.max(np.abs(mk.astype(np.int32) - mp)),
                np.max(np.abs(vk.astype(np.int32) - vp)),
            )),
            "mu_scales_differ": int(np.sum(msk != msp)),
            "nu_scales_differ": int(np.sum(vsk != vsp)),
            "elements": n,
        }
    _select("kernel")
    print(json.dumps(out))


if __name__ == "__main__":
    main(sys.argv[1:])
