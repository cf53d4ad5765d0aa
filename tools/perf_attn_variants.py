"""Compare attention implementations by FULL-STEP time at 124M bs32
seq512 (ablation-style: same train step, only _causal_attention swapped).

Recorded v5e results (2026-07, docs/performance.md): flash512 140 ms,
flash256 174 ms, flash128 228 ms, jnp 184 ms, stock jax pallas 227 ms;
the fused short-seq kernels brought the same step to ~121 ms.

    python tools/perf_attn_variants.py [flash512 fused jnp ...]
"""
import functools
import sys
import time
from dataclasses import replace

import jax
import jax.numpy as jnp
import optax
from jax import lax

import dlrover_tpu.models.transformer as tf_mod
from dlrover_tpu.models.config import gpt2_small
from dlrover_tpu.models import build_train_step, init_sharded_state
from dlrover_tpu.ops.flash_attention import flash_attention
from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh

B, T = 32, 512
ITERS = 30
cfg = replace(gpt2_small(), max_seq_len=T)
mesh = build_mesh(MeshConfig(dp=1))
adamw = optax.adamw(3e-4)


def timed_step(step_fn, state, label):
    @functools.partial(jax.jit, donate_argnums=(0,), static_argnums=(2,))
    def run_steps(state, key, n):
        def body(st, i):
            x = jax.random.randint(
                jax.random.fold_in(key, i), (B, T), 0, cfg.vocab_size,
                jnp.int32)
            st, m = step_fn(st, x, x)
            return st, m["loss"]
        return lax.scan(body, state, jnp.arange(n))

    state, losses = run_steps(state, jax.random.PRNGKey(0), ITERS)
    float(losses[-1])
    t0 = time.perf_counter()
    state, losses = run_steps(state, jax.random.PRNGKey(1), ITERS)
    float(losses[-1])
    dt = (time.perf_counter() - t0) / ITERS
    print(f"{label:28s} {dt*1e3:8.2f} ms/step", flush=True)
    return dt


def attn_variant(name):
    if name == "fused":  # the default dispatch (fused short-seq kernels)
        return lambda q, k, v, mesh=None, layout="bthd": flash_attention(
            q, k, v, causal=True, layout=layout)
    if name == "flash512":
        return lambda q, k, v, mesh=None, layout="bthd": flash_attention(
            q, k, v, causal=True, block_q=512, block_k=512,
            layout=layout, allow_fused=False)
    if name == "flash256":
        return lambda q, k, v, mesh=None, layout="bthd": flash_attention(
            q, k, v, causal=True, block_q=256, block_k=256,
            layout=layout, allow_fused=False)
    if name == "flash128":
        return lambda q, k, v, mesh=None, layout="bthd": flash_attention(
            q, k, v, causal=True, block_q=128, block_k=128,
            layout=layout, allow_fused=False)
    if name == "jnp":
        return lambda q, k, v, mesh=None, layout="bthd": flash_attention(
            q, k, v, causal=True, force="reference", layout=layout)
    if name == "stock":
        from jax.experimental.pallas.ops.tpu.flash_attention import (
            flash_attention as stock_fa,
        )

        def f(q, k, v, mesh=None, layout="bthd"):
            # stock kernel wants [B, H, T, D]
            if layout == "bhtd":
                return stock_fa(
                    q, k, v, causal=True, sm_scale=q.shape[-1] ** -0.5)
            o = stock_fa(
                q.transpose(0, 2, 1, 3),
                k.transpose(0, 2, 1, 3),
                v.transpose(0, 2, 1, 3),
                causal=True,
                sm_scale=q.shape[-1] ** -0.5,
            )
            return o.transpose(0, 2, 1, 3)
        return f
    raise ValueError(name)


names = sys.argv[1:] or ["fused", "flash512", "jnp", "stock"]
for n in names:
    tf_mod._causal_attention = attn_variant(n)
    state, _ = init_sharded_state(jax.random.PRNGKey(1), cfg, mesh, adamw)
    step = build_train_step(cfg, mesh, adamw, donate=True)
    try:
        timed_step(step, state, n)
    except Exception as e:
        print(f"{n:28s} FAILED: {e!r}"[:300], flush=True)
