"""Ablation timing of the full 124M train step (bs32 seq512) on the chip.

The measurement methodology behind docs/performance.md's 124M section:
swap ONE piece of the step (attention kernel / norms / vocab head /
optimizer) and diff against baseline — an isolated microbenchmark of a
short kernel mostly times its dispatch.

    python tools/perf_ablate_124m.py [baseline|no_attn_kernel|...]

Each variant runs the EXACT run_mfu-style chained scan (fresh on-device
batch per step, donated carry, scalar forced). Deltas vs baseline
attribute the step time: head, attention kernel, layernorms, optimizer,
grad-norm.
"""
import functools
import sys
import time
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax

import dlrover_tpu.models.transformer as tf_mod
from dlrover_tpu.models.config import gpt2_small
from dlrover_tpu.models import build_train_step, init_sharded_state
from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh

B, T = 32, 512
ITERS = 30
cfg = replace(gpt2_small(), max_seq_len=T)
mesh = build_mesh(MeshConfig(dp=1))


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
    print(f"{label:36s} {dt*1e3:8.2f} ms/step", flush=True)
    return dt


def fresh_state(tx):
    state, _ = init_sharded_state(jax.random.PRNGKey(1), cfg, mesh, tx)
    return state


variant = sys.argv[1] if len(sys.argv) > 1 else "all"

orig_attn = tf_mod._causal_attention
orig_norm = tf_mod._norm
orig_lm_head = tf_mod.lm_head
orig_nll = tf_mod.token_nll

adamw = optax.adamw(3e-4)


def run_variant(name):
    # reset patches
    tf_mod._causal_attention = orig_attn
    tf_mod._norm = orig_norm
    tf_mod.lm_head = orig_lm_head
    tf_mod.token_nll = orig_nll
    tx = adamw
    if name == "baseline":
        pass
    elif name == "no_attn_kernel":
        tf_mod._causal_attention = (
            lambda q, k, v, mesh=None, layout="bthd": v + q * 1e-6)
    elif name == "no_norm":
        tf_mod._norm = lambda x, p, cfg_: x
    elif name == "no_head":
        # head replaced by a tiny projection to 128 classes: removes the
        # vocab matmul + its bwd but keeps a real softmax-xent structure
        def small_head(params, x, cfg_):
            w = params["embed"]["tokens"].astype(x.dtype)[:128]
            return jnp.einsum("btd,vd->btv", x, w).astype(jnp.float32)
        tf_mod.lm_head = small_head
        tf_mod.token_nll = lambda logits, tgt: (
            jax.scipy.special.logsumexp(logits, axis=-1).mean())
    elif name == "sgd":
        tx = optax.sgd(1e-3)
    step = build_train_step(cfg, mesh, tx, donate=True)
    return timed_step(step, fresh_state(tx), name)


names = ["baseline", "no_attn_kernel", "no_norm", "no_head", "sgd"]
if variant != "all":
    names = [variant]
res = {}
for n in names:
    res[n] = run_variant(n)
if "baseline" in res:
    for n, v in res.items():
        if n != "baseline":
            print(f"delta {n:28s} {(res['baseline']-v)*1e3:8.2f} ms")
