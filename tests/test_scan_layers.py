"""scan_layers models: stacked [L, ...] layer params under one lax.scan.

The point (review r3 #5): the traced graph is O(1) in depth, so deep
models compile WITH remat — the reference's activation-checkpoint
optimization (optimization_library.py:39-58) usable at 48 layers.
Contract: bit-identical math to the unrolled model.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from dlrover_tpu.models import (
    build_train_step,
    forward,
    init_params,
    init_sharded_state,
    loss_fn,
    shard_batch,
    tiny,
)
from dlrover_tpu.models.transformer import (
    stack_layer_params,
    unstack_layer_params,
)
from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh


def _pair(num_layers=4, **kw):
    """(unrolled cfg, scan cfg) with identical weights."""
    cfg = tiny(num_layers=num_layers, **kw)
    scfg = dataclasses.replace(cfg, scan_layers=True)
    params = init_params(jax.random.PRNGKey(0), cfg)
    sparams = dict(params)
    sparams["layers"] = stack_layer_params(params["layers"])
    return cfg, scfg, params, sparams


def _tokens(cfg, batch=4, seq=16, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(0, cfg.vocab_size, (batch, seq)).astype(np.int32)


def test_forward_matches_unrolled():
    cfg, scfg, params, sparams = _pair()
    x = _tokens(cfg)
    ref, _ = jax.jit(lambda p, t: forward(p, t, cfg))(params, x)
    got, _ = jax.jit(lambda p, t: forward(p, t, scfg))(sparams, x)
    # same math, but the scanned body compiles as ONE specialization
    # where the unrolled path fuses per layer — last-ulp reassociation
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref), rtol=1e-5, atol=5e-6
    )


def test_grads_match_unrolled():
    cfg, scfg, params, sparams = _pair()
    x = _tokens(cfg)
    ref_loss, ref_g = jax.jit(
        jax.value_and_grad(lambda p: loss_fn(p, x, x, cfg))
    )(params)
    loss, g = jax.jit(
        jax.value_and_grad(lambda p: loss_fn(p, x, x, scfg))
    )(sparams)
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-6)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-5, atol=1e-6
        ),
        g["layers"],
        stack_layer_params(ref_g["layers"]),
    )


def test_remat_scan_grads_match():
    """remat over the scanned block must not change the numbers."""
    cfg, scfg, params, sparams = _pair()
    rcfg = dataclasses.replace(scfg, remat=True)
    x = _tokens(cfg)
    base, gb = jax.jit(
        jax.value_and_grad(lambda p: loss_fn(p, x, x, scfg))
    )(sparams)
    rem, gr = jax.jit(
        jax.value_and_grad(lambda p: loss_fn(p, x, x, rcfg))
    )(sparams)
    np.testing.assert_allclose(float(rem), float(base), rtol=1e-6)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-7
        ),
        gr,
        gb,
    )


def test_sharded_training_step():
    """scan model trains on an fsdp x dp mesh: the [L, ...] leaves get
    layer_stack-unsharded, embed/mlp axes sharded per the rule table."""
    _, scfg, _, _ = _pair()
    mesh = build_mesh(MeshConfig(fsdp=4, dp=2))
    tx = optax.adamw(1e-2)
    state, sh = init_sharded_state(jax.random.PRNGKey(0), scfg, mesh, tx)
    wq_spec = tuple(sh.params["layers"]["attn"]["wq"].spec)
    assert wq_spec[0] is None, wq_spec  # layer_stack unsharded
    step = build_train_step(scfg, mesh, tx, donate=False)
    x = _tokens(scfg, batch=8)
    b = shard_batch({"x": x, "y": x}, mesh)
    losses = []
    for _ in range(3):
        state, m = step(state, b["x"], b["y"])
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0]


def test_generation_matches_unrolled():
    from dlrover_tpu.rl.generation import generate

    cfg, scfg, params, sparams = _pair(num_layers=2)
    prompts = jnp.asarray(_tokens(cfg, batch=2, seq=4))
    ref, ref_lp = generate(
        params, prompts, jax.random.PRNGKey(7), cfg,
        max_new_tokens=8, greedy=True,
    )
    got, got_lp = generate(
        sparams, prompts, jax.random.PRNGKey(7), scfg,
        max_new_tokens=8, greedy=True,
    )
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
    np.testing.assert_allclose(
        np.asarray(got_lp), np.asarray(ref_lp), rtol=1e-5, atol=1e-6
    )


def test_stack_roundtrip_and_guards():
    cfg = tiny(num_layers=3)
    params = init_params(jax.random.PRNGKey(1), cfg)
    rt = unstack_layer_params(stack_layer_params(params["layers"]))
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(a, b),
        rt,
        params["layers"],
    )
    with pytest.raises(ValueError, match="homogeneous"):
        tiny(num_experts=2, scan_layers=True)
    from dlrover_tpu.parallel.pipeline import stack_pipeline_params

    scfg = tiny(num_layers=4, scan_layers=True)
    sparams = init_params(jax.random.PRNGKey(0), scfg)
    mesh = build_mesh(MeshConfig(pp=2, dp=4))
    from dlrover_tpu.parallel.pipeline import pipeline_forward

    with pytest.raises(ValueError, match="scan_layers"):
        pipeline_forward(
            stack_pipeline_params(
                init_params(jax.random.PRNGKey(0), tiny(num_layers=4)), 2
            ),
            jnp.asarray(_tokens(scfg)),
            scfg,
            mesh,
            4,
        )


def test_deep_remat_graph_is_constant_size():
    """The jaxpr of a scanned 24-layer model must be ~the same size as
    a 2-layer one (O(1) in depth) — that is the property that lets 48
    layers compile with remat under a bounded-size compile service."""
    x = _tokens(tiny(), batch=2, seq=8)

    def jaxpr_len(L):
        scfg = tiny(num_layers=L, scan_layers=True, remat=True)
        p = init_params(jax.random.PRNGKey(0), scfg)
        jpr = jax.make_jaxpr(
            jax.grad(lambda q: loss_fn(q, x, x, scfg))
        )(p)
        return len(str(jpr))

    small, big = jaxpr_len(2), jaxpr_len(24)
    assert big < 1.5 * small, (small, big)
