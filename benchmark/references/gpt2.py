"""Plain reference for the GPT-2 family: forward pass and next-token loss
in straightforward ``jax.numpy`` and float32, ``highest`` matmul precision,
no kernels, no mesh, no bf16. Independent of ``dlrover_tpu.models``: it
takes the program's parameter tree (names as ``init_params`` lays them
out) and nothing else from it.

Follows the published GPT-2: learned positions, pre-LayerNorm blocks
(eps 1e-5), multi-head causal attention scaled by 1/sqrt(head_dim),
GELU MLP in the tanh form (``gelu_new``), final LayerNorm, output head
tied to the token table. Departure: the program keeps attention
projections as [d, heads, head_dim] tensors without biases and the MLP
with biases; the reference reads them as they are.
"""

import jax
import jax.numpy as jnp


def _layer_norm(x, p):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + 1e-5) * p["scale"] + p["bias"]


def _gelu_tanh(x):
    return 0.5 * x * (
        1.0 + jnp.tanh(0.7978845608028654 * (x + 0.044715 * x**3))
    )


def logits(params, tokens):
    p = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
    T = tokens.shape[-1]
    x = p["embed"]["tokens"][tokens] + p["embed"]["positions"][:T][None]
    causal = jnp.tril(jnp.ones((T, T), bool))
    for layer in p["layers"]:
        h = _layer_norm(x, layer["attn_norm"])
        a = layer["attn"]
        q = jnp.einsum("btd,dhk->bhtk", h, a["wq"])
        k = jnp.einsum("btd,dhk->bhtk", h, a["wk"])
        v = jnp.einsum("btd,dhk->bhtk", h, a["wv"])
        s = jnp.einsum("bhqk,bhtk->bhqt", q, k) / jnp.sqrt(
            jnp.float32(q.shape[-1])
        )
        s = jnp.where(causal[None, None], s, -jnp.inf)
        o = jnp.einsum("bhqt,bhtk->bhqk", jax.nn.softmax(s, axis=-1), v)
        x = x + jnp.einsum("bhtk,hkd->btd", o, a["wo"])
        h = _layer_norm(x, layer["mlp_norm"])
        m = layer["mlp"]
        z = _gelu_tanh(jnp.einsum("btd,df->btf", h, m["w_up"]) + m["b_up"])
        x = x + jnp.einsum("btf,fd->btd", z, m["w_down"]) + m["b_down"]
    x = _layer_norm(x, p["final_norm"])
    return jnp.einsum("btd,vd->btv", x, p["embed"]["tokens"])


def loss(params, tokens, targets):
    """Mean next-token negative log-likelihood, float32 throughout."""
    with jax.default_matmul_precision("highest"):
        lg = logits(params, tokens)
        logp = jax.nn.log_softmax(lg, axis=-1)
        picked = jnp.take_along_axis(logp, targets[..., None], axis=-1)
        return -jnp.mean(picked)
