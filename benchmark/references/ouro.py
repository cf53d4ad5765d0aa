"""Plain reference for the ``ouro`` family (ByteDance's Ouro looped
language models, arXiv:2510.25741; ``model_type: ouro``): forward pass and
training loss in straightforward ``jax.numpy`` and float32, ``highest``
matmul precision, a Python loop over the passes and over the layers, no
kernels, no scan over passes or layers, no mesh, no bf16 (the full masked
``[T, T]`` scores, a block of query rows at a time so that 8192 tokens
fit; each exit's logits a block of rows at a time likewise, one exit after
the other). Independent of ``dlrover_tpu``: it takes the program's
parameter tree (names as ``init_params`` lays them out) and nothing else
from it; the number of layers and every width are read off the tree, the
number of passes and the entropy weight are the module's constants.

``RMS(x; w) = x / sqrt(mean(x^2) + 1e-6) * w``. No bias in any projection,
no dropout, no QK-norm, no window. A published block is two entries of the
tree's ``layers`` (``norm`` before, ``out_norm`` after each sub-layer: a
sandwich norm, four weight vectors a block):

- attention: ``a = RMS(h; w1)``; ``q, k, v = a W_q, a W_k, a W_v`` (as
  many key/value heads as query heads); rotary over the whole head, pairs
  ``(i, i + D/2)``, theta 1e6, position = the token's index, the same in
  every pass; ``o = softmax(mask(q k^T / sqrt(D))) v``, causal;
  ``h <- h + RMS(o W_o; w2)``.
- feed-forward: ``m = RMS(h; w3)``; ``h <- h + RMS((silu(m W_g) * (m
  W_u)) W_d; w4)``.
- the loop: ``h_0 = E[tokens]``; for ``t = 1 .. R``: ``s = h_{t-1}``
  through every block, ``h_t = RMS(s; w_f)``: the one final norm after
  every pass, and the normed stream is what the next pass reads. The same
  weights in every pass; nothing tells a pass its number.
- exits: ``z_t = h_t W_head`` (untied); ``nll_t = logsumexp(z_t) -
  z_t[target]`` a token; ``g_t = h_t . w_gate + b_gate``; ``lambda_t =
  sigmoid(g_t)``.
- a token's stopping distribution: ``p_t = lambda_t prod_{j<t} (1 -
  lambda_j)`` for ``t < R``, ``p_R = prod_{j<R} (1 - lambda_j)``
  (``lambda_R`` is unused), from ``log_sigmoid``.
- ``loss = mean over tokens of [sum_t p_t nll_t - BETA H(p)]``, ``H(p) =
  -sum_t p_t log p_t``.

``passes_of(params, ...)`` lets a caller give every pass its own copy of
the layers (a list of R lists), which is how ``tests/test_ouro.py`` shows
that a shared leaf's gradient is the sum over the passes. The
``jax.checkpoint``s change no value: they let a backward pass at 8192
tokens make a block of scores or logits again and not keep it.
"""

import math

import jax
import jax.numpy as jnp

# every matrix product below goes through these two names and nothing else
# does, so that a control can compute the same loss with the operands
# rounded to another precision (PERF.md: how the tolerance was set)
matmul = jnp.matmul
einsum = jnp.einsum

UT_STEPS = 4
BETA = 0.05
EPS = 1e-6
ROPE_THETA = 1e6
ROW_BLOCK = 256


def _rms_norm(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x: [T, heads, D]; rotates the pairs (i, i + D/2) by
    t * theta^(-2i/D)."""
    T, half = x.shape[0], x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(u, a, theta):
    """One sequence, u: [T, d]."""
    T = u.shape[0]
    d, heads, hd = a["wq"].shape

    def project(w):
        return matmul(u, w.reshape(d, heads * hd)).reshape(T, heads, hd)

    q, k, v = project(a["wq"]), project(a["wk"]), project(a["wv"])
    q, k = _rope(q, theta), _rope(k, theta)
    rows = math.gcd(T, ROW_BLOCK)

    @jax.checkpoint
    def some_rows(first):
        qb = jax.lax.dynamic_slice_in_dim(q, first, rows)
        s = einsum("qhk,thk->hqt", qb, k) / jnp.sqrt(jnp.float32(hd))
        ahead = (first + jnp.arange(rows))[:, None] - jnp.arange(T)[None]
        s = s + jnp.where(ahead >= 0, 0.0, -jnp.inf)[None]
        return einsum("hqt,thk->qhk", jax.nn.softmax(s, axis=-1), v)

    o = jax.lax.map(some_rows, jnp.arange(0, T, rows)).reshape(T, heads, hd)
    return matmul(o.reshape(T, heads * hd), a["wo"].reshape(heads * hd, d))


def _layer(x, layer, eps, theta):
    u = _rms_norm(x, layer["norm"]["scale"], eps)
    if "attn" in layer:
        y = jax.lax.map(lambda row: _attention(row, layer["attn"], theta), u)
    else:
        m = layer["mlp"]
        y = matmul(
            jax.nn.silu(matmul(u, m["w_gate"])) * matmul(u, m["w_up"]),
            m["w_down"],
        )
    return x + _rms_norm(y, layer["out_norm"]["scale"], eps)


def _exit(h, targets, head, gate):
    """One pass's exit: each token's NLL and gate logit, [B, T] both."""
    B, T, d = h.shape

    @jax.checkpoint
    def some_rows(rows):
        hb, picked = rows
        z = matmul(hb, head)
        lse = jax.scipy.special.logsumexp(z, axis=-1)
        return lse - jnp.take_along_axis(z, picked[..., None], -1)[..., 0]

    rows = math.gcd(B * T, ROW_BLOCK)
    nll = jax.lax.map(some_rows, (
        h.reshape(-1, rows, d), targets.reshape(-1, rows)
    )).reshape(B, T)
    return nll, jnp.sum(h * gate["w"], -1) + gate["b"][0]


def stopping(gates):
    """``[log p_1, ..., log p_R]`` from the R gate logits (a list), each
    [B, T]; the last logit is unused."""
    log_p, ahead = [], 0.0
    for g in gates[:-1]:
        log_p.append(jax.nn.log_sigmoid(g) + ahead)
        ahead = ahead + jax.nn.log_sigmoid(-g)
    return log_p + [ahead + jnp.zeros_like(gates[-1])]


def passes_of(params, tokens, targets, layers_by_pass, *, eps=EPS,
              rope_theta=ROPE_THETA):
    """Every pass's ``(nll_t, g_t)``, pass ``t`` through
    ``layers_by_pass[t]``; float32 parameters."""
    h = params["embed"]["tokens"][tokens]
    exits = []
    for layers in layers_by_pass:
        for layer in layers:
            h = jax.checkpoint(
                lambda x, one: _layer(x, one, eps, rope_theta)
            )(h, layer)
        h = _rms_norm(h, params["final_norm"]["scale"], eps)
        exits.append(
            _exit(h, targets, params["lm_head"], params["exit_gate"])
        )
    return exits


def loss(params, tokens, targets, *, ut_steps=UT_STEPS, beta=BETA,
         layers_by_pass=None, **widths):
    """The expected NLL under the stopping distribution less ``beta``
    times its entropy, mean over tokens, float32 throughout."""
    with jax.default_matmul_precision("highest"):
        p = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
        if layers_by_pass is None:
            layers_by_pass = [p["layers"]] * ut_steps
        exits = passes_of(p, tokens, targets, layers_by_pass, **widths)
        log_p = stopping([g for _, g in exits])
        each = 0.0
        for (nll, _), lp in zip(exits, log_p):
            prob = jnp.exp(lp)
            each = each + prob * nll + beta * prob * lp
        return jnp.mean(each)
