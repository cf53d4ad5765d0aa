"""Plain reference for the ``afmoe`` family (arcee-ai's Trinity models;
``model_type: afmoe``): forward pass and training loss in straightforward
``jax.numpy`` and float32, ``highest`` matmul precision, no kernels, no
mesh, no bf16, no band or block in the attention (the full masked ``[T, T]``
scores, a block of query rows at a time so that 16384 tokens fit; the
logits a block of rows at a time likewise), no sorting or grouping of
tokens. Independent of ``dlrover_tpu``: it takes the
program's parameter tree (names as ``init_params`` lays them out) and
nothing else from it; a layer's kind is read off its keys (``attn``,
``mlp``, ``moe``), every width off the shapes of its matrices, and which
attention layers have the window off their number, as the source's
``layer_types`` gives it.

A published layer is an attention mixer and then a feed-forward, each
between two RMSNorms: ``h = x + norm_post(mixer(norm_in(x)))``, two entries
of the tree's ``layers`` (``norm`` before, ``out_norm`` after). Every
RMSNorm has eps 1e-5 and is plain, ``w * x_hat``, ``w`` from 1. No bias in
any projection.

- ``x0 = E[tokens] * sqrt(d)`` (``mup_enabled``); the head reads its own
  table and is not multiplied.
- attention layer (32 query heads on 4 key/value heads of 128): ``q, k, v =
  u W_q, u W_k, u W_v``; ``g = u W_g``, one gate value a query head and
  channel; RMSNorm over each head's 128-wide q and k, one weight vector for
  all heads; in a WINDOW layer rotary positions over all 128 dims (theta
  1e4, pairs ``(i, i + 64)``) and a query at ``t`` sees the keys ``t - 2047
  .. t``; in a GLOBAL layer no positions and every key up to ``t``; softmax
  of ``q . k / sqrt(128)``; ``a = (o * sigmoid(g)) W_o``. The attention
  layer number ``n`` of the tree (from 0) is published layer ``first_layer
  + n`` and is global where ``(layer + 1) % global_every == 0``
  (``global_attn_every_n_layers`` 4: layers 3, 7, ...), else a window layer.
- dense feed-forward (the leading layers): ``W_d (silu(W_g u) * W_u u)``.
- expert block: ``p = sigmoid(u W_r)`` over all experts; the 8 largest ``p
  + bias`` are chosen (no group limit: ``n_group`` 1); their gate values
  are their ``p`` over ``sum p + 1e-20`` (``route_norm``), times 2.826
  (``route_scale``); each routed expert ``W_d (silu(W_g u) * W_u u)``; one
  ungated shared expert of the same form; output = shared + routed.
- final RMSNorm, untied head; loss = mean next-token NLL (+ the balance
  loss of every expert block at ``balance_weight``, 0 here: the
  configuration balances by the bias alone).

Every held expert is applied to every token, one expert at a time, and its
output kept where the token chose it (a 0/1 mask times the gate value): no
dispatch, so nothing here can drop a token.

Departures from the source, each as the program has it:
- a chip's share: the tree holds ``w_up.shape[0]`` of the experts the
  router scores, those from ``experts_offset`` on; the router scores and
  chooses among ALL of them, and what the absent experts would add to a
  token is left out (the chips that hold them add it in the deployment);
- forms the catalog row's keys size but do not spell (the configuration's
  ``assumed``): the four norms a layer and where they stand, rotary in the
  window layers only, the gate as an element-wise sigmoid of a projection
  of the layer's normed input, the q / k norm a head before the rotation,
  the bias entering the choice and not the weight, the multiplier on the
  embedding only;
- the gate's projection ``W_g`` is the second half of each head's columns
  in a twice-wide ``wq`` ``[d, heads, 2 * 128]``, a head's ``[query |
  gate]``; the other projections are [d, heads, width] tensors.
"""

import math

import jax
import jax.numpy as jnp

# every matrix product below goes through these two names and nothing else
# does, so that a control can compute the same loss with the operands
# rounded to another precision (PERF.md: how the tolerance was set)
matmul = jnp.matmul
einsum = jnp.einsum

TOP_K = 8
ROUTED_SCALE = 2.826
EPS = 1e-5
ROPE_THETA = 1e4
WINDOW = 2048
GLOBAL_EVERY = 4
FIRST_LAYER = 1
BALANCE_WEIGHT = 0.0
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


def _attention(u, layer, eps, theta, window):
    """One sequence, u: [T, d]. ``window`` None: a global layer."""
    a = layer["attn"]
    T = u.shape[0]
    d, heads, wide = a["wq"].shape
    kv_heads, hd = a["wk"].shape[1:]
    qg = matmul(u, a["wq"].reshape(d, heads * wide)).reshape(T, heads, wide)
    q, gate = qg[..., :hd], qg[..., hd:]
    k = matmul(u, a["wk"].reshape(d, kv_heads * hd)).reshape(T, kv_heads, hd)
    v = matmul(u, a["wv"].reshape(d, kv_heads * hd)).reshape(T, kv_heads, hd)
    q = _rms_norm(q, layer["q_norm"]["scale"], eps)
    k = _rms_norm(k, layer["k_norm"]["scale"], eps)
    if window is not None:
        q, k = _rope(q, theta), _rope(k, theta)
    k = jnp.repeat(k, heads // kv_heads, axis=1)
    v = jnp.repeat(v, heads // kv_heads, axis=1)
    rows = math.gcd(T, ROW_BLOCK)

    @jax.checkpoint  # a backward pass makes a block's scores again
    def some_rows(first):
        qb = jax.lax.dynamic_slice_in_dim(q, first, rows)
        s = einsum("qhk,thk->hqt", qb, k) / jnp.sqrt(jnp.float32(hd))
        ahead = (first + jnp.arange(rows))[:, None] - jnp.arange(T)[None]
        seen = ahead >= 0
        if window is not None:
            seen = seen & (ahead < window)
        # added, not selected: a backward pass then keeps no mask a block
        s = s + jnp.where(seen, 0.0, -jnp.inf)[None]
        return einsum("hqt,thk->qhk", jax.nn.softmax(s, axis=-1), v)

    o = jax.lax.map(some_rows, jnp.arange(0, T, rows)).reshape(T, heads, hd)
    o = o * jax.nn.sigmoid(gate)
    return matmul(o.reshape(T, heads * hd), a["wo"].reshape(heads * hd, d))


def _swiglu(h, w_gate, w_up, w_down):
    return matmul(jax.nn.silu(matmul(h, w_gate)) * matmul(h, w_up), w_down)


def _experts(h, moe, top_k, scale, offset):
    """h: [N, d] -> ([N, d], balance loss) of one expert block."""
    N = h.shape[0]
    E = moe.gate.shape[1]
    held = moe.w_up.shape[0]
    p = jax.nn.sigmoid(matmul(h, moe.gate))
    _, idx = jax.lax.top_k(jax.lax.stop_gradient(p + moe.bias), top_k)
    vals = jnp.take_along_axis(p, idx, axis=-1)
    vals = scale * vals / (jnp.sum(vals, -1, keepdims=True) + 1e-20)
    chosen = jax.nn.one_hot(idx, E, dtype=jnp.float32)  # [N, k, E]
    weight = jnp.sum(chosen * vals[..., None], axis=1)  # [N, E]

    @jax.checkpoint  # a backward pass makes an expert's output again
    def one_expert(weight_e, w_gate, w_up, w_down):
        return weight_e[:, None] * _swiglu(h, w_gate, w_up, w_down)

    out = jnp.zeros_like(h)
    for e in range(held):
        out = out + one_expert(
            weight[:, offset + e], moe.w_gate[e], moe.w_up[e], moe.w_down[e]
        )
    out = out + _swiglu(h, moe.shared_gate, moe.shared_up, moe.shared_down)
    share = jnp.sum(chosen, axis=(0, 1)) / (top_k * N)
    probs = p / jnp.sum(p, -1, keepdims=True)
    return out, E * jnp.sum(share * jnp.mean(probs, axis=0))


def loss(params, tokens, targets, *, top_k=TOP_K, routed_scale=ROUTED_SCALE,
         eps=EPS, rope_theta=ROPE_THETA, window=WINDOW,
         global_every=GLOBAL_EVERY, first_layer=FIRST_LAYER,
         balance_weight=BALANCE_WEIGHT, experts_offset=0):
    """Mean next-token NLL + the weighted balance losses, float32
    throughout. The defaults are Trinity-Mini's, the tree's first
    attention layer published layer 1, and a share of the experts that
    starts at expert 0."""
    with jax.default_matmul_precision("highest"):
        p = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
        B, T = tokens.shape
        d = p["embed"]["tokens"].shape[1]
        x = p["embed"]["tokens"][tokens] * jnp.sqrt(jnp.float32(d))
        aux = jnp.float32(0.0)

        def one_layer(x, layer, layer_window):
            u = _rms_norm(x, layer["norm"]["scale"], eps)
            balance = 0.0
            if "attn" in layer:
                y = jax.lax.map(
                    lambda row: _attention(
                        row, layer, eps, rope_theta, layer_window
                    ), u,
                )
            elif "mlp" in layer:
                m = layer["mlp"]
                y = _swiglu(u, m["w_gate"], m["w_up"], m["w_down"])
            else:
                y, balance = _experts(
                    u.reshape(B * T, -1), layer["moe"], top_k, routed_scale,
                    experts_offset,
                )
                y = y.reshape(x.shape)
            y = _rms_norm(y, layer["out_norm"]["scale"], eps)
            return x + y, balance_weight * balance

        published = first_layer
        for layer in p["layers"]:
            layer_window = None
            if "attn" in layer:
                if (published + 1) % global_every:
                    layer_window = window
                published += 1
            # a backward pass makes each layer again
            x, balance = jax.checkpoint(
                one_layer, static_argnums=(2,)
            )(x, layer, layer_window)
            aux = aux + balance
        x = _rms_norm(x, p["final_norm"]["scale"], eps)

        @jax.checkpoint  # a backward pass makes a block's logits again
        def some_rows(rows):
            h, picked = rows
            logp = jax.nn.log_softmax(matmul(h, p["lm_head"]), axis=-1)
            return jnp.take_along_axis(logp, picked[..., None], axis=-1)

        rows = math.gcd(B * T, ROW_BLOCK)
        logp = jax.lax.map(some_rows, (
            x.reshape(-1, rows, d), targets.reshape(-1, rows)
        ))
        return -jnp.mean(logp) + aux
