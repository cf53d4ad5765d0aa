"""Plain reference for the ``phi4flash`` family (microsoft's
Phi-4-mini-flash-reasoning; the SambaY architecture, arXiv:2507.06607):
forward pass and training loss in straightforward ``jax.numpy`` and
float32, ``highest`` matmul precision, no kernels, no mesh, no bf16, no
chunked or parallel form of the scan (one recurrence step at a time), no
band or block in the attention (the full masked ``[T, T]`` scores, a block
of query rows at a time so that 16384 tokens fit; the logits a block of
rows at a time likewise). Independent of ``dlrover_tpu``: it takes the
program's parameter tree (names as ``init_params`` lays them out) and
nothing else from it; a layer's kind is read off its keys (``sscan``,
``attn``, ``gmu``, ``xattn``, ``mlp``), every width off the shapes of its
matrices, and which attention layers have the window off their number.

A published layer ``l`` is a mixer and then an MLP, each behind a
LayerNorm with weight and bias, eps 1e-5: ``h = h + mixer(LN(h))``, ``h = h
+ MLP(LN'(h))``, two entries of the tree's ``layers``. No positions
anywhere, no dropout. Mixer ``n`` of the tree (from 0) is published layer
``first_layer + n``.

- MLP: ``(silu(u W_gate) * (u W_up)) W_down``, no bias (the source's one
  ``W_1 = [gate | up]`` as two matrices).
- Mamba-1 mixer (``sscan``): ``x, z = u W_x, u W_z``; ``x = silu(causal
  depthwise conv(x) + b_conv)``; ``[delta | B | C] = x W_xproj``; ``dt =
  softplus(delta W_dt + b_dt)``; ``A = -exp(A_log)``; ``S_t[c, n] =
  exp(dt_t[c] A[c, n]) S_{t-1}[c, n] + dt_t[c] B_t[n] x_t[c]``, ``S_0 =
  0``; ``y_t[c] = sum_n C_t[n] S_t[c, n] + D[c] x_t[c]``; out ``(y *
  silu(z)) W_out``. The layer also hands ``m = y`` on.
- differential attention (``attn``; 2P query heads on 2J key/value heads
  of ``hd``): ``q, k, v = u W + b``; query heads ``(2i, 2i+1)`` are ``q1_i,
  q2_i``, key heads ``(2j, 2j+1)`` ``k1_j, k2_j``, the value of key pair
  ``j`` is ``v_j = [v_2j | v_2j+1]`` (``2 hd`` wide), pair ``i`` reads ``j =
  i // (P / J)``. ``o_i = (softmax(mask(q1 k1^T / sqrt(hd))) - lambda
  softmax(mask(q2 k2^T / sqrt(hd)))) v_j``, ``lambda = exp(lq1 . lk1) -
  exp(lq2 . lk2) + lambda_init(l)``, ``lambda_init(l) = 0.8 - 0.6 exp(-0.3
  l)``; ``o_i = RMSNorm(o_i; w_sub, eps) (1 - lambda_init(l))``; the pairs'
  outputs side by side through ``W_o + b_o``. The mask is causal; a layer
  ``l < full_from`` also sees no key more than ``window - 1`` before the
  query. A layer without a window hands its ``k, v`` on.
- gated memory unit (``gmu``): ``(silu(u W_in) * m) W_out`` with ``m`` the
  last scan layer's.
- cross-attention (``xattn``): ``q = u W_q + b_q`` only; ``k, v`` the last
  full attention layer's; the same differential attention with its own
  ``lambda`` vectors, ``w_sub``, ``W_o``, ``b_o``; causal, no window.
- final LayerNorm, logits from the tied table, mean next-token NLL.

Departures from the source, each as the program has it (the
configuration's ``assumed``): the pairing of heads above (any fixed pairing
is the same model under a permutation of random weights); the sizes the
row does not give (states, taps, rank) are read off the shapes; a chip's
share of the vocabulary is the whole table here (ids are drawn under it).
"""

import math

import jax
import jax.numpy as jnp

# every matrix product below goes through these two names and nothing else
# does, so that a control can compute the same loss with the operands
# rounded to another precision (PERF.md: how the tolerance was set)
matmul = jnp.matmul
einsum = jnp.einsum

EPS = 1e-5
WINDOW = 512
FIRST_LAYER = 14
# published layers from here on attend without a window
# (``num_hidden_layers / 2``: the self-decoder's last attention layer)
FULL_FROM = 16
ROW_BLOCK = 256
SCAN_BLOCK = 128


def _layer_norm(x, p, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def lambda_init(layer: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * layer)


def _conv(x, w, b):
    """Causal depthwise convolution, x: [T, C], w: [K, C]: ``y_t = b +
    sum_k w[k] x_{t-(K-1)+k}`` with zeros before the row's start."""
    K, T = w.shape[0], x.shape[0]
    padded = jnp.concatenate([jnp.zeros((K - 1, x.shape[1])), x])
    return b + sum(padded[k:k + T] * w[k] for k in range(K))


def _scan(x, dt, A, Bm, Cm, D):
    """The recurrence itself, one step at a time; x, dt: [T, C], A: [C, N],
    Bm, Cm: [T, N]. A backward pass makes a block of steps again."""
    T, C = x.shape
    block = math.gcd(T, SCAN_BLOCK)

    def step(S, at):
        xt, dtt, bt, ct = at
        S = jnp.exp(dtt[:, None] * A) * S + (dtt * xt)[:, None] * bt[None, :]
        return S, jnp.sum(S * ct[None, :], axis=-1)

    @jax.checkpoint
    def some_steps(S, steps):
        return jax.lax.scan(step, S, steps)

    _, y = jax.lax.scan(
        some_steps, jnp.zeros((C, A.shape[1])),
        tuple(t.reshape(T // block, block, -1) for t in (x, dt, Bm, Cm)),
    )
    return y.reshape(T, C) + D * x


def _mamba(u, m):
    """One sequence, u: [T, d] -> (out [T, d], y [T, d_in])."""
    N = m["A_log"].shape[1]
    R = m["w_dt"].shape[0]
    x, z = matmul(u, m["w_x"]), matmul(u, m["w_z"])
    x = jax.nn.silu(_conv(x, m["conv_w"], m["conv_b"]))
    dbc = matmul(x, m["w_xproj"])
    dt = jax.nn.softplus(matmul(dbc[:, :R], m["w_dt"]) + m["dt_bias"])
    y = _scan(
        x, dt, -jnp.exp(m["A_log"]), dbc[:, R:R + N], dbc[:, R + N:], m["D"]
    )
    return matmul(y * jax.nn.silu(z), m["w_out"]), y


def _keys_values(u, a):
    """One sequence's keys and values [T, heads, hd] of a layer that
    projects them."""
    T = u.shape[0]
    d, kv_heads, hd = a["wk"].shape
    k = matmul(u, a["wk"].reshape(d, -1)).reshape(T, kv_heads, hd) + a["bk"]
    v = matmul(u, a["wv"].reshape(d, -1)).reshape(T, kv_heads, hd) + a["bv"]
    return k, v


def _diff_attention(u, a, k, v, layer, eps, window):
    """One sequence, u: [T, d]; k, v: [T, 2J, hd]. ``window`` None: every
    key up to the query."""
    T = u.shape[0]
    d, heads, hd = a["wq"].shape
    pairs, key_pairs = heads // 2, k.shape[1] // 2
    q = matmul(u, a["wq"].reshape(d, -1)).reshape(T, heads, hd) + a["bq"]
    q = q.reshape(T, pairs, 2, hd)
    # pair i reads key pair i // (pairs / key_pairs)
    k = jnp.repeat(k.reshape(T, key_pairs, 2, hd), pairs // key_pairs, 1)
    v = jnp.repeat(v.reshape(T, key_pairs, 2 * hd), pairs // key_pairs, 1)
    init = lambda_init(layer)
    lam = (
        jnp.exp(jnp.sum(a["lambda_q1"] * a["lambda_k1"]))
        - jnp.exp(jnp.sum(a["lambda_q2"] * a["lambda_k2"])) + init
    )
    rows = math.gcd(T, ROW_BLOCK)

    @jax.checkpoint  # a backward pass makes a block's scores again
    def some_rows(first):
        qb = jax.lax.dynamic_slice_in_dim(q, first, rows)
        s = einsum("qpsk,tpsk->psqt", qb, k) / jnp.sqrt(jnp.float32(hd))
        ahead = (first + jnp.arange(rows))[:, None] - jnp.arange(T)[None]
        seen = ahead >= 0
        if window is not None:
            seen = seen & (ahead < window)
        # added, not selected: a backward pass then keeps no mask a block
        p = jax.nn.softmax(s + jnp.where(seen, 0.0, -jnp.inf), axis=-1)
        return einsum("pqt,tpk->qpk", p[:, 0] - lam * p[:, 1], v)

    o = jax.lax.map(some_rows, jnp.arange(0, T, rows))
    o = o.reshape(T, pairs, 2 * hd)
    o = o / jnp.sqrt(jnp.mean(o * o, -1, keepdims=True) + eps)
    o = o * a["subln"] * (1.0 - init)
    return matmul(o.reshape(T, -1), a["wo"].reshape(heads * hd, d)) + a["bo"]


def _swiglu(h, m):
    return matmul(
        jax.nn.silu(matmul(h, m["w_gate"])) * matmul(h, m["w_up"]),
        m["w_down"],
    )


def loss(params, tokens, targets, *, eps=EPS, window=WINDOW,
         first_layer=FIRST_LAYER, full_from=FULL_FROM):
    """Mean next-token NLL, float32 throughout. The defaults are the
    ``phi4-mini-flash-d6`` configuration's: the tree's first mixer is
    published layer 14, attention layers before layer 16 see 512 keys."""
    with jax.default_matmul_precision("highest"):
        p = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
        B, T = tokens.shape
        d = p["embed"]["tokens"].shape[1]
        x = p["embed"]["tokens"][tokens]

        def one_layer(x, layer, memory, keys_values, published):
            """-> (x, what a scan layer hands on, what a full attention
            layer hands on)."""
            u = _layer_norm(x, layer["norm"], eps)
            if "sscan" in layer:
                y, memory = jax.vmap(lambda r: _mamba(r, layer["sscan"]))(u)
            elif "attn" in layer:
                a = layer["attn"]
                k, v = jax.vmap(lambda r: _keys_values(r, a))(u)
                full = published >= full_from
                y = jax.lax.map(
                    lambda r: _diff_attention(
                        r[0], a, r[1], r[2], published, eps,
                        None if full else window,
                    ), (u, k, v),
                )
                if full:
                    keys_values = (k, v)
            elif "gmu" in layer:
                g = layer["gmu"]
                y = matmul(
                    jax.nn.silu(matmul(u, g["w_in"])) * memory, g["w_out"]
                )
            elif "xattn" in layer:
                y = jax.lax.map(
                    lambda r: _diff_attention(
                        r[0], layer["xattn"], r[1], r[2], published, eps,
                        None,
                    ), (u,) + keys_values,
                )
            else:
                y = _swiglu(u, layer["mlp"])
            return x + y, memory, keys_values

        published = first_layer
        memory = keys_values = None
        for layer in p["layers"]:
            # a backward pass makes each layer again
            x, memory, keys_values = jax.checkpoint(
                one_layer, static_argnums=(4,)
            )(x, layer, memory, keys_values, published)
            published += "mlp" not in layer
        x = _layer_norm(x, p["final_norm"], eps)
        table = p["embed"]["tokens"]

        @jax.checkpoint  # a backward pass makes a block's logits again
        def some_rows(rows):
            h, picked = rows
            logp = jax.nn.log_softmax(matmul(h, table.T), axis=-1)
            return jnp.take_along_axis(logp, picked[..., None], axis=-1)

        rows = math.gcd(B * T, ROW_BLOCK)
        logp = jax.lax.map(some_rows, (
            x.reshape(-1, rows, d), targets.reshape(-1, rows)
        ))
        return -jnp.mean(logp)
