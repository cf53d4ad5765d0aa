"""Plain reference for the Olmo-Hybrid family (``model_type: olmo_hybrid``):
forward pass and training loss in straightforward ``jax.numpy`` and float32,
``highest`` matmul precision, no kernels, no mesh, no bf16, no chunks and no
WY transform in the delta rule. Independent of ``dlrover_tpu``: it takes the
program's parameter tree (names as ``init_params`` lays them out) and
nothing else from it; a layer's kind is read off its keys (``gdn``,
``attn``, ``mlp``), and whether a sub-layer norms its input or its output
off ``norm`` and ``out_norm``.

Follows the published Olmo-Hybrid-7B (its ``config.json``; what the keys do
not spell is the configuration file's ``assumed``). ``x = E[ids]``; every
RMSNorm has eps 1e-6 and a plain weight from 1; no bias anywhere. A
published layer is a mixer and then a SwiGLU of 11008: two entries of the
tree's ``layers``. Of four published layers:

- three are **linear attention** (Gated DeltaNet, 30 heads, key 96, value
  192, one value head a key head), pre-norm, ``x <- x + f(RMSNorm(x))``:
  ``[q | k | v] = h W_qkv`` (2880, 2880, 5760), each channel through a
  causal convolution of 4 taps (four shifted products, no bias) and SiLU;
  a head: ``q <- q / |q| / sqrt(96)``, ``k <- k / |k|`` (``rsqrt(sum x^2 +
  1e-6)``); ``[b | a] = h W_ba``, **``beta = 2 sigmoid(b)``** in (0, 2)
  (``linear_allow_neg_eigval``), ``g = -exp(A_log) softplus(a + dt_bias)``;
  the state ``S [96, 192]`` a head from 0, ONE STEP AT A TIME: ``S <-
  exp(g_t) S``; ``S <- S + k_t (outer) (beta_t (v_t - S^T k_t))``; ``o_t =
  S^T q_t`` (the transition's eigenvalue along ``k_t`` is ``exp(g_t) (1 -
  beta_t)``: negative where beta > 1); then ``w * RMSNorm_192(o) *
  silu(h W_z)`` a head and the out-projection; its SwiGLU pre-norm too;
- one is **attention** (Olmo 3's block, 30 heads of 128 on 30 key/value
  heads), reordered norm, ``x <- x + RMSNorm(f(x))`` with NO norm on the
  input: ``q = RMSNorm_3840(x W_q)``, ``k = RMSNorm_3840(x W_k)`` (one mean
  square over a token's whole projection, a weight a head and dim), ``v =
  x W_v``; no rotation and no positions; causal softmax scaled by
  1/sqrt(128), the full masked score matrix (a block of query rows at a
  time, so that 16384 tokens fit); out-projection; its SwiGLU reads ``x``
  unnormed and its output is normed.

Then the final RMSNorm, the untied head (a block of rows at a time) and the
mean next-token NLL.

The delta rule's steps run in blocks that a backward pass makes again, so
that a gradient over thousands of steps need not keep every state, and so
do a block of score rows and of head rows; the arithmetic is the plain
recurrence and the plain products.
"""

import math

import jax
import jax.numpy as jnp

# every matrix product below goes through these two names and nothing else
# does, so that a control can compute the same loss with the operands
# rounded to another precision (PERF.md: how the tolerance was set)
matmul = jnp.matmul
einsum = jnp.einsum

EPS = 1e-6
L2_EPS = 1e-6
KEY_HEADS = 30
BETA_SCALE = 2.0
ROW_BLOCK = 512


def _rms_norm(x, w, eps):
    """Over the last axis; the weight is the scale itself."""
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _l2norm(x):
    return x / jnp.sqrt(jnp.sum(x * x, -1, keepdims=True) + L2_EPS)


def _delta_rule(q, k, v, beta, g):
    """One sequence, one step at a time. q, k [T, H, dk], v [T, H, dv],
    beta, g [T, H] -> o [T, H, dv]."""
    T, H, dk = q.shape

    def step(S, inp):
        q_t, k_t, v_t, beta_t, g_t = inp
        S = jnp.exp(g_t)[:, None, None] * S
        read = jnp.sum(S * k_t[:, :, None], axis=1)  # S^T k_t: [H, dv]
        S = S + k_t[:, :, None] * (beta_t[:, None] * (v_t - read))[:, None, :]
        return S, jnp.sum(S * q_t[:, :, None], axis=1)

    @jax.checkpoint
    def block(S, inp):
        return jax.lax.scan(step, S, inp)

    n = math.gcd(T, 128)
    xs = jax.tree_util.tree_map(
        lambda t: t.reshape(T // n, n, *t.shape[1:]), (q, k, v, beta, g)
    )
    S0 = jnp.zeros((H, dk, v.shape[-1]), jnp.float32)
    _, o = jax.lax.scan(block, S0, xs)
    return o.reshape(T, H, v.shape[-1])


def _gated_delta(u, p, key_heads, beta_scale, eps):
    """One sequence, u: [T, d]; one value head a key head."""
    T = u.shape[0]
    H = p["A_log"].shape[0]
    dv = p["norm"].shape[0]
    key_w = (p["w_qkv"].shape[1] - H * dv) // 2
    dk = key_w // key_heads
    qkv = matmul(u, p["w_qkv"])
    z = matmul(u, p["w_z"]).reshape(T, H, dv)
    ba = matmul(u, p["w_ba"])
    taps = p["conv_w"].shape[0]
    padded = jnp.pad(qkv, ((taps - 1, 0), (0, 0)))
    qkv = jax.nn.silu(sum(
        padded[i:i + T] * p["conv_w"][i] for i in range(taps)
    ))
    q = _l2norm(qkv[:, :key_w].reshape(T, key_heads, dk))
    q = q / jnp.sqrt(jnp.float32(dk))
    k = _l2norm(qkv[:, key_w:2 * key_w].reshape(T, key_heads, dk))
    v = qkv[:, 2 * key_w:].reshape(T, H, dv)
    beta = beta_scale * jax.nn.sigmoid(ba[:, :H])
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(ba[:, H:] + p["dt_bias"])
    o = _delta_rule(q, k, v, beta, g)
    o = _rms_norm(o, p["norm"], eps) * jax.nn.silu(z)
    return matmul(o.reshape(T, H * dv), p["w_out"])


def _attention(u, layer, eps):
    """One sequence, u: [T, d], as many key/value heads as query heads, no
    positions."""
    a = layer["attn"]
    d, heads, hd = a["wq"].shape
    T = u.shape[0]
    width = heads * hd
    q = matmul(u, a["wq"].reshape(d, width))
    k = matmul(u, a["wk"].reshape(d, width))
    v = matmul(u, a["wv"].reshape(d, width)).reshape(T, heads, hd)
    # one mean square over the token's whole projection
    q = _rms_norm(q, layer["q_norm"]["scale"].reshape(width), eps)
    k = _rms_norm(k, layer["k_norm"]["scale"].reshape(width), eps)
    q, k = q.reshape(T, heads, hd), k.reshape(T, heads, hd)
    rows = math.gcd(T, ROW_BLOCK)

    @jax.checkpoint  # a backward pass makes a block's scores again
    def some_rows(first):
        qb = jax.lax.dynamic_slice_in_dim(q, first, rows)
        s = einsum("qhk,thk->hqt", qb, k) / jnp.sqrt(jnp.float32(hd))
        seen = (first + jnp.arange(rows))[:, None] >= jnp.arange(T)[None]
        s = jnp.where(seen[None], s, -jnp.inf)
        return einsum("hqt,thk->qhk", jax.nn.softmax(s, axis=-1), v)

    o = jax.lax.map(some_rows, jnp.arange(0, T, rows)).reshape(T, width)
    return matmul(o, a["wo"].reshape(width, d))


def _swiglu(h, mlp):
    return matmul(
        jax.nn.silu(matmul(h, mlp["w_gate"])) * matmul(h, mlp["w_up"]),
        mlp["w_down"],
    )


def _nll(x, w_head, targets):
    """Sum of the next-token NLL over x [N, d], a block of rows at a time."""
    N = x.shape[0]
    rows = math.gcd(N, ROW_BLOCK)

    @jax.checkpoint
    def some_rows(first):
        xb = jax.lax.dynamic_slice_in_dim(x, first, rows)
        tb = jax.lax.dynamic_slice_in_dim(targets, first, rows)
        logp = jax.nn.log_softmax(matmul(xb, w_head), axis=-1)
        return -jnp.sum(jnp.take_along_axis(logp, tb[:, None], axis=-1))

    return jnp.sum(jax.lax.map(some_rows, jnp.arange(0, N, rows)))


def loss(params, tokens, targets, *, eps=EPS, key_heads=KEY_HEADS,
         beta_scale=BETA_SCALE):
    """Mean next-token NLL, float32 throughout. The defaults are
    Olmo-Hybrid-7B's."""
    with jax.default_matmul_precision("highest"):
        p = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
        B, T = tokens.shape
        x = p["embed"]["tokens"][tokens]

        @jax.checkpoint  # a backward pass makes each layer again
        def one_layer(x, layer):
            u = x
            if "norm" in layer:  # pre-norm; a reordered layer has none
                u = _rms_norm(x, layer["norm"]["scale"], eps)
            if "gdn" in layer:
                y = jax.lax.map(
                    lambda row: _gated_delta(
                        row, layer["gdn"], key_heads, beta_scale, eps
                    ), u,
                )
            elif "attn" in layer:
                y = jax.lax.map(lambda row: _attention(row, layer, eps), u)
            else:
                y = _swiglu(u, layer["mlp"])
            if "out_norm" in layer:
                y = _rms_norm(y, layer["out_norm"]["scale"], eps)
            return x + y

        for layer in p["layers"]:
            x = one_layer(x, layer)
        x = _rms_norm(x, p["final_norm"]["scale"], eps)
        total = _nll(
            x.reshape(B * T, -1), p["lm_head"], targets.reshape(B * T)
        )
        return total / (B * T)
