"""Plain reference for the Qwen3-Next family (``model_type: qwen3_next``):
forward pass and training loss in straightforward ``jax.numpy`` and
float32, ``highest`` matmul precision, no kernels, no mesh, no bf16, no
chunks and no WY transform in the delta rule, no sorting or grouping of
tokens. Independent of ``dlrover_tpu``: it takes the program's parameter
tree (names as ``init_params`` lays them out) and nothing else from it; a
layer's kind is read off its keys (``gdn``, ``attn``, ``moe``).

Follows the published Qwen3-Next-80B-A3B-Instruct (its ``config.json`` and
the ``qwen3_next`` modelling code). A published layer is a mixer and then a
block of experts, each behind its own RMSNorm, ``h = x + mixer(norm(x))``,
``y = h + experts(norm(h))``: two entries of the tree's ``layers``. Every
RMSNorm has eps 1e-6; the residual stream's norms, the final norm and the
q / k norms are zero-centred, ``x_hat * (1 + w)``; the DeltaNet's gated norm
is plain, ``w * x_hat``. No bias anywhere.

- Gated DeltaNet layer (32 value and 16 key heads of 128, three layers in
  four): ``[q | k | v] = u W_qkv`` (2048, 2048, 4096), ``z = u W_z``,
  ``[b | a] = u W_ba``; causal depthwise convolution of 4 over the q, k, v
  channels, no bias, then SiLU; ``beta = sigmoid(b)``, ``g = -exp(A_log) *
  softplus(a + dt_bias)``; each key head serves two value heads; ``q <-
  (q / |q|) / sqrt(128)``, ``k <- k / |k|`` (``rsqrt(sum x^2 + 1e-6)``);
  per value head the state ``S [128, 128]`` from 0, ONE STEP AT A TIME:
  ``S <- exp(g_t) S``; ``S <- S + k_t (outer) (beta_t (v_t - S^T k_t))``;
  ``o_t = S^T q_t``; then ``w * RMSNorm_128(o) * silu(z)`` a head and the
  out-projection.
- attention layer (16 query and 2 key/value heads of 256, one layer in
  four): a head's query projection is ``[query | gate]``; zero-centred
  RMSNorm over each head's 256 on q and k, one weight vector for all
  heads; rotary positions on dims 0-63 of each head (pairs ``(i, i +
  32)``, theta 1e7), dims 64-255 untouched; causal softmax scaled by
  1/sqrt(256), the full masked score matrix (computed a block of query
  rows at a time, so that 8192 tokens fit); ``o * sigmoid(gate)``; out-
  projection.
- expert block: ``p = softmax(u W_r)`` over all experts; the 10 largest
  chosen, their ``p`` renormalised to sum to one; each routed expert
  ``W_d (silu(W_g u) * W_u u)``; the shared expert the same, times
  ``sigmoid(u . w_sg)``; output = routed + shared.
- final zero-centred RMSNorm, untied head; loss = mean next-token NLL +
  0.001 x the balance loss of every expert block.

Every held expert is applied to every token, one expert at a time, and
its output kept where the token chose it (a 0/1 mask times the gate
value): no dispatch, so nothing here can drop a token.

Departures from the source, each as the program has it:
- a chip's share: the tree holds ``w_up.shape[0]`` of the experts the
  router scores, those from ``experts_offset`` on; the router scores and
  chooses among ALL of them, and what the absent experts would add to a
  token is left out (the chips that hold them add it in the deployment);
- ``in_proj_qkvz`` is the two matrices ``w_qkv`` and ``w_z`` with the
  columns in blocks ``[q | k | v]``, ``[z]``, and ``in_proj_ba`` is ``[b |
  a]``: the source's checkpoint interleaves them a key head, the same
  matrices under a permutation of columns; the attention projections are
  [d, heads, width] tensors;
- the balance loss is E * sum_i f_i * P_i with f_i the share of all k*T
  assignments that went to expert i and P_i the mean router probability,
  each block's own, the blocks summed (the ``qwen3_next`` modelling code
  pools the layers' tokens); its weight, ``router_aux_loss_coef``, is the
  modelling code's default 0.001; there is no router z-loss;
- the source's multi-token-prediction module is not among its
  ``config.json``'s keys and is left out.
"""

import math

import jax
import jax.numpy as jnp

# every matrix product below goes through these two names and nothing else
# does, so that a control can compute the same loss with the operands
# rounded to another precision (PERF.md: how the tolerance was set)
matmul = jnp.matmul
einsum = jnp.einsum

TOP_K = 10
EPS = 1e-6
L2_EPS = 1e-6
ROPE_THETA = 1e7
ROTARY_DIMS = 64
KEY_HEADS = 16
BALANCE_WEIGHT = 1e-3
ROW_BLOCK = 512


def _rms_norm(x, w, eps):
    """Zero-centred: the scale is ``1 + w``."""
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1.0 + w)


def _l2norm(x):
    return x / jnp.sqrt(jnp.sum(x * x, -1, keepdims=True) + L2_EPS)


def _delta_rule(q, k, v, beta, g):
    """One sequence, one step at a time. q, k [T, H, dk], v [T, H, dv],
    beta, g [T, H] -> o [T, H, dv]. The steps run in blocks that are made
    again in a backward pass, so that a gradient over 8192 steps need not
    keep every state; the arithmetic is the plain recurrence."""
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


def _gated_delta(u, p, key_heads, eps):
    """One sequence, u: [T, d]."""
    T = u.shape[0]
    Hv = p["A_log"].shape[0]
    dv = p["norm"].shape[0]
    key_w = (p["w_qkv"].shape[1] - Hv * dv) // 2
    dk = key_w // key_heads
    qkv = matmul(u, p["w_qkv"])
    z = matmul(u, p["w_z"]).reshape(T, Hv, dv)
    ba = matmul(u, p["w_ba"])
    K = p["conv_w"].shape[0]
    padded = jnp.pad(qkv, ((K - 1, 0), (0, 0)))
    qkv = jax.nn.silu(sum(
        padded[i:i + T] * p["conv_w"][i] for i in range(K)
    ))
    q = qkv[:, :key_w].reshape(T, key_heads, dk)
    k = qkv[:, key_w:2 * key_w].reshape(T, key_heads, dk)
    v = qkv[:, 2 * key_w:].reshape(T, Hv, dv)
    beta = jax.nn.sigmoid(ba[:, :Hv])
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(ba[:, Hv:] + p["dt_bias"])
    rep = Hv // key_heads
    q = jnp.repeat(_l2norm(q) / jnp.sqrt(jnp.float32(dk)), rep, axis=1)
    k = jnp.repeat(_l2norm(k), rep, axis=1)
    o = _delta_rule(q, k, v, beta, g)
    o = o / jnp.sqrt(jnp.mean(o * o, -1, keepdims=True) + eps) * p["norm"]
    o = o * jax.nn.silu(z)
    return matmul(o.reshape(T, Hv * dv), p["w_out"])


def _rope(x, theta, dims):
    """x: [T, heads, D]; rotates the pairs (i, i + dims/2) of the first
    ``dims`` by t * theta^(-2i/dims) and leaves the rest."""
    T = x.shape[0]
    half = dims // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2, rest = x[..., :half], x[..., half:dims], x[..., dims:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], -1
    )


def _attention(u, layer, eps, theta, rotary_dims):
    """One sequence, u: [T, d]."""
    a = layer["attn"]
    d, kv_heads, hd = a["wk"].shape
    heads = a["wq"].shape[1]
    T = u.shape[0]
    qg = matmul(u, a["wq"].reshape(d, heads * 2 * hd)).reshape(T, heads, 2 * hd)
    q, gate = qg[..., :hd], qg[..., hd:]
    k = matmul(u, a["wk"].reshape(d, kv_heads * hd)).reshape(T, kv_heads, hd)
    v = matmul(u, a["wv"].reshape(d, kv_heads * hd)).reshape(T, kv_heads, hd)
    q = _rope(_rms_norm(q, layer["q_norm"]["scale"], eps), theta, rotary_dims)
    k = _rope(_rms_norm(k, layer["k_norm"]["scale"], eps), theta, rotary_dims)
    group = heads // kv_heads
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    rows = math.gcd(T, ROW_BLOCK)

    @jax.checkpoint  # a backward pass makes a block's scores again
    def some_rows(first):
        qb = jax.lax.dynamic_slice_in_dim(q, first, rows)
        s = einsum("qhk,thk->hqt", qb, k) / jnp.sqrt(jnp.float32(hd))
        seen = (first + jnp.arange(rows))[:, None] >= jnp.arange(T)[None]
        s = jnp.where(seen[None], s, -jnp.inf)
        return einsum("hqt,thk->qhk", jax.nn.softmax(s, axis=-1), v)

    o = jax.lax.map(some_rows, jnp.arange(0, T, rows)).reshape(T, heads, hd)
    o = o * jax.nn.sigmoid(gate)
    return matmul(o.reshape(T, heads * hd), a["wo"].reshape(heads * hd, d))


def _swiglu(h, w_gate, w_up, w_down):
    return matmul(jax.nn.silu(matmul(h, w_gate)) * matmul(h, w_up), w_down)


def _experts(h, moe, top_k, offset):
    """h: [N, d] -> ([N, d], balance loss) of one expert block."""
    N = h.shape[0]
    E = moe.gate.shape[1]
    held = moe.w_up.shape[0]
    probs = jax.nn.softmax(matmul(h, moe.gate), axis=-1)
    vals, idx = jax.lax.top_k(probs, top_k)
    vals = vals / jnp.sum(vals, -1, keepdims=True)
    chosen = jax.nn.one_hot(idx, E, dtype=jnp.float32)  # [N, k, E]
    weight = jnp.sum(chosen * vals[..., None], axis=1)  # [N, E]

    @jax.checkpoint
    def one_expert(acc, w):
        w_gate, w_up, w_down, weight_e = w
        return acc + weight_e[:, None] * _swiglu(h, w_gate, w_up, w_down), None

    out, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(h),
        (moe.w_gate, moe.w_up, moe.w_down, weight.T[offset:offset + held]),
    )
    shared = _swiglu(h, moe.shared_gate, moe.shared_up, moe.shared_down)
    out = out + jax.nn.sigmoid(matmul(h, moe.shared_out_gate))[:, None] * shared
    share = jnp.sum(chosen, axis=(0, 1)) / (top_k * N)
    return out, E * jnp.sum(share * jnp.mean(probs, axis=0))


def loss(params, tokens, targets, *, top_k=TOP_K, eps=EPS,
         rope_theta=ROPE_THETA, rotary_dims=ROTARY_DIMS,
         key_heads=KEY_HEADS, balance_weight=BALANCE_WEIGHT,
         experts_offset=0):
    """Mean next-token NLL + the weighted balance losses, float32
    throughout. The defaults are Qwen3-Next-80B-A3B's, and a share of the
    experts that starts at expert 0."""
    with jax.default_matmul_precision("highest"):
        p = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
        B, T = tokens.shape
        x = p["embed"]["tokens"][tokens]
        aux = jnp.float32(0.0)

        @jax.checkpoint  # a backward pass makes each layer again
        def one_layer(x, layer):
            u = _rms_norm(x, layer["norm"]["scale"], eps)
            if "gdn" in layer:
                return x + jax.lax.map(
                    lambda row: _gated_delta(row, layer["gdn"], key_heads, eps),
                    u,
                ), 0.0
            if "attn" in layer:
                return x + jax.lax.map(
                    lambda row: _attention(
                        row, layer, eps, rope_theta, rotary_dims
                    ), u,
                ), 0.0
            y, balance = _experts(
                u.reshape(B * T, -1), layer["moe"], top_k, experts_offset
            )
            return x + y.reshape(x.shape), balance_weight * balance

        for layer in p["layers"]:
            x, balance = one_layer(x, layer)
            aux = aux + balance
        x = _rms_norm(x, p["final_norm"]["scale"], eps)
        logp = jax.nn.log_softmax(matmul(x, p["lm_head"]), axis=-1)
        picked = jnp.take_along_axis(logp, targets[..., None], axis=-1)
        return -jnp.mean(picked) + aux
