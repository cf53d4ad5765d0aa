"""Plain reference for the ``ling3`` family (Ling-3.0-flash's language
model; its ``config.json`` names no ``model_type`` the program could take,
the name is this benchmark's): forward pass and training loss in
straightforward ``jax.numpy`` and float32, ``highest`` matmul precision, no
kernels, no mesh, no bf16, no chunks, no triangle's inverse and no
sub-blocks in the delta rule, no padding in the attention, no sorting or
grouping of tokens. Independent of ``dlrover_tpu``: it takes the program's
parameter tree (names as ``init_params`` lays them out) and nothing else
from it; a layer's kind is read off its keys (``gdn``, ``attn``, ``mlp``,
``moe``) and every width off the shapes of its matrices.

A published layer is a mixer and then a feed-forward, each behind its own
RMSNorm, ``h = x + mixer(norm(x))``, ``y = h + ffn(norm(h))``: two entries
of the tree's ``layers``. Every RMSNorm has eps 1e-6 and is plain, ``w *
x_hat``, ``w`` from 1. No bias in any projection.

- Kimi Delta Attention layer (arXiv:2510.26692; 32 heads of 128 / 128, five
  layers in six): ``[q | k | v] = u W_qkv``; causal depthwise convolution
  of 4 over those channels, no bias, then SiLU; ``q <- (q / |q|) /
  sqrt(128)``, ``k <- k / |k|`` a head (``rsqrt(sum x^2 + 1e-6)``);
  ``beta = sigmoid(u W_b)`` a head; the log-decay a head AND key channel,
  ``g = -5 * sigmoid(exp(A_log) * (u W_f + dt_bias))``, ``A_log`` a head,
  ``dt_bias`` a channel; per head the state ``S [128, 128]`` from 0, ONE
  STEP AT A TIME: ``S <- Diag(exp(g_t)) S``; ``S <- S + k_t (outer)
  (beta_t (v_t - S^T k_t))``; ``o_t = S^T q_t``; then ``w * RMSNorm_128(o)
  * sigmoid(u W_z)`` with one gate a head, and the out-projection. No
  rotary positions: the decay carries the order.
- latent attention layer (MLA, DeepSeek-V2's with the query projected
  whole; one layer in six): ``q = u W_q``, 32 heads of ``[nope 128 | rope
  64]``; ``[c | k_rope] = u W_kva`` (512 | 64); ``[k_nope | v] =
  RMSNorm_512(c) W_kvb``, 32 heads of 128 | 128; a head's key is ``[k_nope
  | k_rope]`` with the ONE ``k_rope`` every head shares; RMSNorm over each
  head's 192-wide q and k, one weight vector for all heads; rotary
  positions (theta 6e6, pairs ``(i, i + 32)``) on the 64 rope dims of q
  and k; causal softmax scaled by 1/sqrt(192), the full masked score
  matrix (a block of query rows at a time, so that 8192 tokens fit);
  values 128 wide; out-projection. No output gate.
- dense feed-forward (the leading layer): ``W_d (silu(W_g u) * W_u u)``.
- expert block: ``s = sigmoid(u W_r)`` over all experts; ``s' = s + bias``;
  the experts lie in 8 groups by index, a group scores the sum of its two
  largest ``s'``, and outside a token's 4 best groups ``s'`` is masked; the
  8 largest ``s'`` left are chosen; their gate values are their ``s`` over
  their sum, times 2.5; each routed expert ``W_d (silu(W_g u) * W_u u)``;
  one ungated shared expert of the same form; output = routed + shared.
- final RMSNorm, untied head; loss = mean next-token NLL (+ the balance
  loss of every expert block at ``balance_weight``, 0 here: the source
  balances by the bias alone).

Every held expert is applied to every token, one expert at a time, and
its output kept where the token chose it (a 0/1 mask times the gate
value): no dispatch, so nothing here can drop a token.

Departures from the source, each as the program has it:
- a chip's share: the tree holds ``w_up.shape[0]`` of the experts the
  router scores, those from ``experts_offset`` on; the router scores and
  chooses among ALL of them, groups and bias over all their columns, and
  what the absent experts would add to a token is left out (the chips that
  hold them add it in the deployment);
- forms the catalog row's keys size but do not spell (the configuration's
  ``assumed``): the bounded decay's form, the head-wise sigmoid gate as the
  KDA mixer's, the q / k norm a head over all 192 dims before the rotation,
  the latent attention as the LAST layer of a period of six, rotate-half;
- ``q_proj`` / ``k_proj`` / ``v_proj`` of the KDA mixer are the column
  blocks ``[q | k | v]`` of one matrix ``w_qkv`` and their three
  convolutions one ``conv_w``; the attention projections are [d, heads,
  width] tensors;
- the swiglu limits (``expert_swiglu_limit_list``) are 0 for every layer
  held here: nothing clamps;
- the source's multi-token-prediction module and its vision tower have no
  size among the row's keys and are left out: the model trains on text ids.
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
N_GROUP = 8
TOPK_GROUP = 4
ROUTED_SCALE = 2.5
EPS = 1e-6
L2_EPS = 1e-6
ROPE_THETA = 6e6
DECAY_BOUND = -5.0
BALANCE_WEIGHT = 0.0
ROW_BLOCK = 512


def _rms_norm(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _l2norm(x):
    return x / jnp.sqrt(jnp.sum(x * x, -1, keepdims=True) + L2_EPS)


def _delta_rule(q, k, v, beta, g):
    """One sequence, one step at a time. q, k, g [T, H, dk], v [T, H, dv],
    beta [T, H] -> o [T, H, dv]; ``exp(g_t)`` scales the state's rows, one
    factor a key channel. The steps run in blocks that are made again in
    a backward pass, so that a gradient over 8192 steps need not keep
    every state; the arithmetic is the plain recurrence."""
    T, H, dk = q.shape

    def step(S, inp):
        q_t, k_t, v_t, beta_t, g_t = inp
        S = jnp.exp(g_t)[:, :, None] * S
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


def _kda(u, p, eps, bound):
    """One sequence, u: [T, d]."""
    T = u.shape[0]
    H = p["A_log"].shape[0]
    dv = p["norm"].shape[0]
    dk = p["w_f"].shape[1] // H
    qkv = matmul(u, p["w_qkv"])
    K = p["conv_w"].shape[0]
    padded = jnp.pad(qkv, ((K - 1, 0), (0, 0)))
    qkv = jax.nn.silu(sum(
        padded[i:i + T] * p["conv_w"][i] for i in range(K)
    ))
    q = qkv[:, :H * dk].reshape(T, H, dk)
    k = qkv[:, H * dk:2 * H * dk].reshape(T, H, dk)
    v = qkv[:, 2 * H * dk:].reshape(T, H, dv)
    beta = jax.nn.sigmoid(matmul(u, p["w_b"]))
    f = (matmul(u, p["w_f"]) + p["dt_bias"]).reshape(T, H, dk)
    g = bound * jax.nn.sigmoid(jnp.exp(p["A_log"])[:, None] * f)
    q = _l2norm(q) / jnp.sqrt(jnp.float32(dk))
    o = _delta_rule(q, _l2norm(k), v, beta, g)
    o = o / jnp.sqrt(jnp.mean(o * o, -1, keepdims=True) + eps) * p["norm"]
    o = o * jax.nn.sigmoid(matmul(u, p["w_z"]))[:, :, None]
    return matmul(o.reshape(T, H * dv), p["w_out"])


def _rope(x, theta):
    """x: [T, heads, D]; rotates the pairs (i, i + D/2) by
    t * theta^(-2i/D)."""
    T, half = x.shape[0], x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _latent_attention(u, layer, eps, theta):
    """One sequence, u: [T, d]."""
    a = layer["attn"]
    T = u.shape[0]
    d, heads, qk = a["wq"].shape
    latent = a["kv_norm"]["scale"].shape[0]
    rope = a["w_kva"].shape[1] - latent
    nope = qk - rope
    vd = a["w_kvb"].shape[2] - nope
    q = matmul(u, a["wq"].reshape(d, heads * qk)).reshape(T, heads, qk)
    down = matmul(u, a["w_kva"])
    c = _rms_norm(down[:, :latent], a["kv_norm"]["scale"], eps)
    kv = matmul(
        c, a["w_kvb"].reshape(latent, heads * (nope + vd))
    ).reshape(T, heads, nope + vd)
    k_rope = jnp.repeat(down[:, None, latent:], heads, axis=1)
    k = jnp.concatenate([kv[..., :nope], k_rope], -1)
    v = kv[..., nope:]
    if "q_norm" in layer:
        q = _rms_norm(q, layer["q_norm"]["scale"], eps)
        k = _rms_norm(k, layer["k_norm"]["scale"], eps)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], theta)], -1)
    k = jnp.concatenate([k[..., :nope], _rope(k[..., nope:], theta)], -1)
    rows = math.gcd(T, ROW_BLOCK)

    @jax.checkpoint  # a backward pass makes a block's scores again
    def some_rows(first):
        qb = jax.lax.dynamic_slice_in_dim(q, first, rows)
        s = einsum("qhk,thk->hqt", qb, k) / jnp.sqrt(jnp.float32(qk))
        seen = (first + jnp.arange(rows))[:, None] >= jnp.arange(T)[None]
        s = jnp.where(seen[None], s, -jnp.inf)
        return einsum("hqt,thk->qhk", jax.nn.softmax(s, axis=-1), v)

    o = jax.lax.map(some_rows, jnp.arange(0, T, rows)).reshape(T, heads, vd)
    return matmul(o.reshape(T, heads * vd), a["wo"].reshape(heads * vd, d))


def _swiglu(h, w_gate, w_up, w_down):
    return matmul(jax.nn.silu(matmul(h, w_gate)) * matmul(h, w_up), w_down)


def _experts(h, moe, top_k, n_group, topk_group, scale, offset):
    """h: [N, d] -> ([N, d], balance loss) of one expert block."""
    N = h.shape[0]
    E = moe.gate.shape[1]
    held = moe.w_up.shape[0]
    s = jax.nn.sigmoid(matmul(h, moe.gate))
    biased = jax.lax.stop_gradient(s + moe.bias)
    # a group's score: the sum of its two largest biased scores; the
    # groups outside the topk_group best are masked out of the choice
    grouped = biased.reshape(N, n_group, E // n_group)
    group_score = jnp.sum(jnp.sort(grouped, axis=-1)[..., -2:], axis=-1)
    best = jnp.argsort(-group_score, axis=-1)[:, :topk_group]
    kept = jnp.zeros((N, n_group), bool).at[
        jnp.arange(N)[:, None], best
    ].set(True)
    allowed = jnp.repeat(kept, E // n_group, axis=1)  # [N, E]
    _, idx = jax.lax.top_k(jnp.where(allowed, biased, -jnp.inf), top_k)
    vals = jnp.take_along_axis(s, idx, axis=-1)
    vals = scale * vals / jnp.sum(vals, -1, keepdims=True)
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
    out = out + _swiglu(h, moe.shared_gate, moe.shared_up, moe.shared_down)
    share = jnp.sum(chosen, axis=(0, 1)) / (top_k * N)
    probs = s / jnp.sum(s, -1, keepdims=True)
    return out, E * jnp.sum(share * jnp.mean(probs, axis=0))


def loss(params, tokens, targets, *, top_k=TOP_K, n_group=N_GROUP,
         topk_group=TOPK_GROUP, routed_scale=ROUTED_SCALE, eps=EPS,
         rope_theta=ROPE_THETA, decay_bound=DECAY_BOUND,
         balance_weight=BALANCE_WEIGHT, experts_offset=0):
    """Mean next-token NLL + the weighted balance losses, float32
    throughout. The defaults are Ling-3.0-flash's, and a share of the
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
                    lambda row: _kda(row, layer["gdn"], eps, decay_bound), u
                ), 0.0
            if "attn" in layer:
                return x + jax.lax.map(
                    lambda row: _latent_attention(row, layer, eps, rope_theta),
                    u,
                ), 0.0
            if "mlp" in layer:
                m = layer["mlp"]
                return x + _swiglu(u, m["w_gate"], m["w_up"], m["w_down"]), 0.0
            y, balance = _experts(
                u.reshape(B * T, -1), layer["moe"], top_k, n_group,
                topk_group, routed_scale, experts_offset,
            )
            return x + y.reshape(x.shape), balance_weight * balance

        for layer in p["layers"]:
            x, balance = one_layer(x, layer)
            aux = aux + balance
        x = _rms_norm(x, p["final_norm"]["scale"], eps)
        logp = jax.nn.log_softmax(matmul(x, p["lm_head"]), axis=-1)
        picked = jnp.take_along_axis(logp, targets[..., None], axis=-1)
        return -jnp.mean(picked) + aux
