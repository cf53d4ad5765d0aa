"""Plain reference for the ``mistral4`` family (Mistral-Small-4-119B-2603's
language model): forward pass and training loss in straightforward
``jax.numpy`` and float32, ``highest`` matmul precision, no kernels, no
mesh, no bf16, no padding in the attention, no sorting or grouping of
tokens. Independent of ``dlrover_tpu``: it takes the program's parameter
tree (names as ``init_params`` lays them out) and nothing else from it; a
layer's kind is read off its keys (``attn``, ``moe``) and every width off
the shapes of its matrices.

A published layer is a latent attention and then a block of experts, each
behind its own RMSNorm, ``h = x + attention(norm(x))``, ``y = h +
experts(norm(h))``: two entries of the tree's ``layers``. Every RMSNorm
has eps 1e-6 and is plain, ``w * x_hat``, ``w`` from 1. No bias anywhere.

- latent attention (MLA with a low-rank query, every layer): ``c_q =
  RMSNorm_1024(u W_qa)``, ``q = c_q W_qb``, 32 heads of ``[nope 64 | rope
  64]``; ``[c | k_r] = u W_kva`` (256 | 64); ``[k_nope | v] =
  RMSNorm_256(c) W_kvb``, 32 heads of 64 | 128; a head's key is ``[k_nope
  | k_r]`` with the ONE ``k_r`` every head shares; no q / k head norms.
- rotary positions on ``q_rope`` and ``k_r``, PAIRS ``(2j, 2j + 1)``
  (``rope_interleave``), pair ``j`` of 32 turned by ``pos * f_j`` with the
  YaRN table of the source's ``rope_parameters`` (the form of
  ``transformers``' ``_compute_yarn_parameters``): ``e_j = 10000^(-2j/64)``;
  ``corr(n) = 64 ln(8192 / (2 pi n)) / (2 ln 10000)``; ``low =
  floor(corr(32))``, ``high = ceil(corr(1))``; ``ramp_j = clip((j - low) /
  (high - low), 0, 1)``; ``f_j = e_j (1 - ramp_j) + (e_j / 128) ramp_j``.
  cos and sin are not scaled (``mscale`` = ``mscale_all_dim``).
- the query's position scale (``llama_4_scaling_beta``): ``q <- q * (1 +
  0.1 ln(1 + floor(pos / 8192)))`` on the whole 128-wide head, after the
  rotation.
- scores over 128 times ``m^2 / sqrt(128)``, ``m = 0.1 * 1 * ln(128) + 1``
  (DeepSeek-V3's reading of ``mscale_all_dim`` under YaRN); causal softmax,
  the full masked score matrix (a block of query rows at a time, so that
  16384 tokens fit); values 128 wide; out-projection.
- expert block: ``p = softmax(u W_r)`` over all 128 experts; the 4 largest
  are chosen; their gate values are their ``p`` over their sum, times 1;
  each routed expert ``W_d (silu(W_g u) * W_u u)``; one ungated shared
  expert of the same form; output = routed + shared.
- final RMSNorm, untied head; loss = mean next-token NLL + 0.02 times the
  balance loss of every expert block (``E sum_i f_i P_i``).

Every held expert is applied to every token, one expert at a time, and
its output kept where the token chose it (a 0/1 mask times the gate
value): no dispatch, so nothing here can drop a token.

Departures from the source, each as the program has it:
- a chip's share: the tree holds ``w_up.shape[0]`` of the experts the
  router scores, those from ``experts_offset`` on; the router scores and
  chooses among ALL of them, and what the absent experts would add to a
  token is left out (the chips that hold them add it in the deployment);
- forms the catalog row's keys size but do not spell (the configuration's
  ``assumed``): softmax scores with no selection bias and no group limit,
  the balance weight (Mixtral's), ``m^2`` on the softmax scale, the YaRN
  table and the position scale as ``transformers`` implements the keys;
- the attention projections are [d, heads, width] tensors;
- a block of query rows, an expert and a block of the head's rows are each
  made again in a backward pass (``jax.checkpoint``), so that a gradient at
  16384 tokens fits beside the weights: the arithmetic is the plain one;
- the source's vision encoder is not in the row's config and is left out:
  the model trains on text ids. No multi-token-prediction module.
"""

import math

import jax
import jax.numpy as jnp

# every matrix product below goes through these two names and nothing else
# does, so that a control can compute the same loss with the operands
# rounded to another precision (PERF.md: how the tolerance was set)
matmul = jnp.matmul
einsum = jnp.einsum

TOP_K = 4
ROUTED_SCALE = 1.0
EPS = 1e-6
ROPE_THETA = 1e4
ROPE_FACTOR = 128.0
ROPE_ORIGINAL_LEN = 8192
ROPE_BETA_FAST = 32.0
ROPE_BETA_SLOW = 1.0
MSCALE_ALL_DIM = 1.0
POS_SCALE_BETA = 0.1
BALANCE_WEIGHT = 0.02
ROW_BLOCK = 256


def _rms_norm(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def yarn_table(dims, theta, factor, original_len, beta_fast, beta_slow):
    """``f_j`` for the ``dims / 2`` pairs, and ``low`` and ``high``."""
    def corr(turns):
        return dims * math.log(original_len / (2 * math.pi * turns)) / (
            2 * math.log(theta)
        )

    low = max(math.floor(corr(beta_fast)), 0)
    high = min(math.ceil(corr(beta_slow)), dims - 1)
    j = jnp.arange(dims // 2, dtype=jnp.float32)
    ramp = jnp.clip((j - low) / max(high - low, 1e-3), 0.0, 1.0)
    e = theta ** (-2.0 * j / dims)
    return e * (1.0 - ramp) + e / factor * ramp, low, high


def softmax_mscale(factor, mscale_all_dim):
    """``m``: the scores are scaled by ``m^2 / sqrt(width)``."""
    return 0.1 * mscale_all_dim * math.log(factor) + 1.0


def pos_scale(pos, beta, original_len):
    """``s(pos)`` of a query at position ``pos`` (float32 array)."""
    return 1.0 + beta * jnp.log(1.0 + jnp.floor(pos / original_len))


def _rope_pairs(x, freqs):
    """x: [T, heads, D]; rotates the pairs (2j, 2j + 1) by ``t * f_j``."""
    T, D = x.shape[0], x.shape[-1]
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    pairs = x.reshape(*x.shape[:-1], D // 2, 2)
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack(
        [a * cos - b * sin, b * cos + a * sin], axis=-1
    ).reshape(x.shape)


def _latent_attention(u, a, eps, yarn, beta):
    """One sequence, u: [T, d]. ``yarn``: theta, factor, original length,
    beta_fast, beta_slow, mscale_all_dim."""
    T, d = u.shape
    heads, qk = (a["w_qb"] if "w_qb" in a else a["wq"]).shape[1:]
    latent = a["kv_norm"]["scale"].shape[0]
    rope = a["w_kva"].shape[1] - latent
    nope = qk - rope
    vd = a["w_kvb"].shape[2] - nope
    theta, factor, original_len, beta_fast, beta_slow, all_dim = yarn
    if "w_qb" in a:
        cq = _rms_norm(
            matmul(u, a["w_qa"]), a["q_latent_norm"]["scale"], eps
        )
        q = matmul(cq, a["w_qb"].reshape(-1, heads * qk))
    else:  # a tree whose query is projected whole (the tests' controls)
        q = matmul(u, a["wq"].reshape(d, heads * qk))
    q = q.reshape(T, heads, qk)
    down = matmul(u, a["w_kva"])
    c = _rms_norm(down[:, :latent], a["kv_norm"]["scale"], eps)
    kv = matmul(
        c, a["w_kvb"].reshape(latent, heads * (nope + vd))
    ).reshape(T, heads, nope + vd)
    freqs, _, _ = yarn_table(
        rope, theta, factor, original_len, beta_fast, beta_slow
    )
    k_r = _rope_pairs(down[:, None, latent:], freqs)  # one for every head
    k = jnp.concatenate(
        [kv[..., :nope], jnp.repeat(k_r, heads, axis=1)], -1
    )
    v = kv[..., nope:]
    q = jnp.concatenate(
        [q[..., :nope], _rope_pairs(q[..., nope:], freqs)], -1
    )
    pos = jnp.arange(T, dtype=jnp.float32)
    q = q * pos_scale(pos, beta, original_len)[:, None, None]
    scale = softmax_mscale(factor, all_dim) ** 2 / math.sqrt(qk)
    rows = math.gcd(T, ROW_BLOCK)

    @jax.checkpoint  # a backward pass makes a block's scores again
    def some_rows(first):
        qb = jax.lax.dynamic_slice_in_dim(q, first, rows)
        s = einsum("qhk,thk->hqt", qb, k) * scale
        seen = (first + jnp.arange(rows))[:, None] >= jnp.arange(T)[None]
        # added, not selected: a backward pass then keeps no mask a block
        s = s + jnp.where(seen, 0.0, -jnp.inf)[None]
        return einsum("hqt,thk->qhk", jax.nn.softmax(s, axis=-1), v)

    o = jax.lax.map(some_rows, jnp.arange(0, T, rows)).reshape(T, heads, vd)
    return matmul(o.reshape(T, heads * vd), a["wo"].reshape(heads * vd, d))


def _swiglu(h, w_gate, w_up, w_down):
    return matmul(jax.nn.silu(matmul(h, w_gate)) * matmul(h, w_up), w_down)


def _experts(h, moe, top_k, scale, offset):
    """h: [N, d] -> ([N, d], balance loss) of one expert block."""
    N = h.shape[0]
    E = moe.gate.shape[1]
    held = moe.w_up.shape[0]
    p = jax.nn.softmax(matmul(h, moe.gate), axis=-1)
    vals, idx = jax.lax.top_k(p, top_k)
    vals = scale * vals / jnp.sum(vals, -1, keepdims=True)
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
    return out, E * jnp.sum(share * jnp.mean(p, axis=0))


def loss(params, tokens, targets, *, top_k=TOP_K, routed_scale=ROUTED_SCALE,
         eps=EPS, rope_theta=ROPE_THETA, rope_factor=ROPE_FACTOR,
         rope_original_len=ROPE_ORIGINAL_LEN, rope_beta_fast=ROPE_BETA_FAST,
         rope_beta_slow=ROPE_BETA_SLOW, mscale_all_dim=MSCALE_ALL_DIM,
         pos_scale_beta=POS_SCALE_BETA, balance_weight=BALANCE_WEIGHT,
         experts_offset=0):
    """Mean next-token NLL + the weighted balance losses, float32
    throughout. The defaults are Mistral-Small-4-119B-2603's, and a share
    of the experts that starts at expert 0."""
    yarn = (
        rope_theta, rope_factor, rope_original_len, rope_beta_fast,
        rope_beta_slow, mscale_all_dim,
    )
    with jax.default_matmul_precision("highest"):
        p = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
        B, T = tokens.shape
        d = p["embed"]["tokens"].shape[1]
        x = p["embed"]["tokens"][tokens]
        aux = jnp.float32(0.0)

        @jax.checkpoint  # a backward pass makes each layer again
        def one_layer(x, layer):
            u = _rms_norm(x, layer["norm"]["scale"], eps)
            if "attn" in layer:
                return x + jax.lax.map(
                    lambda row: _latent_attention(
                        row, layer["attn"], eps, yarn, pos_scale_beta
                    ), u,
                ), 0.0
            y, balance = _experts(
                u.reshape(B * T, -1), layer["moe"], top_k, routed_scale,
                experts_offset,
            )
            return x + y.reshape(x.shape), balance_weight * balance

        for layer in p["layers"]:
            x, balance = one_layer(x, layer)
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
