"""Plain reference for the OLMoE family (``model_type: olmoe``): forward
pass and training loss in straightforward ``jax.numpy`` and float32,
``highest`` matmul precision, no kernels, no mesh, no bf16, no sorting or
grouping of tokens. Independent of ``dlrover_tpu``: it takes the
program's parameter tree (names as ``init_params`` lays them out) and
nothing else from it.

Follows the published OLMoE-1B-7B (arXiv:2409.02060 and the ``olmoe``
modelling code): pre-RMSNorm blocks (eps 1e-5), multi-head causal
attention scaled by 1/sqrt(head_dim) with RMSNorm over the WHOLE query and
key projections before the head split (QK-norm) and rotary positions
(theta 10000, pairs (i, i + D/2)) after it; every block's FFN a sparse
layer of SwiGLU experts: router softmax over all experts, the 8 largest
probabilities taken as they are (``norm_topk_prob`` false), each token's
output the gate-weighted sum of its experts' outputs, no shared expert;
final RMSNorm; untied output head. The loss is mean next-token NLL + 0.01
x load-balance loss + 0.001 x router z-loss.

Every expert is applied to every token, one expert at a time, and an
expert's output is kept where the token chose it (a 0/1 mask times the
gate value): that needs no dispatch, so nothing here can drop a token.

Departures from the source, each as the program has it:
- the attention projections are [d, heads, head_dim] tensors and the
  QK-norm scales [heads, head_dim]; read flattened, they are the
  source's [d, d] matrices and [d] scales;
- the balance loss is E * sum_i f_i * P_i with f_i the share of all k*T
  assignments that went to expert i (the paper's form). The ``olmoe``
  modelling code sums f over the k ranks without dividing by k, and pools
  the layers' tokens; here each layer has its own loss;
- the layers' auxiliary losses are summed, where the training code
  averages them over the layers: at a depth of L the weights act L times
  as strongly.
"""

import jax
import jax.numpy as jnp

TOP_K = 8
EPS = 1e-5
ROPE_THETA = 10000.0
BALANCE_WEIGHT = 0.01
Z_WEIGHT = 1e-3


def _rms_norm(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x: [T, heads, D]; rotates the pairs (i, i + D/2) by t * theta^(-2i/D)."""
    T, _, D = x.shape
    half = D // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(h, layer, eps, theta, qk_norm):
    """One sequence, h: [T, d]."""
    a = layer["attn"]
    d, heads, hd = a["wq"].shape
    kv_heads = a["wk"].shape[1]
    T = h.shape[0]
    q = h @ a["wq"].reshape(d, heads * hd)
    k = h @ a["wk"].reshape(d, kv_heads * hd)
    v = h @ a["wv"].reshape(d, kv_heads * hd)
    if qk_norm:
        q = _rms_norm(q, layer["q_norm"]["scale"].reshape(-1), eps)
        k = _rms_norm(k, layer["k_norm"]["scale"].reshape(-1), eps)
    q = _rope(q.reshape(T, heads, hd), theta)
    k = _rope(k.reshape(T, kv_heads, hd), theta)
    v = v.reshape(T, kv_heads, hd)
    group = heads // kv_heads
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    s = jnp.einsum("qhk,thk->hqt", q, k) / jnp.sqrt(jnp.float32(hd))
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None], s, -jnp.inf)
    o = jnp.einsum("hqt,thk->qhk", jax.nn.softmax(s, axis=-1), v)
    return o.reshape(T, heads * hd) @ a["wo"].reshape(heads * hd, d)


def _sparse_ffn(h, moe, top_k, norm_topk_prob):
    """h: [N, d] -> ([N, d], balance loss, z loss) of one sparse layer."""
    N = h.shape[0]
    E = moe.gate.shape[1]
    logits = h @ moe.gate
    probs = jax.nn.softmax(logits, axis=-1)
    vals, idx = jax.lax.top_k(probs, top_k)
    if norm_topk_prob:
        vals = vals / jnp.sum(vals, -1, keepdims=True)
    chosen = jax.nn.one_hot(idx, E, dtype=jnp.float32)  # [N, k, E]
    weight = jnp.sum(chosen * vals[..., None], axis=1)  # [N, E]

    def one_expert(acc, w):
        w_gate, w_up, w_down, weight_e = w
        y = (jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down
        return acc + weight_e[:, None] * y, None

    out, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(h),
        (moe.w_gate, moe.w_up, moe.w_down, weight.T),
    )
    share = jnp.sum(chosen, axis=(0, 1)) / (top_k * N)
    balance = E * jnp.sum(share * jnp.mean(probs, axis=0))
    z = jnp.mean(jax.scipy.special.logsumexp(logits, axis=-1) ** 2)
    return out, balance, z


def loss(params, tokens, targets, *, top_k=TOP_K, eps=EPS,
         rope_theta=ROPE_THETA, qk_norm=True, norm_topk_prob=False,
         balance_weight=BALANCE_WEIGHT, z_weight=Z_WEIGHT):
    """Mean next-token NLL + the weighted auxiliary losses, float32
    throughout. The defaults are OLMoE-1B-7B's."""
    with jax.default_matmul_precision("highest"):
        p = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
        B, T = tokens.shape
        x = p["embed"]["tokens"][tokens]
        aux = jnp.float32(0.0)
        for layer in p["layers"]:
            h = _rms_norm(x, layer["attn_norm"]["scale"], eps)
            # one sequence at a time: a [heads, T, T] score block each
            x = x + jax.lax.map(
                lambda row: _attention(row, layer, eps, rope_theta, qk_norm),
                h,
            )
            h = _rms_norm(x, layer["mlp_norm"]["scale"], eps)
            y, balance, z = _sparse_ffn(
                h.reshape(B * T, -1), layer["moe"], top_k, norm_topk_prob
            )
            x = x + y.reshape(x.shape)
            aux = aux + balance_weight * balance + z_weight * z
        x = _rms_norm(x, p["final_norm"]["scale"], eps)
        logp = jax.nn.log_softmax(x @ p["lm_head"], axis=-1)
        picked = jnp.take_along_axis(logp, targets[..., None], axis=-1)
        return -jnp.mean(picked) + aux
