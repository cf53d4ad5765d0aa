"""Plain reference for the Nemotron-H family (``model_type: nemotron_h``):
forward pass and training loss in straightforward ``jax.numpy`` and
float32, ``highest`` matmul precision, no kernels, no mesh, no bf16, no
chunked scan, no sorting or grouping of tokens. Independent of
``dlrover_tpu``: it takes the program's parameter tree (names as
``init_params`` lays them out) and nothing else from it; a layer's kind
is read off its keys (``ssm``, ``attn``, ``moe``).

Follows the published NVIDIA-Nemotron-3-Nano-30B-A3B (the ``nemotron_h``
modelling code and the Nemotron-H description, arXiv:2504.03624): every
layer is ONE mixer behind one RMSNorm (eps 1e-5), ``x + mixer(norm(x))``.

- Mamba-2 layer: ``[z | xBC | dt] = u W_in``; causal depthwise
  convolution of 4 with bias over the xBC channels, then SiLU; split
  into x [heads, 64], B and C [8 groups, 128], head h using group h // 8;
  ``dt = softplus(dt + dt_bias)``, ``a = -exp(A_log)``; the recurrence
  ``S_t = exp(dt_t a) S_{t-1} + dt_t x_t (outer) B_t``,
  ``y_t = S_t C_t + D x_t``, ONE STEP AT A TIME over the sequence;
  ``RMSNorm(y * silu(z))`` over groups of d_inner / 8 with one weight
  vector; out-projection.
- attention layer: 32 query heads and 2 key/value heads of 128, NO
  positions of any kind, causal softmax scaled by 1/sqrt(128), the full
  masked score matrix (computed a block of query rows at a time, so that
  8192 tokens fit).
- expert layer: ``s = sigmoid(u W_r)`` over all experts; the 6 largest
  of ``s + b`` chosen; gate values the chosen ``s`` over their sum, times
  2.5; each routed expert ``W2 relu(W1 u)^2``; the shared expert the same
  at its own width; output = routed + shared.
- final RMSNorm, untied head; loss = mean next-token NLL + 1e-4 x the
  balance loss of every expert layer.

Every held expert is applied to every token, one expert at a time, and
its output kept where the token chose it (a 0/1 mask times the gate
value): no dispatch, so nothing here can drop a token.

Departures from the source, each as the program has it:
- a chip's share: the tree holds ``w_up.shape[0]`` of the experts the
  router scores, those from ``experts_offset`` on; the router scores and
  chooses among ALL of them, and what the absent experts would add to a
  token is left out (the chips that hold them add it in the deployment);
- the in-projection is three matrices ``w_z``, ``w_xbc``, ``w_dt``: side
  by side they are the source's one ``in_proj`` ([z | xBC | dt]); the
  attention projections are [d, heads, head_dim] tensors;
- the balance loss (the source trains with none; the selection bias
  alone balances it) is E * sum_i f_i * P_i with f_i the share of all
  k*T assignments that went to expert i and P_i the mean of the scores
  normalised to sum to one a token, each layer's own, the layers summed;
- the source's ``rope_theta`` is unused: its attention layers call no
  rotary embedding (the Nemotron-H description).
"""

import math

import jax
import jax.numpy as jnp

# every matrix product below goes through these two names and nothing else
# does, so that a control can compute the same loss with the operands
# rounded to another precision (PERF.md: how the tolerance was set)
matmul = jnp.matmul
einsum = jnp.einsum

TOP_K = 6
EPS = 1e-5
ROUTED_SCALE = 2.5
BALANCE_WEIGHT = 1e-4
SSM_GROUPS = 8
ROW_BLOCK = 512


def _rms_norm(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _recurrence(x, dt, a, Bm, Cm):
    """One sequence, one step at a time. x [T, H, P], dt [T, H], a [H],
    Bm, Cm [T, H, N] -> y [T, H, P]. The steps run in blocks that are
    recomputed in a backward pass, so that a gradient over 8192 steps
    need not keep every state; the arithmetic is the plain recurrence."""
    T, H, P = x.shape

    def step(S, inp):
        x_t, dt_t, B_t, C_t = inp
        S = (
            jnp.exp(dt_t * a)[:, None, None] * S
            + (dt_t[:, None] * x_t)[:, :, None] * B_t[:, None, :]
        )
        return S, jnp.sum(S * C_t[:, None, :], axis=-1)

    @jax.checkpoint
    def block(S, inp):
        return jax.lax.scan(step, S, inp)

    n = math.gcd(T, 128)
    xs = jax.tree_util.tree_map(
        lambda t: t.reshape(T // n, n, *t.shape[1:]), (x, dt, Bm, Cm)
    )
    S0 = jnp.zeros((H, P, Bm.shape[-1]), jnp.float32)
    _, ys = jax.lax.scan(block, S0, xs)
    return ys.reshape(T, H, P)


def _mamba2(u, p, groups, eps):
    """One sequence, u: [T, d]."""
    T = u.shape[0]
    heads = p["A_log"].shape[0]
    d_in = p["w_z"].shape[1]
    state = (p["w_xbc"].shape[1] - d_in) // (2 * groups)
    z = matmul(u, p["w_z"])
    xbc = matmul(u, p["w_xbc"])
    dt = jax.nn.softplus(matmul(u, p["w_dt"]) + p["dt_bias"])
    K = p["conv_w"].shape[0]
    padded = jnp.pad(xbc, ((K - 1, 0), (0, 0)))
    xbc = jax.nn.silu(p["conv_b"] + sum(
        padded[k:k + T] * p["conv_w"][k] for k in range(K)
    ))
    x = xbc[:, :d_in].reshape(T, heads, d_in // heads)
    Bm = xbc[:, d_in:d_in + groups * state].reshape(T, groups, state)
    Cm = xbc[:, d_in + groups * state:].reshape(T, groups, state)
    rep = heads // groups
    y = _recurrence(
        x, dt, -jnp.exp(p["A_log"]),
        jnp.repeat(Bm, rep, axis=1), jnp.repeat(Cm, rep, axis=1),
    )
    y = (y + p["D"][:, None] * x).reshape(T, d_in) * jax.nn.silu(z)
    y = y.reshape(T, groups, d_in // groups)
    y = y / jnp.sqrt(jnp.mean(y * y, -1, keepdims=True) + eps)
    return matmul(y.reshape(T, d_in) * p["norm"], p["w_out"])


def _attention(u, a):
    """One sequence, u: [T, d]; no positions."""
    d, heads, hd = a["wq"].shape
    kv_heads = a["wk"].shape[1]
    T = u.shape[0]
    q = matmul(u, a["wq"].reshape(d, heads * hd)).reshape(T, heads, hd)
    k = matmul(u, a["wk"].reshape(d, kv_heads * hd)).reshape(T, kv_heads, hd)
    v = matmul(u, a["wv"].reshape(d, kv_heads * hd)).reshape(T, kv_heads, hd)
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

    o = jax.lax.map(some_rows, jnp.arange(0, T, rows))
    return matmul(o.reshape(T, heads * hd), a["wo"].reshape(heads * hd, d))


def _experts(h, moe, top_k, scale, offset):
    """h: [N, d] -> ([N, d], balance loss) of one expert layer."""
    N = h.shape[0]
    E = moe.gate.shape[1]
    held = moe.w_up.shape[0]
    scores = jax.nn.sigmoid(matmul(h, moe.gate))
    _, idx = jax.lax.top_k(scores + moe.bias, top_k)
    vals = jnp.take_along_axis(scores, idx, axis=-1)
    vals = scale * vals / jnp.sum(vals, -1, keepdims=True)
    chosen = jax.nn.one_hot(idx, E, dtype=jnp.float32)  # [N, k, E]
    weight = jnp.sum(chosen * vals[..., None], axis=1)  # [N, E]

    @jax.checkpoint
    def one_expert(acc, w):
        w_up, w_down, weight_e = w
        y = matmul(jnp.square(jax.nn.relu(matmul(h, w_up))), w_down)
        return acc + weight_e[:, None] * y, None

    out, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(h),
        (moe.w_up, moe.w_down, weight.T[offset:offset + held]),
    )
    out = out + matmul(
        jnp.square(jax.nn.relu(matmul(h, moe.shared_up))), moe.shared_down
    )
    share = jnp.sum(chosen, axis=(0, 1)) / (top_k * N)
    probs = scores / jnp.sum(scores, -1, keepdims=True)
    return out, E * jnp.sum(share * jnp.mean(probs, axis=0))


def loss(params, tokens, targets, *, top_k=TOP_K, eps=EPS,
         routed_scale=ROUTED_SCALE, balance_weight=BALANCE_WEIGHT,
         ssm_groups=SSM_GROUPS, experts_offset=0):
    """Mean next-token NLL + the weighted balance losses, float32
    throughout. The defaults are Nemotron-3-Nano-30B-A3B's, and a share
    of the experts that starts at expert 0."""
    with jax.default_matmul_precision("highest"):
        p = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
        B, T = tokens.shape
        x = p["embed"]["tokens"][tokens]
        aux = jnp.float32(0.0)

        @jax.checkpoint  # a backward pass makes each layer again
        def one_layer(x, layer):
            u = _rms_norm(x, layer["norm"]["scale"], eps)
            if "ssm" in layer:
                return x + jax.lax.map(
                    lambda row: _mamba2(row, layer["ssm"], ssm_groups, eps),
                    u,
                ), 0.0
            if "attn" in layer:
                return x + jax.lax.map(
                    lambda row: _attention(row, layer["attn"]), u
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
        logp = jax.nn.log_softmax(matmul(x, p["lm_head"]), axis=-1)
        picked = jnp.take_along_axis(logp, targets[..., None], axis=-1)
        return -jnp.mean(picked) + aux
