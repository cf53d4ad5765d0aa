"""Plain reference for the ``sdar_moe`` family (JetLM's SDAR mixture-of-
experts models, arXiv:2510.06303) as it is TRAINED: by diffusion over
blocks (BD3-LM's vectorised form, arXiv:2503.09573, over MDLM's masked
objective, arXiv:2406.07524). Forward pass and training loss in
straightforward ``jax.numpy`` and float32, ``highest`` matmul precision, no
kernels, no mesh, no bf16, no walk over blocks: the visibility rule is a
boolean ``[rows, 2L]`` built from ``c`` and ``b`` below (a block of query
rows at a time so that 2 x 8192 positions fit; the logits a block of rows
at a time likewise), plain softmax, the held experts one at a time.
Independent of ``dlrover_tpu``: it takes the program's parameter tree
(names as ``init_params`` lays them out: a published layer is two entries
of ``layers``, an attention mixer and then the experts, each behind its one
RMSNorm ``norm``) and nothing else from it, and draws its own noise.

A row of data is ``x_0`` of ``L`` ids; ``B`` is the block length, ``MASK``
the mask token's id (the table's last row unless stated).

- noise (integers and float32): ``key = fold_in(PRNGKey(seed), sum(x_0) mod
  2^31)`` a row; ``t_b = t_min + (1 - t_min) U_b``, ``U_b ~ U[0, 1)`` a
  block from ``split(key)[0]``; ``u_i ~ U[0, 1)`` a position from
  ``split(key)[1]``; ``m_i = u_i < t_{i // B}``; ``x_t[i] = MASK if m_i else
  x_0[i]``.
- input: ids ``[x_t ; x_0]`` (2L), positions ``p(i) = i mod L``; ``h =
  E[ids]``.
- a layer (pre-norm, RMSNorm eps 1e-6, plain ``w * x_hat``, no bias):
  ``q, k, v = a W_q, a W_k, a W_v`` of ``a = RMSNorm(h)``; RMSNorm over each
  head's q and k, one weight vector for all heads of a kind; rotary over
  the whole head, pairs ``(d, d + D/2)``, theta 1e6, angle ``p(i)
  theta^(-2d/D)``; query head ``g`` reads key/value head ``g // group``;
  softmax of ``q . k / sqrt(D)`` over the keys a query sees; ``h += o W_o``.
  With ``c(i) = i >= L`` (clean) and ``b(i) = (i mod L) // B``, query ``i``
  sees key ``j`` iff ``(not c(i) and not c(j) and b(j) == b(i)) or (not
  c(i) and c(j) and b(j) < b(i)) or (c(i) and c(j) and b(j) <= b(i))``.
- experts: ``r = softmax(e W_r)`` of ``e = RMSNorm(h)`` over all experts;
  the 8 largest; gates ``r / sum of the chosen r``; ``h += sum over the
  chosen AND held experts of gate * W_down (silu(e W_gate) * e W_up)``.
  Every one of the 2L positions is routed.
- loss: ``z = RMSNorm(h[:L]) W_head`` (the clean half never reaches the
  head); ``(1 / L) sum_{i<L} m_i (1 / t_{i // B}) (logsumexp(z_i) -
  z_i[x_0[i]])``, mean over the rows, + 0.01 x the load-balance loss + 0.001
  x the router z-loss of every expert layer (OLMoE's forms and weights,
  ``references/olmoe.py``, over the 2L positions a row). ``targets`` is not
  read.

Departures from the source, each as the program has it: a chip's share (the
tree holds ``w_up.shape[0]`` of the experts the router scores, those from
``experts_offset`` on; what the absent ones would add is left out); block
length, schedule, ``t_min``, the aligned prediction and the doubled row are
the configuration's ``assumed``.
"""

import math

import jax
import jax.numpy as jnp

# every matrix product below goes through these two names and nothing else
# does, so that a control can compute the same loss with the operands
# rounded to another precision (PERF.md: how the tolerance was set)
matmul = jnp.matmul
einsum = jnp.einsum

BLOCK = 4
T_MIN = 1e-3
NOISE_SEED = 0
TOP_K = 8
EPS = 1e-6
ROPE_THETA = 1e6
BALANCE_WEIGHT = 0.01
Z_WEIGHT = 1e-3
ROW_BLOCK = 256


def noise(tokens, *, block=BLOCK, t_min=T_MIN, seed=NOISE_SEED):
    """rows x_0 [B, L] -> (masked [B, L] bool, t [B, L] float32)."""
    L = tokens.shape[1]
    root = jax.random.PRNGKey(seed)

    def one_row(row):
        total = jnp.sum(row.astype(jnp.uint32)) & jnp.uint32(2**31 - 1)
        key_t, key_u = jax.random.split(jax.random.fold_in(root, total))
        u_b = jax.random.uniform(key_t, (L // block,), jnp.float32)
        t = jnp.float32(t_min) + (1.0 - jnp.float32(t_min)) * u_b
        t = jnp.repeat(t, block)
        u = jax.random.uniform(key_u, (L,), jnp.float32)
        return u < t, t

    return jax.vmap(one_row)(tokens)


def _rms_norm(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    """x: [T, heads, D] at positions pos [T]; rotates the pairs (d, d +
    D/2) by pos * theta^(-2d/D)."""
    half = x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def sees(i, j, L, block):
    """The visibility rule: query positions ``i`` [n, 1] against key
    positions ``j`` [1, m] of the doubled row -> bool [n, m]."""
    ci, cj = i >= L, j >= L
    bi, bj = (i % L) // block, (j % L) // block
    return (
        (~ci & ~cj & (bj == bi)) | (~ci & cj & (bj < bi))
        | (ci & cj & (bj <= bi))
    )


def _attention(a, layer, eps, theta, block):
    """One doubled row, a: [2L, d]."""
    w = layer["attn"]
    T = a.shape[0]
    L = T // 2
    d, heads, hd = w["wq"].shape
    kv_heads = w["wk"].shape[1]
    pos = jnp.arange(T) % L
    q = matmul(a, w["wq"].reshape(d, heads * hd)).reshape(T, heads, hd)
    k = matmul(a, w["wk"].reshape(d, kv_heads * hd)).reshape(T, kv_heads, hd)
    v = matmul(a, w["wv"].reshape(d, kv_heads * hd)).reshape(T, kv_heads, hd)
    q = _rope(_rms_norm(q, layer["q_norm"]["scale"], eps), pos, theta)
    k = _rope(_rms_norm(k, layer["k_norm"]["scale"], eps), pos, theta)
    k = jnp.repeat(k, heads // kv_heads, axis=1)
    v = jnp.repeat(v, heads // kv_heads, axis=1)
    rows = math.gcd(T, ROW_BLOCK)

    @jax.checkpoint  # a backward pass makes a block's scores again
    def some_rows(first):
        qb = jax.lax.dynamic_slice_in_dim(q, first, rows)
        s = einsum("qhk,thk->hqt", qb, k) / jnp.sqrt(jnp.float32(hd))
        seen = sees(
            (first + jnp.arange(rows))[:, None], jnp.arange(T)[None, :], L,
            block,
        )
        # added, not selected: a backward pass then keeps no mask a block
        s = s + jnp.where(seen, 0.0, -jnp.inf)[None]
        return einsum("hqt,thk->qhk", jax.nn.softmax(s, axis=-1), v)

    o = jax.lax.map(some_rows, jnp.arange(0, T, rows)).reshape(T, heads * hd)
    return matmul(o, w["wo"].reshape(heads * hd, d))


def _experts(e, moe, top_k, offset):
    """e: [N, d] -> ([N, d], balance loss, z loss) of one expert layer."""
    N = e.shape[0]
    E = moe.gate.shape[1]
    held = moe.w_up.shape[0]
    logits = matmul(e, moe.gate)
    r = jax.nn.softmax(logits, axis=-1)
    vals, idx = jax.lax.top_k(r, top_k)
    vals = vals / jnp.sum(vals, -1, keepdims=True)
    chosen = jax.nn.one_hot(idx, E, dtype=jnp.float32)  # [N, k, E]
    weight = jnp.sum(chosen * vals[..., None], axis=1)  # [N, E]

    @jax.checkpoint  # a backward pass makes an expert's output again
    def one_expert(weight_e, w_gate, w_up, w_down):
        y = matmul(jax.nn.silu(matmul(e, w_gate)) * matmul(e, w_up), w_down)
        return weight_e[:, None] * y

    out = jnp.zeros_like(e)
    for n in range(held):
        out = out + one_expert(
            weight[:, offset + n], moe.w_gate[n], moe.w_up[n], moe.w_down[n]
        )
    share = jnp.sum(chosen, axis=(0, 1)) / (top_k * N)
    balance = E * jnp.sum(share * jnp.mean(r, axis=0))
    z = jnp.mean(jax.scipy.special.logsumexp(logits, axis=-1) ** 2)
    return out, balance, z


def loss(params, tokens, targets, *, block=BLOCK, t_min=T_MIN,
         noise_seed=NOISE_SEED, mask_id=None, top_k=TOP_K, eps=EPS,
         rope_theta=ROPE_THETA, balance_weight=BALANCE_WEIGHT,
         z_weight=Z_WEIGHT, experts_offset=0):
    """The block-diffusion training loss of rows ``tokens`` [B, L] + the
    weighted router losses, float32 throughout; ``targets`` is not read.
    The defaults are the ``sdar-30b-a3b-d8`` configuration's; ``mask_id``
    None is the table's last row."""
    del targets
    with jax.default_matmul_precision("highest"):
        p = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
        B, L = tokens.shape
        table = p["embed"]["tokens"]
        d = table.shape[1]
        if mask_id is None:
            mask_id = table.shape[0] - 1
        masked, t = noise(tokens, block=block, t_min=t_min, seed=noise_seed)
        x_t = jnp.where(masked, mask_id, tokens)
        x = table[jnp.concatenate([x_t, tokens], axis=1)]  # [B, 2L, d]
        aux = jnp.float32(0.0)

        def one_layer(x, layer):
            a = _rms_norm(x, layer["norm"]["scale"], eps)
            if "attn" in layer:
                y = jax.lax.map(
                    lambda row: _attention(row, layer, eps, rope_theta, block),
                    a,
                )
                return x + y, jnp.float32(0.0)
            y, balance, z = _experts(
                a.reshape(B * 2 * L, d), layer["moe"], top_k, experts_offset
            )
            return (
                x + y.reshape(x.shape),
                balance_weight * balance + z_weight * z,
            )

        for layer in p["layers"]:
            # a backward pass makes each layer again
            x, router = jax.checkpoint(one_layer)(x, layer)
            aux = aux + router
        h = _rms_norm(x[:, :L], p["final_norm"]["scale"], eps)

        @jax.checkpoint  # a backward pass makes a block's logits again
        def some_rows(rows):
            hb, picked = rows
            z = matmul(hb, p["lm_head"])
            lse = jax.scipy.special.logsumexp(z, axis=-1)
            return lse - jnp.take_along_axis(z, picked[..., None], -1)[..., 0]

        rows = math.gcd(B * L, ROW_BLOCK)
        nll = jax.lax.map(some_rows, (
            h.reshape(-1, rows, d), tokens.reshape(-1, rows)
        )).reshape(B, L)
        weight = jnp.where(masked, 1.0 / t, 0.0)
        return jnp.mean(weight * nll) + aux
