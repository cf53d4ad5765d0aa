"""The Gated DeltaNet layer (arXiv:2412.06464, as ``qwen3_next`` runs it)
in plain ``jax.numpy``: in-projections, causal depthwise convolution, the
gated delta rule computed in chunks, gated per-head RMSNorm, out-projection.

The recurrence, for one value head with state ``S [d_k, d_v]`` from 0, decay
``alpha_t = exp(g_t)``, ``g_t = -exp(A_log) * softplus(a_t + dt_bias)``,
write strength ``beta_t = gdn_beta_scale * sigmoid(b_t)`` (the scale 1, or
up to 2: ``olmo_hybrid``'s ``linear_allow_neg_eigval``), unit-length
``k_t`` and ``q_t`` (``q`` over ``sqrt(d_k)`` besides), ``d_k`` and ``d_v``
each the model's own (128 / 128, 96 / 192):

    S <- alpha_t S
    S <- S + k_t (outer) (beta_t (v_t - S^T k_t))
    o_t = S^T q_t

so the transition ``alpha_t (I - beta_t k_t k_t^T)`` is a matrix (its
eigenvalue along ``k_t`` is ``alpha_t (1 - beta_t)``: negative where the
write strength passes 1), and the
pass over chunk states has no closed form in scalar decays as Mamba-2's
has (``ops/mamba2.py``). ``gated_delta_chunked`` computes it in chunks of
``chunk`` steps (the WY / UT transform). With ``gamma_i`` the running sum
of ``g`` inside a chunk:

    A  = -tril_{-1}((K_beta K^T) * exp(gamma_i - gamma_j))
    T  = (I - A)^{-1}                      unit lower triangular
    U  = T V_beta        W = T (K_beta * exp(gamma))

then over the chunks IN ORDER, from ``S = 0`` (``chunk_state_pass``):

    V' = U - W S
    S <- exp(gamma_C) S + (K * exp(gamma_C - gamma))^T V'

and every position reads the state that entered its chunk and the chunk's
own ``V'``: ``O = (Q * exp(gamma)) S + tril((Q K^T) * exp(gamma_i -
gamma_j)) V'``. Decays, their sums, ``T`` and the carried state are
float32; matmul operands are the activation dtype, accumulated in float32.
No decay is ever divided by: every exponent above is <= 0.

The pass is the layer's serial depth: ``T / chunk`` steps forward and as
many backward, each two small matmuls a head; ``U``, ``W`` and the read-out
do not depend on the carried state and are batched over all chunks outside
it. It is differentiated by hand (``jax.grad`` of the forward loop carries
all of a step's matmuls through the reversed loop and keeps the float32
state of every chunk). It is executed one of two ways, chosen from its own
arguments by the rule of the chunk-local work around it
(``gated_delta_kernels.fits``): as two kernels that keep a head's state in
VMEM across a layer's chunks and make the cotangents of ``U``, ``W``, ``K``
and the decays in the reversed step that holds their operands
(``gated_delta_kernels.state_pass`` / ``state_pass_rev``), or, at every
other shape, as a ``lax.scan`` each way with those cotangents as einsums
over all chunks after the reversed one (``_pass_scan`` / ``_pass_scan_bwd``:
the statement the kernels are held to).
``common/trace_counts`` holds the passes traced (``gdn_sites``, one a
mixer of a program), their sequential steps, a backward pass counted with
its forward (``gdn_chunk_steps``: the chunk states walked in order, whoever
walks them), the sites whose chunk-local work went into the kernels
(``gdn_kernel_sites``) and those whose pass did (``gdn_pass_kernel_sites``).

**What a recomputed layer keeps.** The pass's forward rule names what it
hands its backward rule (``W``, the keys as the pass took them, ``delta``,
``a``, and its two results ``V'`` and the entered states, which the
read-out reads too), ``_delta_rule`` names the rule's ``o``, and the mixer
the projection's ``[q | k | v]``, which its convolution reads
(``jax.ad_checkpoint.checkpoint_name``, ``KEPT``). Outside a
``jax.checkpoint`` whose policy saves those names a name is an identity and
lowers to nothing. Inside one (``models/transformer.recomputed``) nothing
the backward pass reads depends on a second ``wy``, a second pass or a
second read-out any more: it makes the norm, the small projections, the
convolution, the unit-length q and k, the decays and the gate again from
the layer's input and the kept arrays, and runs the backward kernels and
the reversed pass on what the first forward left (``gdn_kept_sites``;
``gdn_chunk_steps`` then holds one forward pass a site and not the two the
layer is traced as: the count of a differentiated step, since the flag it
asks says where the site was traced and not whether it is differentiated).
What is named is also what the step's non-donating twin, the one that runs
while a flash save is staged, must hold beside two copies of the state:
the convolution's result has no name for that (``PERF.md`` PR 56). For
either kind of decay and either way to execute the chunk-local work: the
names sit where both meet.

``cfg.gdn_decay`` "channel" is Kimi Delta Attention's rule (arXiv:
2510.26692): the decay is a vector over the key's channels, the transition
``(I - beta_t k_t k_t^T) Diag(alpha_t)``, and ``gamma`` a row ``[C, d_k]``
a chunk. The decay then no longer factors out of the products over the
key's channels, ``A_ij = sum_c kbeta_ic k_jc exp(gamma_ic - gamma_jc)``:
``_decayed_scores`` forms such a masked square in row blocks of
``SUB_BLOCK`` steps, each a matmul of two factors taken at the block's first
row, ``exp(gamma_i - gamma_b)`` <= 1 on the rows and ``exp(gamma_b -
gamma_j)`` on the columns: <= 1 before the block and, on the block's own
diagonal square, a division by at most ``SUB_BLOCK - 1`` steps' decay. A
step's log-decay is bounded below (``cfg.gdn_decay_bound`` >= -5), so that
quotient stays under ``exp(75)`` in float32. The pass carries ``a`` as a
row over the key's channels and takes the keys already decayed to the
chunk's end; everything else (the pass and its hand-written reversal, the
triangle's inverse's cotangent, the counts) is the one code for both kinds.
Where the shapes allow (``gated_delta_kernels.fits``, the one rule for both
kinds) the kind's chunk-local work runs in kernels of its own, the
``gdn_channel_*`` beside the scalar kind's ``gdn_chunk_*``; ``_wy_channel``
and ``_read_out_channel`` are the statement those are held to and what runs
at every other shape. Such a layer's decays start slow, and where a chunk's
keys are nearly parallel and hardly decay ``unit_lower_inverse``'s product
form loses the inverse to cancellation: this kind inverts by halves
(``unit_lower_inverse_blocked``), in the kernels too. So does the scalar
kind where the write strength is scaled past 1 (``halves``): ``A``'s
entries double with beta and the product form's powers with them, and on
keys that share a direction without decay it is off by 1e-2 at a mean
``k_i . k_j`` of 0.2 where beta under 1 leaves 1e-5, and by 1e4 at 0.5
(PERF.md, Findings PR 64); by halves the error stays under 1e-5.

Which shapes run in the kernels is said in ONE place,
``gated_delta_kernels.fits``: heads of whole 128-lane tiles token-major
as the arrays lie, any other head of whole quarter tiles (96 / 192)
head-major at its stated width; this module only lays the arrays out as
that rule says (``gated_delta_chunked``); a site's count says what its blocks
hold against what the model states (``gdn_head_lanes``,
``gdn_head_lanes_used``, counted with the kernels' site;
``gdn_beta_scaled_sites`` a scaled site).

The spans of a layer: ``scope/layer/gdn/{in_proj,conv,scan,gate,out_proj}``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from dlrover_tpu.common import trace_counts
from dlrover_tpu.ops import gated_delta_kernels as kernels
from dlrover_tpu.ops.mamba2 import (
    conv_silu, gated_group_rmsnorm, gated_norm,
)

L2_EPS = 1e-6  # of the unit-length q and k (the source's ``l2norm``)
SUB_BLOCK = kernels.SUB_BLOCK  # the one bound of both ways to execute
DT_SHARE = (0.002, 0.2)  # of ``sigmoid(dt_bias)`` at init, vector decay

# what a ``jax.checkpoint`` around a layer saves of a delta-rule mixer when
# its policy holds these names (``models/transformer.recomputed``; the
# module's docstring): what the pass's forward rule hands its backward rule
# (``W``, the keys, ``delta``, ``a``, and its results ``V'`` and the entered
# states), the rule's result ``o`` ...
_PASS_KEPT = (
    "gdn_pass_w", "gdn_pass_k", "gdn_pass_delta", "gdn_pass_a",
    "gdn_pass_vn", "gdn_pass_s_in",
)
_RULE_KEPT = "gdn_rule_o"
# ... and of the stretch before the rule, named at the mixer's own call:
# the projection's ``[q | k | v]``, what the convolution reads (its result
# is made again from it: that array kept as well bought 1.6 ms of a 603 ms
# step for 1.13 GiB, ``PERF.md`` PR 56)
_IN_KEPT = "gdn_in_qkv"
KEPT = _PASS_KEPT + (_RULE_KEPT, _IN_KEPT)


def init_gated_delta_params(key, cfg, dtype):
    """One layer's parameters. The source's ``in_proj_qkvz`` is stored as
    its column blocks ``[q | k | v]`` (``w_qkv``: the channels the
    convolution runs over) and ``z`` (``w_z``), ``in_proj_ba`` as ``[b |
    a]`` (``w_ba``); ``A_log = log U(0, 16]``, ``dt_bias = 1``, the gated
    norm's weight 1, the convolution without bias."""
    d, Hv = cfg.model_dim, cfg.gdn_value_heads
    key_w = cfg.gdn_key_heads * cfg.gdn_key_dim
    val_w = Hv * cfg.gdn_value_dim
    # the output gate's projection: a channel, or one a head
    gate_w = Hv if cfg.gdn_gate == "head_sigmoid" else val_w
    kq, kz, kb, kc, ka, ko = jax.random.split(key, 6)

    def dense(k, shape, fan_in):
        return (jax.random.normal(k, shape) * fan_in**-0.5).astype(dtype)

    p = {
        "w_qkv": dense(kq, (d, 2 * key_w + val_w), d),
        "w_z": dense(kz, (d, gate_w), d),
        "w_ba": dense(kb, (d, 2 * Hv), d),
        "conv_w": dense(kc, (cfg.gdn_conv, 2 * key_w + val_w), cfg.gdn_conv),
        "dt_bias": jnp.ones((Hv,), dtype),
        "A_log": jnp.log(
            16.0 * (1.0 - jax.random.uniform(ka, (Hv,)))
        ).astype(dtype),
        "norm": jnp.ones((cfg.gdn_value_dim,), dtype),
        "w_out": dense(ko, (val_w, d), val_w),
    }
    if cfg.gdn_decay == "channel":
        # the write strength's projection alone (``w_b``), the decay's at
        # full rank (``w_f``) with its bias a channel, ``A_log`` from 0
        del p["w_ba"]
        kb, kf = jax.random.split(kb)
        p["w_b"] = dense(kb, (d, Hv), d)
        p["w_f"] = dense(kf, (d, key_w), d)
        p["A_log"] = jnp.zeros((Hv,), dtype)
        # sigmoid(dt_bias), the share of the bound a step decays by where
        # the projection reads 0, log-uniform over DT_SHARE
        lo, hi = DT_SHARE
        share = jnp.exp(
            jax.random.uniform(ka, (key_w,)) * jnp.log(hi / lo) + jnp.log(lo)
        )
        p["dt_bias"] = jnp.log(share / (1.0 - share)).astype(dtype)
    return p


def gated_delta_logical_axes(cfg):
    axes = {
        "w_qkv": ("embed", None),
        "w_z": ("embed", None),
        "w_ba": ("embed", None),
        "conv_w": (None, None),
        "dt_bias": (None,),
        "A_log": (None,),
        "norm": (None,),
        "w_out": (None, "embed"),
    }
    if cfg.gdn_decay == "channel":
        del axes["w_ba"]
        axes.update(w_b=("embed", None), w_f=("embed", None))
    return axes


def l2norm(x):
    """``x / |x|`` over the last axis, float32 (``rsqrt(sum x^2 + eps)``)."""
    xf = x.astype(jnp.float32)
    return xf * lax.rsqrt(jnp.sum(xf * xf, -1, keepdims=True) + L2_EPS)


def head_gated_rmsnorm(o, z, weight, eps: float):
    """``weight * RMSNorm(o) * sigmoid(z)`` a head: o [B, T, H * d_v], one
    gate a head z [B, T, H], the norm over each head's own d_v with the
    one weight [d_v]. float32."""
    B, T, H = z.shape
    of = o.astype(jnp.float32).reshape(B, T, H, -1)
    of = of * lax.rsqrt(jnp.mean(of * of, -1, keepdims=True) + eps)
    gate = jax.nn.sigmoid(z.astype(jnp.float32))[..., None]
    return (of * weight.astype(jnp.float32) * gate).reshape(o.shape)


@jax.custom_vjp
def unit_lower_inverse(A):
    """``(I - A)^{-1}`` for strictly lower triangular ``A [..., C, C]``
    (float32): ``A`` is nilpotent, so the inverse is the finite product
    ``(I + A)(I + A^2)(I + A^4) ...`` up to ``A^(C-1)``, ``ceil(log2 C)``
    factors, all matmuls. Its cotangent is ``T^T dT T^T``."""
    C = A.shape[-1]
    hi = lax.Precision.HIGHEST
    T = A + jnp.eye(C, dtype=A.dtype)
    power, reach = A, 2  # T holds the powers below ``reach``
    while reach < C:
        power = jnp.matmul(power, power, precision=hi)
        T = T + jnp.matmul(T, power, precision=hi)
        reach *= 2
    return T


def _unit_lower_inverse_fwd(A):
    T = unit_lower_inverse(A)
    return T, T


def _unit_lower_inverse_bwd(T, dT):
    hi = lax.Precision.HIGHEST
    Tt = jnp.swapaxes(T, -1, -2)
    return (jnp.matmul(jnp.matmul(Tt, dT, precision=hi), Tt, precision=hi),)


unit_lower_inverse.defvjp(_unit_lower_inverse_fwd, _unit_lower_inverse_bwd)


_INVERSE_BASE = kernels.INVERSE_BASE


def _blocked_inverse(A):
    C = A.shape[-1]
    lead = A.shape[:-2]
    hi = lax.Precision.HIGHEST
    size = 1 << max(C - 1, 0).bit_length()  # whole halvings
    if size != C:
        A = jnp.pad(A, [(0, 0)] * len(lead) + [(0, size - C)] * 2)
    s = min(_INVERSE_BASE, size)
    n = size // s
    T = unit_lower_inverse(
        jnp.einsum("...iaib->...iab", A.reshape(*lead, n, s, n, s))
    )  # [..., n, s, s]: the diagonal blocks' inverses
    while s < size:
        n = size // (2 * s)
        low = A.reshape(*lead, n, 2, s, n, 2, s)[..., :, 1, :, :, 0, :]
        A21 = jnp.einsum("...iaib->...iab", low)
        T11, T22 = T[..., 0::2, :, :], T[..., 1::2, :, :]
        T21 = jnp.matmul(
            jnp.matmul(T22, A21, precision=hi), T11, precision=hi
        )
        T = jnp.concatenate([
            jnp.concatenate([T11, jnp.zeros_like(T11)], axis=-1),
            jnp.concatenate([T21, T22], axis=-1),
        ], axis=-2)
        s *= 2
    return T[..., 0, :C, :C]


@jax.custom_vjp
def unit_lower_inverse_blocked(A):
    """``unit_lower_inverse`` by halves: with ``I - A = [[L11, 0], [-A21,
    L22]]`` the inverse is ``[[T11, 0], [T22 A21 T11, T22]]``, from
    diagonal blocks of ``_INVERSE_BASE`` up, a level two matmuls over all
    its blocks at once. Every intermediate is a block of the inverse
    itself, so it is as well conditioned as the inverse is. The product
    form's powers ``A^2, A^4, ... A^32`` of a 64-step chunk reach 1e17
    where the chunk's keys are nearly parallel and hardly decay, and
    cancel to a result of order 1 that float32 cannot hold: the first cell
    whose decays start slow read NaN from step 42 on (PERF.md, Findings PR
    45). Inside a block of 8 the powers stay under 35 and the product form
    serves."""
    return _blocked_inverse(A)


def _unit_lower_inverse_blocked_fwd(A):
    T = _blocked_inverse(A)
    return T, T


unit_lower_inverse_blocked.defvjp(
    _unit_lower_inverse_blocked_fwd, _unit_lower_inverse_bwd
)


def _decay_state(a, S):
    """``a`` times the state [..., d_k, d_v]: a scalar a head, or a row over
    the key's channels (one axis more)."""
    return (a[..., None] if a.ndim == S.ndim - 1 else a[..., None, None]) * S


def _decay_rows(delta, V):
    """``delta`` [..., C] times the rows of ``V`` [..., C, d_v]; ``V`` as
    it is where the keys carry the decay (``delta`` None)."""
    return V if delta is None else delta[..., None] * V


def _pass_in_kernels(U, W, delta) -> bool:
    """Which way the pass is executed, by the one rule of the chunk-local
    work around it (``gated_delta_kernels.fits``), read from the pass's own
    arguments: a sequence is the chunks it came in."""
    n, C, d_k = W.shape[0], W.shape[-2], W.shape[-1]
    return kernels.fits(d_k, U.shape[-1], C, n * C, W.dtype, delta is None)


def _pass_forward(U, W, K, delta, a):
    """The pass's forward, counted where both ways to execute it pass."""
    # the primal trace of a recomputed layer that keeps ``KEPT``: its
    # backward pass reads what the forward rule left and runs no forward
    # pass again, so the steps that run are those of the rule's own trace
    # (made when the layer is differentiated, and not inside ``keeping``)
    kept = trace_counts.keeping()
    trace_counts.count("gdn_sites")
    trace_counts.count("gdn_kept_sites", kept)
    trace_counts.count("gdn_chunk_steps", 0 if kept else U.shape[0])
    if _pass_in_kernels(U, W, delta):
        trace_counts.count("gdn_pass_kernel_sites")
        return tuple(kernels.state_pass(U, W, K, delta, a))
    return _pass_scan(U, W, K, delta, a)


def _pass_scan(U, W, K, delta, a):
    """The plain statement: a ``lax.scan`` over the chunks with the float32
    state its carry. What runs at every shape ``kernels.fits`` refuses, and
    what the pass's kernels are held to."""
    f32, act = jnp.float32, W.dtype

    def step(S, x):
        U, W, K, delta, a = x
        Sb = S.astype(act)
        Vn = U - jnp.einsum(
            "bgrid,bgrdv->bgriv", W, Sb, preferred_element_type=f32
        )
        S = _decay_state(a, S) + jnp.einsum(
            "bgjd,bgrjv->bgrdv", K, _decay_rows(delta, Vn).astype(act),
            preferred_element_type=f32,
        )
        return S, (Vn.astype(act), Sb)

    _, b, g, r, _, dv = U.shape
    S0 = jnp.zeros((b, g, r, K.shape[-1], dv), f32)
    _, out = lax.scan(step, S0, (U, W, K, delta, a))
    return out


@jax.custom_vjp
def chunk_state_pass(U, W, K, delta, a):
    """The serial pass over the chunk states, chunk axis first: ``U``
    [n, b, g, r, C, d_v] float32, ``W`` [n, b, g, r, C, d_k] and ``K``
    [n, b, g, C, d_k] in the activation dtype (a key head ``g`` serves its
    ``r`` value heads), ``delta = exp(gamma_C - gamma)`` [n, b, g, r, C]
    and ``a = exp(gamma_C)`` [n, b, g, r] float32. Where the decay is a
    vector over the key's channels, ``a`` is [n, b, g, r, d_k], ``K``
    comes already times ``exp(gamma_C - gamma)`` and ``delta`` is None.
    Returns ``V' = U - W S`` [n, b, g, r, C, d_v] and the state that
    ENTERED each chunk [n, b, g, r, d_k, d_v], both in the activation
    dtype (what the read-out's matmuls take)."""
    return _pass_forward(U, W, K, delta, a)


def _chunk_state_pass_fwd(U, W, K, delta, a):
    Vn, S_in = _pass_forward(U, W, K, delta, a)
    # one copy of each: the named ``V'`` and states are the results too
    # (a ``delta`` of None, the vector kind's, is an empty tree to a name)
    res = tuple(map(checkpoint_name, (W, K, delta, a, Vn, S_in), _PASS_KEPT))
    return res[4:], res


def _chunk_state_pass_bwd(res, cts):
    """The reversed pass, counted where both ways to execute it pass."""
    W, K, delta, a, Vn, S_in = res
    trace_counts.count("gdn_chunk_steps", W.shape[0])
    if _pass_in_kernels(Vn, W, delta):
        return kernels.state_pass_rev(*res, *cts)
    return _pass_scan_bwd(res, cts)


def _pass_scan_bwd(res, cts):
    """The plain statement of the reversed pass. It carries the cotangent
    of the state alone: a step is ``dV'`` of the state's update (one
    matmul) and the state's own cotangent (one more). What the steps leave
    behind (the cotangent of every chunk's leaving state, of its decayed
    ``V'``) gives the cotangents of ``W``, ``K`` and the decays in matmuls
    over all chunks at once, after the loop."""
    W, K, delta, a, Vn, S_in = res
    dVn, dS_in = cts
    f32, act = jnp.float32, W.dtype

    def step(dS, x):  # dS: of the state that LEFT this chunk
        W, K, delta, a, dVn, dS_in = x
        dSb = dS.astype(act)
        dVd = jnp.einsum(
            "bgjd,bgrdv->bgrjv", K, dSb, preferred_element_type=f32
        )
        dV = dVn.astype(f32) + _decay_rows(delta, dVd)
        dS = (
            _decay_state(a, dS) + dS_in.astype(f32)
            - jnp.einsum(
                "bgrid,bgriv->bgrdv", W, dV.astype(act),
                preferred_element_type=f32,
            )
        )
        return dS, (dVd, dSb)

    _, (dVd, dS_out) = lax.scan(
        step, jnp.zeros(S_in.shape[1:], f32),
        (W, K, delta, a, dVn, dS_in), reverse=True,
    )
    Vf = Vn.astype(f32)
    dU = dVn.astype(f32) + _decay_rows(delta, dVd)
    dW = -jnp.einsum(
        "nbgriv,nbgrdv->nbgrid", dU.astype(act), S_in,
        preferred_element_type=f32,
    ).astype(act)
    dK = jnp.einsum(
        "nbgrjv,nbgrdv->nbgjd", _decay_rows(delta, Vf).astype(act), dS_out,
        preferred_element_type=f32,
    ).astype(act)
    ddelta = None if delta is None else jnp.sum(dVd * Vf, axis=-1)
    da = jnp.einsum(
        "nbgrdv,nbgrdv->nbgrd" if a.ndim == 5 else "nbgrdv,nbgrdv->nbgr",
        dS_out, S_in, preferred_element_type=f32,
    )
    return dU, dW, dK, ddelta, da


chunk_state_pass.defvjp(_chunk_state_pass_fwd, _chunk_state_pass_bwd)


def _chunks(x, nc, C):
    """[B, T, heads, w] -> [nc, B, heads, C, w]: chunk axis first, heads
    before the positions of a chunk."""
    B, _, H, w = x.shape
    return jnp.transpose(x.reshape(B, nc, C, H, w), (1, 0, 3, 2, 4))


def _sum_over(g, picks):
    """``sum_j g[..., j] * picks[j, i]``, float32 at full precision."""
    return jnp.einsum(
        "nbgrj,ji->nbgri", g, picks, precision=lax.Precision.HIGHEST
    )


def _decays(g, diagonal: bool):
    """``gamma``, the running sum of ``g`` [..., C] inside its chunk, and
    the [..., C, C] square ``exp(gamma_i - gamma_j)`` below the diagonal
    (with it where ``diagonal``), 0 elsewhere; float32. The diagonal's
    ``exp(0)`` is a constant 1 and not ``exp`` of a difference: as a
    difference its cotangent reaches ``g`` as two reductions that must
    cancel to the last bit, and a head that decays fast multiplies what
    is left by its ``|g|`` (PERF.md, Findings PR 43)."""
    C = g.shape[-1]
    gamma = _sum_over(g, jnp.triu(jnp.ones((C, C), jnp.float32)))
    below = jnp.tril(jnp.ones((C, C), bool), -1)
    decay = jnp.exp(jnp.where(
        below, gamma[..., :, None] - gamma[..., None, :], -jnp.inf
    ))
    if diagonal:
        decay = decay + jnp.eye(C, dtype=jnp.float32)
    return gamma, decay


def _wy(k, v, beta, g, halves: bool = False):
    """What a chunk computes before the pass, for all chunks at once: from
    k [n, b, g, C, d_k], v [n, b, g, r, C, d_v] and beta, g
    [n, b, g, r, C] the pass's ``U, W, delta, a``; the triangle's inverse
    by halves where ``halves``."""
    f32, act = jnp.float32, k.dtype
    gamma, decay = _decays(g, diagonal=False)
    kk = jnp.einsum("nbgid,nbgjd->nbgij", k, k, preferred_element_type=f32)
    T = (unit_lower_inverse_blocked if halves else unit_lower_inverse)(
        -(beta[..., :, None] * kk[:, :, :, None] * decay)
    )
    U = jnp.einsum(
        "nbgrij,nbgrjv->nbgriv", T.astype(act),
        (v.astype(f32) * beta[..., None]).astype(act),
        preferred_element_type=f32,
    )
    W = jnp.einsum(
        "nbgrij,nbgjd->nbgrid",
        (T * (beta * jnp.exp(gamma))[..., None, :]).astype(act), k,
        preferred_element_type=f32,
    ).astype(act)
    # what is left of the chunk after each position, summed as such and
    # not as ``gamma_C - gamma`` (the last position's is 0 by construction)
    C = g.shape[-1]
    left = _sum_over(g, jnp.tril(jnp.ones((C, C), jnp.float32), -1))
    return U, W, jnp.exp(left), jnp.exp(gamma[..., -1])


def _read_out(q, k, g, Vn, S_in):
    """What every position reads: the state that entered its chunk and
    the chunk's own ``V'`` up to itself. -> [n, b, g, r, C, d_v] float32."""
    f32, act = jnp.float32, k.dtype
    gamma, decay = _decays(g, diagonal=True)
    qk = jnp.einsum("nbgid,nbgjd->nbgij", q, k, preferred_element_type=f32)
    own = jnp.einsum(
        "nbgrij,nbgrjv->nbgriv", (qk[:, :, :, None] * decay).astype(act),
        Vn, preferred_element_type=f32,
    )
    entered = jnp.einsum(
        "nbgid,nbgrdv->nbgriv", q, S_in, preferred_element_type=f32
    )
    return own + entered * jnp.exp(gamma)[..., None]


def _sum_rows(g, picks):
    """``sum_j g[..., j, :] * picks[j, i]`` for rows of channels
    [..., C, d_k], float32 at full precision."""
    return jnp.einsum(
        "...jd,ji->...id", g, picks, precision=lax.Precision.HIGHEST
    )


def _decayed_scores(x, k, gamma):
    """``sum_c x_ic k_jc exp(gamma_ic - gamma_jc)`` for j < i and 0
    elsewhere, [..., C, C] float32, from x, k and gamma [..., C, d_k]: the
    masked square of a decay that is a vector over the key's channels.
    Row block ``a`` of ``SUB_BLOCK`` steps is one matmul of ``x *
    exp(gamma_i - gamma_a)`` with ``k * exp(gamma_a - gamma_j)``,
    ``gamma_a`` the block's first row: every exponent is <= 0 but on the
    block's own columns, where it is at most ``SUB_BLOCK - 1`` steps'
    log-decay (the module's docstring); columns past the block are 0. The
    result does not depend on ``gamma_a``, so it takes no cotangent: what
    it would get is two sums that cancel."""
    f32, act = jnp.float32, k.dtype
    *lead, C, dk = gamma.shape
    sub = SUB_BLOCK if C % SUB_BLOCK == 0 else C
    nb = C // sub
    first = lax.stop_gradient(gamma[..., ::sub, :])[..., None, :]
    rows = jnp.exp(gamma.reshape(*lead, nb, sub, dk) - first)
    upto = jnp.arange(C) < (jnp.arange(nb)[:, None] + 1) * sub  # [nb, C]
    cols = jnp.exp(jnp.where(
        upto[..., None], first - gamma[..., None, :, :], -jnp.inf
    ))
    xr = (x.astype(f32).reshape(*lead, nb, sub, dk) * rows).astype(act)
    kc = (k.astype(f32)[..., None, :, :] * cols).astype(act)
    s = jnp.einsum(
        "...aid,...ajd->...aij", xr, kc, preferred_element_type=f32
    ).reshape(*lead, C, C)
    return jnp.where(jnp.tril(jnp.ones((C, C), bool), -1), s, 0.0)


def _wy_channel(k, v, beta, g):
    """``_wy`` where the decay is a vector over the key's channels: from k
    [n, b, h, C, d_k], v [n, b, h, C, d_v], beta [n, b, h, C] and g
    [n, b, h, C, d_k] the pass's ``U, W``, the keys decayed to the chunk's
    end ``K * exp(gamma_C - gamma)`` and ``a = exp(gamma_C)`` [n, b, h,
    d_k]."""
    f32, act = jnp.float32, k.dtype
    C = g.shape[-2]
    gamma = _sum_rows(g, jnp.triu(jnp.ones((C, C), f32)))
    kb = k.astype(f32) * beta[..., None]
    T = unit_lower_inverse_blocked(
        -_decayed_scores(kb, k, gamma)
    ).astype(act)
    U = jnp.einsum(
        "nbhij,nbhjv->nbhiv", T,
        (v.astype(f32) * beta[..., None]).astype(act),
        preferred_element_type=f32,
    )
    W = jnp.einsum(
        "nbhij,nbhjd->nbhid", T, (kb * jnp.exp(gamma)).astype(act),
        preferred_element_type=f32,
    ).astype(act)
    # what is left of the chunk after each position, summed as such
    left = _sum_rows(g, jnp.tril(jnp.ones((C, C), f32), -1))
    K_left = (k.astype(f32) * jnp.exp(left)).astype(act)
    return U, W, K_left, jnp.exp(gamma[..., -1, :])


def _read_out_channel(q, k, g, Vn, S_in):
    """``_read_out`` for a vector decay -> [n, b, h, C, d_v] float32. A
    position's own step enters undecayed, as a constant 1 (``_decays``)."""
    f32, act = jnp.float32, k.dtype
    C = g.shape[-2]
    gamma = _sum_rows(g, jnp.triu(jnp.ones((C, C), f32)))
    own_step = jnp.sum(q.astype(f32) * k.astype(f32), -1)
    scores = _decayed_scores(q, k, gamma) + (
        own_step[..., None] * jnp.eye(C, dtype=f32)
    )
    own = jnp.einsum(
        "nbhij,nbhjv->nbhiv", scores.astype(act), Vn,
        preferred_element_type=f32,
    )
    entered = jnp.einsum(
        "nbhid,nbhdv->nbhiv", (q.astype(f32) * jnp.exp(gamma)).astype(act),
        S_in, preferred_element_type=f32,
    )
    return own + entered


def _chunked_channel(q, k, v, beta, g, chunk: int):
    """``gated_delta_chunked`` for g [B, T, H, d_k]: the plain statement,
    its two stretches made again in the backward pass as the scalar
    decay's are."""
    B, T, H, dk = q.shape
    nc = T // chunk
    qc, kc, vc = (_chunks(x, nc, chunk) for x in (q, k, v))
    beta = jnp.transpose(beta.reshape(B, nc, chunk, H), (1, 0, 3, 2))
    g = _chunks(g, nc, chunk)
    U, W, K_left, a = jax.checkpoint(_wy_channel)(kc, vc, beta, g)
    # the pass's layout: every key head serves its one value head
    Vn, S_in = chunk_state_pass(
        U[:, :, :, None], W[:, :, :, None], K_left, None, a[:, :, :, None]
    )
    o = jax.checkpoint(_read_out_channel)(
        qc, kc, g, Vn[:, :, :, 0], S_in[:, :, :, 0]
    )
    # [n, b, h, C, d_v] -> [b, (n, C), h, d_v]
    o = jnp.transpose(o, (1, 0, 3, 2, 4)).reshape(B, T, H, v.shape[3])
    return o.astype(k.dtype)


def gated_delta_chunked(q, k, v, beta, g, chunk: int, halves: bool = False):
    """The gated delta rule in chunks of ``chunk`` steps: q, k
    [B, T, H_k, d_k] (unit length, q over sqrt(d_k) besides) and v
    [B, T, H_v, d_v] in the activation dtype, beta and g [B, T, H_v]
    float32 (g <= 0) -> o [B, T, H_v, d_v], accumulated in float32 and
    rounded once to the activation dtype. Value head ``h`` reads key head
    ``h // (H_v // H_k)``. T must be whole chunks. A decay that is a
    vector over the key's channels comes as g [B, T, H_v, d_k], bounded
    below as the module's docstring says, with ``H_v == H_k``. ``halves``:
    the scalar kind's triangle inverted by halves too (a write strength
    past 1, the module's docstring).

    The two stretches around the pass are made again in the backward pass
    and not kept: their [C, C] squares a value head a chunk (decays, ``T``,
    masked scores) would be the layer's largest residuals. Where the
    shapes allow (``gated_delta_kernels.fits``) both stretches, forward
    and backward, are kernels that keep those squares in VMEM and read
    and write the layouts around them, for either kind of decay; ``_wy``
    and ``_read_out`` (``_wy_channel`` and ``_read_out_channel`` for a
    vector decay) are the statement they are held to, and what runs
    everywhere else."""
    B, T, Hk, dk = q.shape
    Hv, dv = v.shape[2], v.shape[3]
    if T % chunk:
        raise ValueError(f"sequence {T} is not whole chunks of {chunk}")
    if Hv % Hk:
        raise ValueError(f"{Hv} value heads do not share {Hk} key heads")
    nc, r = T // chunk, Hv // Hk
    if g.ndim == 4 and r != 1:
        raise ValueError(
            f"a decay a key channel needs as many key heads ({Hk}) as "
            f"value heads ({Hv})"
        )
    in_kernels = kernels.fits(dk, dv, chunk, T, k.dtype, g.ndim == 4)
    if g.ndim == 4:  # a site of the kernels is counted by ``wy_channel``
        if not in_kernels:
            return _chunked_channel(q, k, v, beta, g, chunk)
        q, k, g = (x.reshape(B, T, Hk * dk) for x in (q, k, g))
        U, W, K_left, a = kernels.wy_channel(
            k, v.reshape(B, T, Hv * dv), beta, g, Hk, chunk
        )
        Vn, S_in = chunk_state_pass(U, W, K_left, None, a)
        o = kernels.read_out_channel(q, k, g, Vn, S_in)
        return o.reshape(B, T, Hv, dv)

    def per_head(x):  # [B, T, H_v] -> [nc, B, H_k, r, C]
        return jnp.transpose(
            x.reshape(B, nc, chunk, Hk, r), (1, 0, 3, 4, 2)
        )

    beta, g = per_head(beta), per_head(g)
    if in_kernels:  # a site of the kernels is counted by ``wy``
        # token-major as they lie, or head-major at the stated widths
        # where a head is no whole tiles (``kernels.fits``); q, k, then
        # g, then v: the order the recorded steps were lowered in
        tiles = kernels.whole_tiles(dk, dv)
        if tiles:
            q, k = q.reshape(B, T, Hk * dk), k.reshape(B, T, Hk * dk)
        else:
            q, k = (jnp.transpose(x, (0, 2, 1, 3)) for x in (q, k))
        rows = (nc, B, Hk, 1, r * chunk)
        g = g.reshape(rows)
        if tiles:
            v = v.reshape(B, T, Hv * dv)
        else:
            v = jnp.transpose(v.reshape(B, T, Hk, r, dv), (0, 2, 3, 1, 4))
        U, W, kc, delta, a = kernels.wy(
            k, v, beta.reshape(rows), g, Hk, r, chunk, halves
        )
        Vn, S_in = chunk_state_pass(U, W, kc, delta, a)
        o = kernels.read_out(q, k, g, Vn, S_in)
        if o.ndim == 5:  # [b, g, r, T, d_v]
            o = jnp.transpose(o, (0, 3, 1, 2, 4))
        return o.reshape(B, T, Hv, dv)
    qc, kc = _chunks(q, nc, chunk), _chunks(k, nc, chunk)
    vc = _chunks(v, nc, chunk).reshape(nc, B, Hk, r, chunk, dv)
    U, W, delta, a = jax.checkpoint(_wy, static_argnums=(4,))(
        kc, vc, beta, g, halves
    )
    Vn, S_in = chunk_state_pass(U, W, kc, delta, a)
    o = jax.checkpoint(_read_out)(qc, kc, g, Vn, S_in)
    # [n, b, g, r, C, d_v] -> [b, (n, C), (g, r), d_v]
    o = jnp.transpose(o, (1, 0, 4, 2, 3, 5)).reshape(B, T, Hv, dv)
    return o.astype(k.dtype)


def _delta_rule(q, k, v, beta, g, chunk: int, mesh, halves: bool = False):
    """``gated_delta_chunked`` as the mixer calls it. Where the chunk-local
    work goes into kernels and GSPMD still owns a mesh axis, the call runs
    under ``shard_map``, batch over the data axes and heads over tp: for
    ``models/transformer._causal_attention``'s reason and by its rules
    (the rule is independent per example and key head; GSPMD refuses to
    partition a Mosaic kernel on its own; a batch or a head count that does
    not divide is an error on the TPU and stays with GSPMD elsewhere). The
    plain statement is left to GSPMD as it was."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    def rule(*a):
        return checkpoint_name(
            gated_delta_chunked(*a, chunk, halves), _RULE_KEPT
        )

    args = (q, k, v, beta, g)
    if not kernels.fits(
        q.shape[3], v.shape[3], chunk, q.shape[1], q.dtype, g.ndim == 4
    ):
        return rule(*args)

    def specs(batch, heads):
        wide, narrow = P(batch, None, heads, None), P(batch, None, heads)
        return dict(
            in_specs=(
                wide, wide, wide, narrow, wide if g.ndim == 4 else narrow
            ),
            out_specs=wide, check_vma=False,
        )

    if mesh is None:
        auto = jax.sharding.get_abstract_mesh().auto_axes
        if not auto or jax.typeof(q).vma:
            return rule(*args)
        rest = tuple(a for a in auto if a != "tp") or None
        return shard_map(
            rule, axis_names=frozenset(auto),
            **specs(rest, "tp" if "tp" in auto else None),
        )(*args)
    if mesh.size == 1:
        return rule(*args)
    data, tp = mesh.shape["dp"] * mesh.shape["fsdp"], mesh.shape["tp"]
    if q.shape[0] % data or q.shape[2] % tp:
        if jax.default_backend() == "tpu":
            raise ValueError(
                f"gated delta rule batch {q.shape[0]} and key heads "
                f"{q.shape[2]} do not divide dp*fsdp={data} and tp={tp} of "
                f"mesh {dict(mesh.shape)}: its Pallas kernels cannot be "
                "partitioned unevenly; pick a (micro)batch and a tp that "
                "divide"
            )
        return rule(*args)
    return shard_map(rule, mesh=mesh, **specs(("dp", "fsdp"), "tp"))(*args)


def gated_delta_mixer(u, p, cfg, eps: float, mesh=None):
    """u [B, T, d] (already normed) -> [B, T, d]. ``mesh``: the mesh the
    step is sharded over, or None inside a region that names its own axes
    (``_delta_rule``)."""
    Bsz, T, _ = u.shape
    Hv, Hk = cfg.gdn_value_heads, cfg.gdn_key_heads
    dk, dv = cfg.gdn_key_dim, cfg.gdn_value_dim
    key_w = Hk * dk
    act = u.dtype
    f32 = jnp.float32
    channel = cfg.gdn_decay == "channel"
    with jax.named_scope("scope/layer/gdn/in_proj"):
        qkv = checkpoint_name(u @ p["w_qkv"].astype(act), _IN_KEPT)
        z = u @ p["w_z"].astype(act)
        if channel:
            b = jnp.dot(u, p["w_b"].astype(act), preferred_element_type=f32)
            f = jnp.dot(u, p["w_f"].astype(act), preferred_element_type=f32)
        else:
            ba = jnp.dot(
                u, p["w_ba"].astype(act), preferred_element_type=jnp.float32
            )
    # the elementwise stretches compute in float32 and are made again in
    # the backward pass, as the Mamba-2 layer's (``ops/mamba2.py``)
    with jax.named_scope("scope/layer/gdn/conv"):
        qkv = conv_silu(qkv, p["conv_w"], mesh=mesh)
    with jax.named_scope("scope/layer/gdn/scan"):
        if channel:
            beta = jax.nn.sigmoid(b)
            g = cfg.gdn_decay_bound * jax.nn.sigmoid(
                jnp.exp(p["A_log"].astype(f32))[:, None] * (
                    f + p["dt_bias"].astype(f32)
                ).reshape(Bsz, T, Hk, dk)
            )
        else:
            beta = jax.nn.sigmoid(ba[..., :Hv])
            g = -jnp.exp(p["A_log"].astype(jnp.float32)) * jax.nn.softplus(
                ba[..., Hv:] + p["dt_bias"].astype(jnp.float32)
            )
        if cfg.gdn_beta_scale != 1.0:
            trace_counts.count("gdn_beta_scaled_sites")
            beta = cfg.gdn_beta_scale * beta
        q = qkv[..., :key_w].reshape(Bsz, T, Hk, dk)
        k = qkv[..., key_w:2 * key_w].reshape(Bsz, T, Hk, dk)
        v = qkv[..., 2 * key_w:].reshape(Bsz, T, Hv, dv)
        q, k = jax.checkpoint(lambda q, k: (
            (l2norm(q) * dk**-0.5).astype(act), l2norm(k).astype(act)
        ))(q, k)
        o = _delta_rule(
            q, k, v, beta, g, min(cfg.gdn_chunk, T), mesh,
            cfg.gdn_beta_scale > 1.0,
        )
        o = o.reshape(Bsz, T, Hv * dv)
    with jax.named_scope("scope/layer/gdn/gate"):
        if cfg.gdn_gate == "head_sigmoid":
            def statement(o, z, w):
                return head_gated_rmsnorm(o, z, w, eps).astype(act)
        else:
            def statement(o, z, w):
                return gated_group_rmsnorm(
                    o, z, jnp.tile(w, Hv), Hv, eps, norm_before_gate=True
                ).astype(act)
        o = gated_norm(statement, o, z, p["norm"], dv, eps, mesh=mesh)
    with jax.named_scope("scope/layer/gdn/out_proj"):
        return o @ p["w_out"].astype(act)
