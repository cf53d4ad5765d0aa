"""The Mamba-2 layer (state-space duality, arXiv:2405.21060) in plain
``jax.numpy``, differentiated by JAX: in-projection, causal depthwise
convolution, the selective state-space recurrence computed in chunks,
gated group RMSNorm, out-projection.

The recurrence, for one head with scalar decay ``a = -exp(A_log)``,
step ``dt_t = softplus(dt_t + dt_bias)``, state ``S [P, N]``:

    S_t = exp(dt_t * a) * S_{t-1} + dt_t * x_t (outer) B_t
    y_t = S_t C_t + D * x_t

``ssd_chunked`` computes it in chunks of ``chunk`` steps, all as matmuls
(the "SSD" form): inside a chunk ``y = (C B^T * L) (dt x)`` with
``L[i, j] = exp(sum_{j < s <= i} dt_s a)`` for ``i >= j``; each chunk's
own end state ``sum_j exp(.) dt_j x_j (outer) B_j``; one pass over the
chunk states carries them forward (a float32 matmul with the lower
triangle of the chunks' decays: the scan over nc states in closed form);
and each position reads the state that entered its chunk,
``exp(.) C_t S_in``. Decays, their cumulative sums and the pass over the
chunk states are float32; the matmul operands are
the activation dtype, accumulated in float32. The tests hold it to the
recurrence itself, one step at a time.

The convolution and its SiLU are ``conv_silu``, which three layers call:
this one, the Gated DeltaNet layer (``ops/gated_delta.py``) and the Mamba-1
layer (``ops/selective_scan.py``, with a bias): the plain statement here,
or the ``conv_silu_*`` kernels of ``ops/conv_kernels.py`` where their rule
takes the input. The gated norm after the scan is ``gated_norm`` in the
same way, for this layer and both gates of the Gated DeltaNet layer: the
plain statement (``gated_group_rmsnorm`` here, ``head_gated_rmsnorm``
there), or the ``gated_norm_*`` kernels of ``ops/gated_norm_kernels.py``.

The spans of a layer: ``scope/layer/ssm/{in_proj,conv,scan,gate,out_proj}``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from dlrover_tpu.common import trace_counts
from dlrover_tpu.ops import conv_kernels, gated_norm_kernels, ssd_kernels


def init_mamba2_params(key, cfg, dtype):
    """One layer's parameters. The in-projection is stored as its three
    column blocks ``[z | xBC | dt]`` (``w_z``, ``w_xbc``, ``w_dt``), each
    whole (8, 128) tiles wide where the concatenation is not; ``dt_bias``
    is the inverse softplus of a step drawn log-uniformly from
    ``[ssm_dt_min, ssm_dt_max]``, ``A_log = log U[1, 16]``, ``D = 1``; the
    out-projection is scaled by ``1 / sqrt(num_layers)``
    (``rescale_prenorm_residual``)."""
    d, d_in, H = cfg.model_dim, cfg.ssm_inner, cfg.ssm_heads
    conv_ch = d_in + 2 * cfg.ssm_groups * cfg.ssm_state
    kz, kx, kt, kc, kd, ka, ko = jax.random.split(key, 7)

    def dense(k, shape, fan_in):
        return (jax.random.normal(k, shape) * fan_in**-0.5).astype(dtype)

    dt = jnp.exp(
        jax.random.uniform(kd, (H,))
        * (math.log(cfg.ssm_dt_max) - math.log(cfg.ssm_dt_min))
        + math.log(cfg.ssm_dt_min)
    )
    dt = jnp.maximum(dt, cfg.ssm_dt_floor)
    return {
        "w_z": dense(kz, (d, d_in), d),
        "w_xbc": dense(kx, (d, conv_ch), d),
        "w_dt": dense(kt, (d, H), d),
        "conv_w": dense(kc, (cfg.ssm_conv, conv_ch), cfg.ssm_conv),
        "conv_b": jnp.zeros((conv_ch,), dtype),
        "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype),
        "A_log": jnp.log(
            jax.random.uniform(ka, (H,), minval=1.0, maxval=16.0)
        ).astype(dtype),
        "D": jnp.ones((H,), dtype),
        "norm": jnp.ones((d_in,), dtype),
        "w_out": (
            dense(ko, (d_in, d), d_in) / math.sqrt(cfg.num_layers)
        ).astype(dtype),
    }


def mamba2_logical_axes():
    return {
        "w_z": ("embed", "ssm_inner"),
        "w_xbc": ("embed", None),
        "w_dt": ("embed", None),
        "conv_w": (None, None),
        "conv_b": (None,),
        "dt_bias": (None,),
        "A_log": (None,),
        "D": (None,),
        "norm": ("norm",),
        "w_out": ("ssm_inner", "embed"),
    }


def causal_conv1d(x, w, b=None):
    """Depthwise causal convolution over time: ``y_t = b + sum_k w[k] *
    x_{t - (K-1) + k}`` with zeros before the row's start. x: [B, T, C],
    w: [K, C], b: [C] or None for no bias; float32 inside."""
    K, T = w.shape[0], x.shape[1]
    xf = x.astype(jnp.float32)
    wf = w.astype(jnp.float32)
    padded = jnp.pad(xf, ((0, 0), (K - 1, 0), (0, 0)))
    y = 0.0 if b is None else b.astype(jnp.float32)
    for k in range(K):
        y = y + padded[:, k:k + T, :] * wf[k]
    return y


def conv_silu(x, w, b=None, mesh=None):
    """``silu(causal_conv1d(x, w, b))`` rounded once to ``x``'s dtype, the
    stretch before a mixer's scan. Where ``conv_kernels.fits`` takes the
    input, forward and backward are the ``conv_silu_*`` kernels; everywhere
    else the plain statement, float32 inside and made again in the backward
    pass: what either keeps is its inputs in the activation dtype, and not
    a float32 copy of every channel of every token. ``mesh``: the mesh the
    step is sharded over, or None inside a region that names its own
    axes. A call is a site of ``common/trace_counts`` (``conv_sites``, and
    ``conv_kernel_sites`` where the kernels take it), both counted here, so
    a layer traced twice under ``jax.checkpoint`` counts twice in both."""
    in_kernels = conv_kernels.fits(x, w, mesh)
    trace_counts.count("conv_sites")
    trace_counts.count("conv_kernel_sites", in_kernels)
    if in_kernels:
        return conv_kernels.conv_silu(x, w, b)
    return jax.checkpoint(
        lambda x, w, b: jax.nn.silu(causal_conv1d(x, w, b)).astype(x.dtype)
    )(x, w, b)


def ssd_chunked(x, dt, a, Bm, Cm, chunk: int):
    """The recurrence in chunks of ``chunk`` steps, as matmuls: x
    [B, T, H, P], dt [B, T, H] (after softplus), a [H] (negative), Bm, Cm
    [B, T, G, N] -> y [B, T, H, P] in float32, without the ``D`` skip.
    ``x``, ``Bm``, ``Cm`` in the activation dtype, ``dt`` and ``a``
    float32. T must be whole chunks."""
    Bsz, T, H, P = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    if T % chunk:
        raise ValueError(f"sequence {T} is not whole chunks of {chunk}")
    nc, Q, rep = T // chunk, chunk, H // G
    f32, dt_act = jnp.float32, x.dtype

    # log-decay of every step and its running sum inside the chunk, head
    # major and time last ([.., H, Q] is whole (8, 128) tiles, and so are
    # the [Q, Q] decay squares; with the heads last every such array
    # would pad 8 lanes to 128)
    la = jnp.swapaxes((dt * a).reshape(Bsz, nc, Q, H), 2, 3)  # <= 0
    cum_h = jnp.einsum(
        "bchj,ji->bchi", la, jnp.triu(jnp.ones((Q, Q), f32)),
        precision=lax.Precision.HIGHEST,
    ).reshape(Bsz, nc, G, rep, Q)
    xdt = (x.astype(f32) * dt[..., None]).astype(dt_act)
    xdt = xdt.reshape(Bsz, nc, Q, G, rep, P)
    Bc = Bm.reshape(Bsz, nc, Q, G, N)
    Cc = Cm.reshape(Bsz, nc, Q, G, N)

    # inside the chunk: (C B^T * L) (dt x)
    cb = jnp.einsum(
        "bcign,bcjgn->bcgij", Cc, Bc, preferred_element_type=f32
    )
    diff = cum_h[..., :, None] - cum_h[..., None, :]  # [b, c, g, r, i, j]
    decay = jnp.exp(
        jnp.where(jnp.tril(jnp.ones((Q, Q), bool)), diff, -jnp.inf)
    )
    m = (decay * cb[:, :, :, None]).astype(dt_act)
    y = jnp.einsum(
        "bcgrij,bcjgrp->bcgrip", m, xdt, preferred_element_type=f32
    )

    # each chunk's own end state, then the states that enter each chunk
    to_end = jnp.exp(cum_h[..., -1:] - cum_h)  # [b, c, g, r, q]
    states = jnp.einsum(
        "bcgrqp,bcqgn->bcgrpn",
        jnp.einsum("bcqgrp,bcgrq->bcgrqp", xdt, to_end.astype(dt_act)),
        Bc, preferred_element_type=f32,
    )
    # The pass over the chunk states, S_in[c] = sum_{c' < c} exp(sum of
    # the whole-chunk log-decays strictly between c' and c) S_own[c'],
    # as ONE float32 matmul over the chunk axis and not a loop of nc
    # steps: the TPU runs a loop's few small operations an iteration one
    # after the other, nc times a layer, forward and backward.
    total = jnp.moveaxis(cum_h[..., -1], 1, -1)  # [b, g, r, c]
    run = jnp.cumsum(total, axis=-1)
    # decay from the end of chunk c' to the start of chunk c
    between = (run - total)[..., :, None] - run[..., None, :]
    earlier = jnp.tril(jnp.ones((nc, nc), bool), -1)
    carry = jnp.exp(jnp.where(earlier, between, -jnp.inf))  # [b,g,r,c,c']
    entering = jnp.einsum(
        "bgrcz,bzgrpn->bcgrpn", carry, states,
        precision=lax.Precision.HIGHEST, preferred_element_type=f32,
    )

    # what the entering state adds at every position of the chunk
    y = y + jnp.einsum(
        "bcqgn,bcgrpn->bcgrqp", Cc, entering.astype(dt_act),
        preferred_element_type=f32,
    ) * jnp.exp(cum_h)[..., None]
    return jnp.moveaxis(y, 4, 2).reshape(Bsz, T, H, P)


def gated_group_rmsnorm(y, z, weight, groups: int, eps: float,
                        norm_before_gate: bool = False):
    """``RMSNorm_group(y * silu(z)) * weight``: the mean square is taken
    over each of ``groups`` equal slices of the last axis. float32.
    ``norm_before_gate``: ``RMSNorm_group(y) * weight * silu(z)``, the
    gate outside the norm (the Gated DeltaNet layer's order)."""
    yf = y.astype(jnp.float32)
    gate = jax.nn.silu(z.astype(jnp.float32))
    if not norm_before_gate:
        yf = yf * gate
    shape = yf.shape
    yg = yf.reshape(*shape[:-1], groups, shape[-1] // groups)
    yg = yg * lax.rsqrt(jnp.mean(yg * yg, -1, keepdims=True) + eps)
    out = yg.reshape(shape) * weight.astype(jnp.float32)
    return out * gate if norm_before_gate else out


def gated_norm(statement, o, z, weight, width: int, eps: float,
               inside: bool = False, mesh=None):
    """The gated norm after a mixer's scan, rounded once to ``o``'s dtype:
    ``statement(o, z, weight)``, which is ``RMSNorm(o) * weight * gate(z)``
    over each ``width`` channels of o [B, T, C] (``inside``: the gate inside
    the norm) with z [B, T, C] for ``silu(z)`` a channel or [B, T, C /
    width] for ``sigmoid(z)`` a group, and a weight of C channels or of one
    group's. Where ``gated_norm_kernels.fits`` takes the input, forward and
    backward are the ``gated_norm_*`` kernels; everywhere else the plain
    statement, float32 inside and made again in the backward pass: what
    either keeps is its inputs. ``mesh`` as ``conv_silu``'s. A call is a
    site of ``common/trace_counts`` (``gate_sites``, and
    ``gate_kernel_sites`` where the kernels take it), both counted here, so
    a layer traced twice under ``jax.checkpoint`` counts twice in both."""
    in_kernels = gated_norm_kernels.fits(o, z, width, mesh)
    trace_counts.count("gate_sites")
    trace_counts.count("gate_kernel_sites", in_kernels)
    if in_kernels:
        every = jnp.tile(weight, o.shape[-1] // weight.shape[0])
        return gated_norm_kernels.gated_norm(o, z, every, width, eps, inside)
    return jax.checkpoint(statement)(o, z, weight)


def mamba2_mixer(u, p, cfg, eps: float, mesh=None):
    """u [B, T, d] (already normed) -> [B, T, d]. ``mesh``: the mesh the
    step is sharded over, or None inside a region that names its own axes
    (``conv_silu``, ``gated_norm``)."""
    Bsz, T, _ = u.shape
    H, P, G, N = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups, cfg.ssm_state
    d_in = H * P
    dt_act = u.dtype
    with jax.named_scope("scope/layer/ssm/in_proj"):
        z = u @ p["w_z"].astype(dt_act)
        xbc = u @ p["w_xbc"].astype(dt_act)
        dt = jnp.dot(
            u, p["w_dt"].astype(dt_act),
            preferred_element_type=jnp.float32,
        )
    # the elementwise stretches (convolution + SiLU, the gate and its
    # norm) compute in float32 and are made again in the backward pass:
    # what they keep is their inputs in the activation dtype, and not a
    # float32 copy of every channel of every token
    with jax.named_scope("scope/layer/ssm/conv"):
        xbc = conv_silu(xbc, p["conv_w"], p["conv_b"], mesh)
    x = xbc[..., :d_in].reshape(Bsz, T, H, P)
    Bm = xbc[..., d_in:d_in + G * N].reshape(Bsz, T, G, N)
    Cm = xbc[..., d_in + G * N:].reshape(Bsz, T, G, N)
    with jax.named_scope("scope/layer/ssm/scan"):
        dt = jax.nn.softplus(dt + p["dt_bias"].astype(jnp.float32))
        a = -jnp.exp(p["A_log"].astype(jnp.float32))
        chunk = min(cfg.ssm_chunk, T)
        # a site of ``common/trace_counts``, counted as ``conv_silu``
        # counts its own
        in_kernels = ssd_kernels.fits(x, dt, Bm, Cm, chunk, mesh)
        trace_counts.count("ssd_sites")
        trace_counts.count("ssd_kernel_sites", in_kernels)
        if in_kernels:
            # the ``ssd_scan_*`` kernels: the decay squares live in VMEM,
            # the backward makes them again there, and the ``D`` skip and
            # the rounding are the forward kernel's last lines
            y = ssd_kernels.ssd(
                x, dt, a, Bm, Cm, p["D"].astype(jnp.float32), chunk
            )
        else:
            # the [Q, Q] decay squares of every head are recomputed in the
            # backward pass and not kept: at 8192 tokens they are the
            # layer's largest residuals by far and cost a few percent of
            # its time
            y = jax.checkpoint(ssd_chunked, static_argnums=(5,))(
                x, dt, a, Bm, Cm, chunk
            )
            y = y + p["D"].astype(jnp.float32)[:, None] * x.astype(jnp.float32)
            y = y.astype(dt_act)
        y = y.reshape(Bsz, T, d_in)
    with jax.named_scope("scope/layer/ssm/gate"):
        def statement(y, z, w):
            return gated_group_rmsnorm(y, z, w, G, eps).astype(dt_act)

        y = gated_norm(
            statement, y, z, p["norm"], d_in // G, eps, inside=True,
            mesh=mesh,
        )
    with jax.named_scope("scope/layer/ssm/out_proj"):
        return y @ p["w_out"].astype(dt_act)
