"""Pure-functional decoder-only transformer, sharding-annotated.

TPU-first design notes:
- Parameters are a plain pytree; ``logical_axes(cfg)`` returns a matching
  pytree of logical axis names that ``parallel.sharding_rules`` maps to
  mesh axes — this replaces the reference's module-surgery TP registry
  (atorch modules_registry.py, layers.py:239-670): the *same* model code
  runs DP, FSDP, TP, SP, EP or any mix purely via shardings.
- All matmuls are batched and bf16-friendly (``cfg.dtype``); normalization
  and softmax accumulate in fp32.
- Attention: ring attention over the ``sp`` axis when a mesh is given
  (long-context path), single-device causal attention otherwise.
- ``cfg.remat`` wraps each layer in ``recomputed``: a ``jax.checkpoint``
  that trades FLOPs for HBM (the reference's activation-checkpoint
  optimization, atorch auto/opt_lib checkpoint entry) and keeps, of what
  the layer computes, what its attention kernel read and returned alone.
"""

from __future__ import annotations

import contextlib
import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from dlrover_tpu.common import trace_counts
from dlrover_tpu.models.config import (
    LAYER_KINDS,
    LAYER_READS,
    TransformerConfig,
    is_moe_layer,
    num_moe_layers,
)
from dlrover_tpu.ops.flash_attention import KEPT as ATTENTION_KEPT
from dlrover_tpu.ops.gated_delta import (
    KEPT as DELTA_RULE_KEPT,
    gated_delta_logical_axes,
    gated_delta_mixer,
    init_gated_delta_params,
)
from dlrover_tpu.ops.mamba2 import (
    init_mamba2_params,
    mamba2_logical_axes,
    mamba2_mixer,
)
from dlrover_tpu.parallel.moe import (
    KEPT as SHARE_KEPT,
    MoEParams,
    init_moe_params,
    moe_layer,
    moe_layer_local,
    relu2,
)
from dlrover_tpu.ops.selective_scan import (
    init_memory_unit_params,
    init_selective_scan_params,
    memory_unit_logical_axes,
    memory_unit_mixer,
    selective_scan_logical_axes,
    selective_scan_mixer,
)
from dlrover_tpu.parallel.ring_attention import ring_self_attention

Params = Dict[str, Any]


def _dtype(cfg: TransformerConfig):
    return jnp.dtype(cfg.dtype)


def _pdtype(cfg: TransformerConfig):
    return jnp.dtype(cfg.param_dtype)


# ---------------------------------------------------------------------------
# init + logical sharding axes
# ---------------------------------------------------------------------------
def init_params(key, cfg: TransformerConfig) -> Params:
    pd = _pdtype(cfg)
    d, h, kvh, hd = cfg.model_dim, cfg.num_heads, cfg.kv_heads, cfg.head_dim
    f = cfg.ffn_dim

    def dense(k, shape, fan_in):
        return (jax.random.normal(k, shape) * fan_in**-0.5).astype(pd)

    def norm_scale(shape):
        # a zero-centred norm's weight starts at 0: its scale is 1 + w
        if cfg.norm_weight == "one_plus":
            return jnp.zeros(shape, pd)
        return jnp.ones(shape, pd)

    def qk_norms():
        # a weight a head and dim, or one vector for all heads
        a_head = cfg.qk_norm_span == "token"
        qk = cfg.qk_head_dim
        return {
            "q_norm": {"scale": norm_scale((h, qk) if a_head else (qk,))},
            "k_norm": {"scale": norm_scale((kvh, qk) if a_head else (qk,))},
        }

    keys = iter(jax.random.split(key, 8 + cfg.num_layers * 16))
    params: Params = {
        "embed": {
            "tokens": dense(next(keys), (cfg.vocab_size, d), d),
        },
        "final_norm": {"scale": norm_scale((d,))},
        "layers": [],
    }
    if not cfg.rmsnorm:
        params["final_norm"]["bias"] = jnp.zeros((d,), pd)
    if cfg.position_kind == "learned":
        params["embed"]["positions"] = dense(
            next(keys), (cfg.max_seq_len, d), d
        )
    if not cfg.tie_embeddings:
        params["lm_head"] = dense(next(keys), (d, cfg.vocab_size), d)

    def attention():
        if cfg.attn_kind == "latent":
            latent, nope = cfg.kv_latent_dim, cfg.qk_nope_dim
            rope, vd = cfg.qk_rope_dim, cfg.v_head_dim
            rq = cfg.q_latent_dim
            # the query projected whole, or through a latent of its own
            query = {"wq": dense(next(keys), (d, h, nope + rope), d)}
            if rq:
                query = {
                    "w_qa": dense(next(keys), (d, rq), d),
                    "q_latent_norm": {"scale": norm_scale((rq,))},
                    "w_qb": dense(next(keys), (rq, h, nope + rope), rq),
                }
            return {
                **query,
                # [latent | the one rotated key every head shares]
                "w_kva": dense(next(keys), (d, latent + rope), d),
                "kv_norm": {"scale": norm_scale((latent,))},
                # a head's [unrotated key | value] from the latent
                "w_kvb": dense(next(keys), (latent, h, nope + vd), latent),
                "wo": dense(next(keys), (h, vd, d), h * vd),
            }
        # with an output gate a head's projection is [query | gate]
        q_width = 2 * hd if cfg.attn_gate else hd
        a = {
            "wq": dense(next(keys), (d, h, q_width), d),
            "wk": dense(next(keys), (d, kvh, hd), d),
            "wv": dense(next(keys), (d, kvh, hd), d),
            "wo": dense(next(keys), (h, hd, d), h * hd),
        }
        if cfg.attn_kind == "diff":
            a.update(differential())
            if cfg.attn_bias:
                a["bk"] = jnp.zeros((kvh, hd), pd)
                a["bv"] = jnp.zeros((kvh, hd), pd)
        return a

    def differential():
        """What differential attention adds to a layer's projections:
        the four vectors of its ``lambda`` (normal, 0.1), the RMSNorm of
        a pair's output, and the biases of the query and output
        projections where ``attn_bias``."""
        extra = {
            name: (0.1 * jax.random.normal(next(keys), (hd,))).astype(pd)
            for name in ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2")
        }
        extra["subln"] = jnp.ones((2 * hd,), pd)
        if cfg.attn_bias:
            extra["bq"] = jnp.zeros((h, hd), pd)
            extra["bo"] = jnp.zeros((d,), pd)
        return extra

    def cross_attention():
        return {
            "wq": dense(next(keys), (d, h, hd), d),
            "wo": dense(next(keys), (h, hd, d), h * hd),
            **differential(),
        }

    def dense_mlp(width):
        if cfg.swiglu:
            return {
                "w_gate": dense(next(keys), (d, width), d),
                "w_up": dense(next(keys), (d, width), d),
                "w_down": dense(next(keys), (width, d), width),
            }
        return {
            "w_up": dense(next(keys), (d, width), d),
            "b_up": jnp.zeros((width,), pd),
            "w_down": dense(next(keys), (width, d), width),
            "b_down": jnp.zeros((d,), pd),
        }

    def experts():
        return init_moe_params(
            next(keys), cfg.num_experts, d, f, dtype=pd,
            gated=cfg.swiglu, held=cfg.experts_held,
            selection_bias=cfg.router == "sigmoid",
            shared_dim=cfg.shared_expert_dim,
            shared_out_gate=bool(cfg.shared_expert_gate),
        )

    mixers = {
        "M": lambda: init_mamba2_params(next(keys), cfg, pd),
        "G": lambda: init_gated_delta_params(next(keys), cfg, pd),
        "*": attention,
        "W": attention,
        "E": experts,
        "-": lambda: dense_mlp(cfg.dense_mlp_dim or f),
        "S": lambda: init_selective_scan_params(next(keys), cfg, pd),
        "U": lambda: init_memory_unit_params(next(keys), cfg, pd),
        "C": cross_attention,
    }

    def layer_norm():
        norm = {"scale": norm_scale((d,))}
        if not cfg.rmsnorm:
            norm["bias"] = jnp.zeros((d,), pd)
        return norm

    for kind, reordered in zip(
        cfg.layer_pattern, cfg.reordered_norm_entries
    ):
        # one mixer a layer behind one norm (and before one, where
        # ``mixer_out_norm``); a reordered-norm entry has the one after
        layer = {} if reordered else {"norm": layer_norm()}
        if cfg.mixer_out_norm or reordered:
            layer["out_norm"] = layer_norm()
        layer[LAYER_KINDS[kind]] = mixers[kind]()
        if kind in "*W" and cfg.qk_norm:
            layer.update(qk_norms())
        params["layers"].append(layer)

    # without a pattern every layer is the attention + FFN block
    blocks = 0 if cfg.layer_pattern else cfg.num_layers
    for i in range(blocks):
        layer = {
            "attn_norm": {"scale": norm_scale((d,))},
            "mlp_norm": {"scale": norm_scale((d,))},
            "attn": attention(),
        }
        if not cfg.rmsnorm:
            layer["attn_norm"]["bias"] = jnp.zeros((d,), pd)
            layer["mlp_norm"]["bias"] = jnp.zeros((d,), pd)
        if cfg.qk_norm:
            layer.update(qk_norms())
        if is_moe_layer(cfg, i):
            layer["moe"] = experts()
        else:
            layer["mlp"] = dense_mlp(f)
        params["layers"].append(layer)
    if cfg.scan_layers:
        params["layers"] = stack_layer_params(params["layers"])
    if cfg.ut_steps > 1:
        # the exit gate: one linear map with a bias on the normed stream
        params["exit_gate"] = {
            "w": dense(next(keys), (d,), d), "b": jnp.zeros((1,), pd),
        }
    return params


def stack_layer_params(layers: list) -> Params:
    """[L homogeneous layer dicts] → one pytree of [L, ...] leaves (the
    ``cfg.scan_layers`` storage layout)."""
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *layers)


def unstack_layer_params(stacked: Params) -> list:
    """Inverse of ``stack_layer_params`` (checkpoint interop with
    list-layout models)."""
    L = jax.tree_util.tree_leaves(stacked)[0].shape[0]
    return [
        jax.tree_util.tree_map(lambda x: x[i], stacked) for i in range(L)
    ]


def logical_axes(cfg: TransformerConfig) -> Params:
    """Pytree congruent with ``init_params`` holding logical axis tuples."""
    axes: Params = {
        "embed": {"tokens": ("vocab", "embed")},
        "final_norm": {"scale": ("norm",)},
        "layers": [],
    }
    if not cfg.rmsnorm:
        axes["final_norm"]["bias"] = ("norm",)
    if cfg.position_kind == "learned":
        axes["embed"]["positions"] = (None, "embed")
    if not cfg.tie_embeddings:
        axes["lm_head"] = ("embed", "vocab")

    def attention():
        if cfg.attn_kind == "latent":
            query = {"wq": ("embed", "heads", "head_dim")}
            if cfg.q_latent_dim:
                query = {
                    "w_qa": ("embed", None),
                    "q_latent_norm": {"scale": (None,)},
                    "w_qb": (None, "heads", "head_dim"),
                }
            return {
                **query,
                "w_kva": ("embed", None),
                "kv_norm": {"scale": (None,)},
                "w_kvb": (None, "heads", "head_dim"),
                "wo": ("heads", "head_dim", "embed"),
            }
        a = {
            "wq": ("embed", "heads", "head_dim"),
            "wk": ("embed", "kv_heads", "head_dim"),
            "wv": ("embed", "kv_heads", "head_dim"),
            "wo": ("heads", "head_dim", "embed"),
        }
        if cfg.attn_kind == "diff":
            a.update(differential())
            if cfg.attn_bias:
                a["bk"] = ("kv_heads", "head_dim")
                a["bv"] = ("kv_heads", "head_dim")
        return a

    def differential():
        extra = {
            name: ("head_dim",)
            for name in ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2")
        }
        extra["subln"] = (None,)
        if cfg.attn_bias:
            extra["bq"] = ("heads", "head_dim")
            extra["bo"] = ("norm",)
        return extra

    def cross_attention():
        return {
            "wq": ("embed", "heads", "head_dim"),
            "wo": ("heads", "head_dim", "embed"),
            **differential(),
        }

    def dense_mlp():
        if cfg.swiglu:
            return {
                "w_gate": ("embed", "mlp"),
                "w_up": ("embed", "mlp"),
                "w_down": ("mlp", "embed"),
            }
        return {
            "w_up": ("embed", "mlp"),
            "b_up": ("mlp",),
            "w_down": ("mlp", "embed"),
            "b_down": ("norm",),
        }

    def experts():
        up = ("experts", None, "expert_mlp")
        shared = cfg.shared_expert_dim
        return MoEParams(
            gate=(None, None),
            w_up=up,
            w_down=("experts", "expert_mlp", None),
            w_gate=up if cfg.swiglu else None,
            bias=(None,) if cfg.router == "sigmoid" else None,
            shared_up=("embed", "mlp") if shared else None,
            shared_down=("mlp", "embed") if shared else None,
            shared_gate=(
                ("embed", "mlp") if shared and cfg.swiglu else None
            ),
            shared_out_gate=(
                ("embed",) if shared and cfg.shared_expert_gate else None
            ),
        )

    def qk_norms():
        if cfg.qk_norm_span == "head":
            return {
                "q_norm": {"scale": ("head_dim",)},
                "k_norm": {"scale": ("head_dim",)},
            }
        return {
            "q_norm": {"scale": ("heads", "head_dim")},
            "k_norm": {"scale": ("kv_heads", "head_dim")},
        }

    mixers = {
        "M": mamba2_logical_axes,
        "G": lambda: gated_delta_logical_axes(cfg),
        "*": attention, "W": attention, "E": experts, "-": dense_mlp,
        "S": selective_scan_logical_axes, "U": memory_unit_logical_axes,
        "C": cross_attention,
    }

    def layer_norm():
        norm = {"scale": ("norm",)}
        if not cfg.rmsnorm:
            norm["bias"] = ("norm",)
        return norm

    for kind, reordered in zip(
        cfg.layer_pattern, cfg.reordered_norm_entries
    ):
        layer = {} if reordered else {"norm": layer_norm()}
        if cfg.mixer_out_norm or reordered:
            layer["out_norm"] = layer_norm()
        layer[LAYER_KINDS[kind]] = mixers[kind]()
        if kind in "*W" and cfg.qk_norm:
            layer.update(qk_norms())
        axes["layers"].append(layer)

    blocks = 0 if cfg.layer_pattern else cfg.num_layers
    for i in range(blocks):
        layer = {
            "attn_norm": {"scale": ("norm",)},
            "mlp_norm": {"scale": ("norm",)},
            "attn": attention(),
        }
        if not cfg.rmsnorm:
            layer["attn_norm"]["bias"] = ("norm",)
            layer["mlp_norm"]["bias"] = ("norm",)
        if cfg.qk_norm:
            layer.update(qk_norms())
        if is_moe_layer(cfg, i):
            layer["moe"] = experts()
        else:
            layer["mlp"] = dense_mlp()
        axes["layers"].append(layer)
    if cfg.scan_layers:
        layer0 = axes["layers"][0]
        axes["layers"] = jax.tree_util.tree_map(
            lambda t: ("layer_stack",) + t,
            layer0,
            is_leaf=lambda x: isinstance(x, tuple)
            and all(a is None or isinstance(a, str) for a in x),
        )
    if cfg.ut_steps > 1:
        axes["exit_gate"] = {"w": ("norm",), "b": (None,)}
    return axes


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
def _norm_eps(cfg: TransformerConfig) -> float:
    if cfg.norm_eps is not None:
        return cfg.norm_eps
    return 1e-6 if cfg.rmsnorm else 1e-5


def _rms_scale(p, cfg: TransformerConfig):
    """An RMSNorm's scale in float32 from its weight: the weight itself,
    or ``1 + w`` where the norms are zero-centred."""
    scale = p["scale"].astype(jnp.float32)
    return 1.0 + scale if cfg.norm_weight == "one_plus" else scale


def _norm(x, p, cfg: TransformerConfig):
    xf = x.astype(jnp.float32)
    eps = _norm_eps(cfg)
    if cfg.rmsnorm:
        y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
        return (y * _rms_scale(p, cfg)).astype(x.dtype)
    mu = jnp.mean(xf, -1, keepdims=True)
    var = jnp.var(xf, -1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    y = y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    return y.astype(x.dtype)


def _qk_norm(x, p, cfg: TransformerConfig, layout: str = "bthd"):
    """RMSNorm of the query (or key) projection before RoPE. x:
    [B,T,H,D] or [B,H,T,D] per layout. ``qk_norm_span`` "token": over a
    token's WHOLE projection, every head together, the scale [H,D]
    (OLMoE's QK-norm); "head": over each head's own D, the scale [D]."""
    xf = x.astype(jnp.float32)
    if cfg.qk_norm_span == "head":
        ms = jnp.mean(xf * xf, axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(ms + _norm_eps(cfg)) * _rms_scale(p, cfg)
        return y.astype(x.dtype)
    heads = 1 if layout == "bhtd" else 2
    ms = jnp.mean(xf * xf, axis=(heads, 3), keepdims=True)
    scale = _rms_scale(p, cfg)
    if layout == "bhtd":
        scale = scale[:, None, :]
    return (xf * jax.lax.rsqrt(ms + _norm_eps(cfg)) * scale).astype(x.dtype)


def yarn_frequencies(dims: int, theta: float, factor: float,
                     original_len: int, beta_fast: float, beta_slow: float):
    """YaRN's rotary table (arXiv:2309.00071; the form of ``transformers``'
    ``_compute_yarn_parameters``) for ``dims`` rotated dims: pair ``j`` of
    ``dims / 2`` turns by ``pos * f_j``. ``e_j = theta^(-2j/dims)`` is the
    table made for ``original_len`` positions; the pair that turns ``n``
    times over them is ``corr(n) = dims ln(original_len / (2 pi n)) / (2 ln
    theta)``; pairs up to ``floor(corr(beta_fast))`` keep ``e_j``, pairs
    from ``ceil(corr(beta_slow))`` on turn ``factor`` times slower, and a
    linear ramp blends the pairs between. float32 [dims / 2], made on the
    host while the program is traced."""
    def corr(turns):
        return dims * math.log(original_len / (2 * math.pi * turns)) / (
            2 * math.log(theta)
        )

    low = max(math.floor(corr(beta_fast)), 0)
    high = min(math.ceil(corr(beta_slow)), dims - 1)
    j = np.arange(dims // 2, dtype=np.float32)
    # a ramp between equal ends is a step (``transformers`` does the same)
    ramp = np.clip((j - low) / max(high - low, 1e-3), 0.0, 1.0)
    e = np.float32(theta) ** (-2.0 * j / np.float32(dims))
    return (e * (1.0 - ramp) + e / np.float32(factor) * ramp).astype(
        np.float32
    )


def yarn_softmax_mscale(cfg: TransformerConfig) -> float:
    """What a latent attention's softmax scale is multiplied by under
    YaRN: ``m^2``, ``m = 0.1 * rope_mscale_all_dim * ln(rope_factor) + 1``
    (DeepSeek-V3's reading of ``mscale_all_dim``); 1 where the
    configuration states none."""
    if not cfg.rope_mscale_all_dim:
        return 1.0
    return (
        0.1 * cfg.rope_mscale_all_dim * math.log(cfg.rope_factor) + 1.0
    ) ** 2


def _rope_table(cfg: TransformerConfig, dims: int) -> dict:
    """``_rope``'s ``freqs`` and ``pairs`` for ``dims`` rotated dims as the
    configuration states them: nothing for the plain table on rotate-half
    pairs, which ``_rope`` makes from ``theta`` as it always did. A site
    rotated by a scaled table is counted (``rope_scaled_sites``)."""
    table = {}
    if cfg.rope_scaling == "yarn":
        trace_counts.count("rope_scaled_sites")
        table["freqs"] = yarn_frequencies(
            dims, cfg.rope_theta, cfg.rope_factor, cfg.rope_original_len,
            cfg.rope_beta_fast, cfg.rope_beta_slow,
        )
    if cfg.rope_pairs:
        table["pairs"] = cfg.rope_pairs
    return table


def _pos_scale(q, positions, cfg: TransformerConfig, layout: str = "bthd"):
    """The query times ``1 + beta * ln(1 + floor(pos / L))``, ``beta``
    ``cfg.attn_pos_scale_beta`` and ``L`` ``cfg.rope_original_len``, in
    float32 (Llama 4's position-dependent scale, after the rotation): 1
    below ``L``. q: [B,T,H,D] or [B,H,T,D] per layout. The site's query
    rows, and those of them past ``L`` (a row's positions run from 0), are
    counted (``attn_pos_rows``, ``attn_pos_scaled_rows``)."""
    if not cfg.attn_pos_scale_beta:
        return q
    batch, rows = positions.shape
    trace_counts.count("attn_pos_rows", batch * rows)
    trace_counts.count(
        "attn_pos_scaled_rows",
        batch * max(rows - cfg.rope_original_len, 0),
    )
    scale = 1.0 + cfg.attn_pos_scale_beta * jnp.log1p(
        jnp.floor(positions.astype(jnp.float32) / cfg.rope_original_len)
    )
    scale = scale[:, None, :, None] if layout == "bhtd" else (
        scale[:, :, None, None]
    )
    return (q.astype(jnp.float32) * scale).astype(q.dtype)


def _rotate(q, k, positions, cfg: TransformerConfig, layout: str = "bthd",
            dims: int = 0):
    """A projected attention's q and k rotated as the configuration states
    (``_rope`` by ``_rope_table``), the query then scaled by its position
    (``_pos_scale``)."""
    table = _rope_table(cfg, dims or q.shape[-1])
    q = _rope(q, positions, cfg.rope_theta, layout, dims, **table)
    k = _rope(k, positions, cfg.rope_theta, layout, dims, **table)
    return _pos_scale(q, positions, cfg, layout), k


def _rope(x, positions, theta: float, layout: str = "bthd", dims: int = 0,
          freqs=None, pairs: str = ""):
    """Rotate pairs (d, d+D/2). x: [B,T,H,D] or [B,H,T,D] per layout.
    ``dims`` (0 = D): only the leading ``dims`` of a head are rotated, in
    pairs (d, d+dims/2); the rest pass as they are. ``freqs`` (float32
    [dims / 2]): the pairs' frequencies where the table is no plain
    ``theta^(-2j/dims)`` (``_rope_table``). ``pairs`` "interleaved": the
    pairs are ``(2j, 2j + 1)``, and the rotated dims come out as
    ``[every pair's first | every pair's second]``: the one permutation
    for a query and its keys, which their scores cannot see, so nothing
    puts them back."""
    rotated = dims or x.shape[-1]
    half = rotated // 2
    if freqs is None:
        freqs = 1.0 / (
            theta ** (jnp.arange(0, half, dtype=jnp.float32) / half)
        )
    ang = positions[:, :, None].astype(jnp.float32) * freqs  # [B,T,half]
    if layout == "bhtd":
        cos = jnp.cos(ang)[:, None, :, :]
        sin = jnp.sin(ang)[:, None, :, :]
    else:
        cos = jnp.cos(ang)[:, :, None, :]
        sin = jnp.sin(ang)[:, :, None, :]
    if pairs == "interleaved":
        x1, x2 = x[..., 0:rotated:2], x[..., 1:rotated:2]
    else:
        x1, x2 = x[..., :half], x[..., half:rotated]
    parts = [x1 * cos - x2 * sin, x2 * cos + x1 * sin]
    if rotated < x.shape[-1]:
        parts.append(x[..., rotated:])
    return jnp.concatenate(parts, axis=-1).astype(x.dtype)


def _count_score_lanes(called: int, used: int):
    """One attention site by the width of a head's query and key, into
    ``common/trace_counts``: what the attention call is given
    (``attn_score_lanes``) and what the model states
    (``attn_score_lanes_used``), each summed over the sites. They differ
    where a call pads: a latent attention's 192-wide scores run through
    kernels that take one width of whole lane tiles for q, k and v."""
    trace_counts.count("attn_score_lanes", called)
    trace_counts.count("attn_score_lanes_used", used)


def _causal_attention(q, k, v, mesh=None, layout: str = "bthd",
                      sm_scale: Optional[float] = None,
                      window: Optional[int] = None,
                      diffusion_block: Optional[int] = None):
    """Single-shard causal attention, [B,T,H,D] or [B,H,T,D]; with a
    ``window`` a query sees itself and the ``window - 1`` keys before it.
    With a ``diffusion_block`` the row is a noised copy before a clean
    one and the rule is block diffusion's, not the triangle
    (``ops/flash_attention.block_diffusion_attention``).

    Dispatches to the Pallas flash-attention kernel on TPU (fused
    single-program kernels at short seq, block-tiled streaming beyond)
    and the materialized-score jnp path elsewhere —
    ops/flash_attention.py owns both and their shared numerics.

    Wherever GSPMD still owns a mesh axis the call runs under
    ``shard_map``, batch over the data axes and heads over tp:
    attention is independent per example and head, and GSPMD cannot
    partition a Mosaic kernel on its own (the TPU lowering refuses:
    "Mosaic kernels cannot be automatically partitioned"). With a
    ``mesh`` that is all of its axes; with ``mesh=None`` inside the
    explicit gradient sync's partial-manual region (dp manual, tp left
    to GSPMD) it is the axes that region left auto. The pipeline's
    regions, which track varying axes in their types, keep the plain
    call. ``shard_map`` needs the batch and the heads to divide evenly;
    a batch that does not (a small accumulation microbatch) stays with
    GSPMD, which pads — fine for the jnp path, an error on the TPU
    where the kernel would be refused.
    """
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from dlrover_tpu.ops.flash_attention import (
        block_diffusion_attention,
        flash_attention,
    )

    def attend(q, k, v):
        if diffusion_block:
            return block_diffusion_attention(
                q, k, v, block_len=diffusion_block, layout=layout,
                sm_scale=sm_scale,
            )
        scope = (
            jax.named_scope("scope/layer/attn/window") if window
            else contextlib.nullcontext()
        )
        with scope:
            return flash_attention(
                q, k, v, causal=True, layout=layout, sm_scale=sm_scale,
                window=window,
            )

    def spec(batch, heads):
        if layout == "bhtd":
            return P(batch, heads, None, None)
        return P(batch, None, heads, None)

    if mesh is None:
        auto = jax.sharding.get_abstract_mesh().auto_axes
        # no region around us, a fully manual one, or one whose types
        # track varying axes (an untracked map inside would break them)
        if not auto or jax.typeof(q).vma:
            return attend(q, k, v)
        # every axis mentioned: one left out would read as replicated
        # and cost a psum in the backward pass
        rest = tuple(a for a in auto if a != "tp") or None
        inner = spec(rest, "tp" if "tp" in auto else None)
        return shard_map(
            attend, in_specs=(inner,) * 3, out_specs=inner,
            axis_names=frozenset(auto), check_vma=False,
        )(q, k, v)
    if mesh.size == 1:
        return attend(q, k, v)
    data = mesh.shape["dp"] * mesh.shape["fsdp"]
    heads_axis = 1 if layout == "bhtd" else 2
    if q.shape[0] % data or any(
        x.shape[heads_axis] % mesh.shape["tp"] for x in (q, k)
    ):
        if jax.default_backend() == "tpu":
            raise ValueError(
                f"attention batch {q.shape[0]} and heads "
                f"{q.shape[heads_axis]}/{k.shape[heads_axis]} do not "
                f"divide dp*fsdp={data} and tp={mesh.shape['tp']} of "
                f"mesh {dict(mesh.shape)}: the Pallas kernel cannot be "
                "partitioned unevenly; pick a (micro)batch and a tp "
                "that divide"
            )
        return attend(q, k, v)
    both = spec(("dp", "fsdp"), "tp")
    return shard_map(
        attend, mesh=mesh, in_specs=(both,) * 3, out_specs=both,
        check_vma=False,
    )(q, k, v)


def check_window_mesh(cfg: TransformerConfig, mesh):
    """Refuse a model with window layers on a mesh that splits the
    sequence: ring and Ulysses attention know no window and would run
    those layers as full attention (``build_train_step`` asks when a step
    is built, a window layer when it is traced); likewise the layers
    that know no split sequence at all: a selective scan, whose state
    would have to pass from shard to shard, differential attention, and
    the doubled row of diffusion over blocks."""
    if mesh is None or mesh.shape.get("sp", 1) <= 1:
        return
    if cfg.objective:
        raise NotImplementedError(
            f"objective {cfg.objective!r} knows no sequence-parallel "
            f"scheme: under sp = {mesh.shape['sp']} {cfg.sp_scheme} "
            "attention would run the doubled row under the causal "
            "triangle, where a noised position sees the answer"
        )
    if cfg.ut_steps > 1:
        raise NotImplementedError(
            f"a looped model (ut_steps {cfg.ut_steps}) calls every "
            f"attention layer {cfg.ut_steps} times a step over the same "
            f"weights: under sp = {mesh.shape['sp']} {cfg.sp_scheme} "
            "attention's exchange and its by-hand backward rule are not "
            "shown to hold there"
        )
    if cfg.attn_window:
        raise NotImplementedError(
            f"the window layers (attn_window {cfg.attn_window}) know no "
            f"sequence-parallel scheme: under sp = {mesh.shape['sp']} "
            f"{cfg.sp_scheme} attention would run them as full attention"
        )
    if "S" in cfg.layer_pattern:
        raise NotImplementedError(
            "a selective scan is a recurrence over the whole row: under "
            f"sp = {mesh.shape['sp']} every shard would start its scan "
            "(and its convolution) from zeros and not from the state the "
            "shard before it ended with"
        )
    if cfg.attn_kind == "diff":
        raise NotImplementedError(
            "differential attention knows no sequence-parallel scheme: "
            f"under sp = {mesh.shape['sp']} {cfg.sp_scheme} attention "
            "takes one width for scores and values and no key pair's "
            "doubled value"
        )


def _normed(x, layer, cfg: TransformerConfig, norm: str = "norm"):
    """What a layer's mixer reads: ``norm(x)``, or ``x`` itself where the
    layer has no input norm (an entry of ``cfg.reordered_norm_entries``)."""
    if norm not in layer:
        trace_counts.count("reordered_norm_sites")
        return x
    return _norm(x, layer[norm], cfg)


def _residual(x, out, layer, cfg: TransformerConfig):
    """``x + out``, the mixer's output through the layer's output norm
    first where it has one (``cfg.mixer_out_norm``, and an entry of
    ``cfg.reordered_norm_entries``)."""
    if "out_norm" in layer:
        with jax.named_scope("scope/layer/out_norm"):
            out = _norm(out, layer["out_norm"], cfg)
    return x + out


@jax.named_scope("scope/layer/attn")
def _attention_block(x, layer, cfg: TransformerConfig, mesh, positions,
                     norm: str = "attn_norm", kind: str = ""):
    """``x + attention(norm(x))``; ``norm`` names the layer's norm (a
    one-mixer layer of a ``layer_pattern`` has the one, "norm") and
    ``kind`` its letter there: a "W" layer attends through
    ``cfg.attn_window``, and ``cfg.layer_positions`` may differ by it."""
    if cfg.attn_kind == "latent":
        return _latent_attention(x, layer, cfg, mesh, positions, norm)
    h = _normed(x, layer, cfg, norm)
    sp = mesh is not None and mesh.shape.get("sp", 1) > 1
    window = cfg.attn_window if kind == "W" else None
    if window or cfg.objective:
        check_window_mesh(cfg, mesh)
    if cfg.objective:
        trace_counts.count("attn_bd_sites")
    _count_score_lanes(cfg.head_dim, cfg.head_dim)
    # single-shard path: kernel-native [B,H,T,D] straight from the
    # projection einsums — no relayout transposes around the attention
    # kernel. SP schemes shard/permute the seq dim and keep [B,T,H,D].
    layout = "bthd" if sp else "bhtd"
    proj = "btd,dhk->bthk" if sp else "btd,dhk->bhtk"
    q = jnp.einsum(proj, h, layer["attn"]["wq"].astype(h.dtype))
    k = jnp.einsum(proj, h, layer["attn"]["wk"].astype(h.dtype))
    v = jnp.einsum(proj, h, layer["attn"]["wv"].astype(h.dtype))
    if cfg.attn_gate:
        q, gate = q[..., :cfg.head_dim], q[..., cfg.head_dim:]
    if cfg.qk_norm:
        q = _qk_norm(q, layer["q_norm"], cfg, layout)
        k = _qk_norm(k, layer["k_norm"], cfg, layout)
    if cfg.layer_positions(kind) == "rope":
        q, k = _rotate(q, k, positions, cfg, layout, cfg.rope_dim)
    if cfg.mup_attn_scale is not None:
        # muP 1/d attention: fold the deviation from the kernels' builtin
        # 1/sqrt(d) into q, so flash and ring paths need no new plumbing
        q = q * (cfg.mup_attn_scale * cfg.head_dim**0.5)
    if not sp:
        o = _causal_attention(
            q, k, v, mesh, layout="bhtd", window=window,
            diffusion_block=cfg.diffusion_block or None,
        )
    elif cfg.sp_scheme == "ulysses":
        from dlrover_tpu.parallel.ulysses import ulysses_self_attention

        o = ulysses_self_attention(q, k, v, mesh, causal=True)
    elif cfg.sp_scheme == "ring":
        o = ring_self_attention(q, k, v, mesh, causal=True)
    else:
        # a typo silently running the OTHER scheme would make every
        # perf comparison quietly wrong
        raise ValueError(
            f"unknown sp_scheme {cfg.sp_scheme!r} "
            "(expected 'ring' or 'ulysses')"
        )
    if cfg.attn_gate:
        with jax.named_scope("scope/layer/attn/gate"):
            o = (
                o.astype(jnp.float32)
                * jax.nn.sigmoid(gate.astype(jnp.float32))
            ).astype(o.dtype)
    out = "bthk,hkd->btd" if sp else "bhtk,hkd->btd"
    return _residual(
        x, jnp.einsum(out, o, layer["attn"]["wo"].astype(o.dtype)), layer,
        cfg,
    )


_LANES = 128


def _attention_of_two_widths(q, k, v, mesh, window=None, mscale=1.0):
    """Causal attention [B, H, T, .] whose scores contract another width
    than its values have (q, k 192 and v 128 wide, say; or 64 and a
    differential pair's 128), scaled by the stated score width (times
    ``mscale``), through a ``window`` where one is given. The attention
    kernels take ONE width of
    whole lane tiles for q, k and v, so the call pads: zeros on q and k
    leave every score as it is, v is padded and the output sliced; the
    width called is counted beside the width stated."""
    qk, vd = q.shape[-1], v.shape[-1]
    width = -(-max(qk, vd) // _LANES) * _LANES
    _count_score_lanes(width, qk)

    def pad(t):
        return jnp.pad(t, ((0, 0),) * 3 + ((0, width - t.shape[-1]),))

    return _causal_attention(
        pad(q), pad(k), pad(v), mesh, layout="bhtd",
        sm_scale=qk**-0.5 * mscale, window=window,
    )[..., :vd]


def diff_lambda_init(published_layer: int) -> float:
    """Differential attention's ``lambda_init`` of a layer by its index in
    the whole published model (arXiv:2410.05258)."""
    return 0.8 - 0.6 * math.exp(-0.3 * published_layer)


def _diff_head_order(pairs: int, key_pairs: int):
    """The order the attention call wants the query heads in. The model
    states pairs: query heads ``(2i, 2i+1)`` are ``q1_i, q2_i``, key heads
    ``(2j, 2j+1)`` ``k1_j, k2_j``, and pair ``i`` reads ``j = i // r``, ``r``
    query pairs a key pair. A grouped-query call gives key head ``m`` the
    query heads ``[m G, (m + 1) G)``, ``G = r``: so key head ``2j`` must be
    followed by the ``q1`` of its ``r`` pairs and key head ``2j + 1`` by
    their ``q2``."""
    r = pairs // key_pairs
    return np.array([
        2 * (j * r + i) + second
        for j in range(key_pairs) for second in (0, 1) for i in range(r)
    ])


def _diff_keys_values(h, a):
    """A differential layer's keys ``[B, kv_heads, T, head_dim]`` and the
    value pairs as the attention call reads them, ``[B, kv_heads, T, 2
    head_dim]``: key heads ``2j`` and ``2j + 1`` both read ``v_j = [v_2j |
    v_2j+1]``. What a "*" layer hands the "C" layers above it."""
    k = jnp.einsum("btd,dhk->bhtk", h, a["wk"].astype(h.dtype))
    v = jnp.einsum("btd,dhk->bhtk", h, a["wv"].astype(h.dtype))
    if "bk" in a:
        k = k + a["bk"].astype(h.dtype)[:, None, :]
        v = v + a["bv"].astype(h.dtype)[:, None, :]
    B, kvh, T, hd = v.shape
    pair = jnp.moveaxis(v.reshape(B, kvh // 2, 2, T, hd), 2, 3)
    pair = pair.reshape(B, kvh // 2, 1, T, 2 * hd)
    v2 = jnp.broadcast_to(pair, (B, kvh // 2, 2, T, 2 * hd))
    return k, v2.reshape(B, kvh, T, 2 * hd)


@jax.named_scope("scope/layer/attn")
def _diff_attention(x, layer, cfg: TransformerConfig, mesh, kind: str,
                    published: int, shared=None):
    """``x + attention(norm(x))`` with differential attention
    (``cfg.attn_kind`` "diff"; arXiv:2410.05258): a pair's output is
    ``(softmax(q1 k1 / sqrt(hd)) - lambda softmax(q2 k2 / sqrt(hd))) v``
    with ``v`` the key pair's two values side by side, ``lambda =
    exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init(published)``, then an
    RMSNorm over the pair's ``2 hd`` with a weight, times ``1 -
    lambda_init``. A "W" layer attends through ``cfg.attn_window``; a "C"
    layer projects queries only and reads ``shared``, the keys and value
    pairs of the last "*" layer (``_diff_keys_values``). Returns ``(x,
    keys and value pairs)``.

    Both softmaxes of every pair come from ONE attention call, each score
    computed once: the call has ``num_heads`` query heads of ``hd`` on
    ``kv_heads`` key heads, in ``_diff_head_order``, and values ``2 hd``
    wide, through ``_attention_of_two_widths`` (q and k padded to the
    values' width)."""
    check_window_mesh(cfg, mesh)
    cross = kind == "C"
    a = layer[LAYER_KINDS[kind]]
    heads, kvh, hd = cfg.num_heads, cfg.kv_heads, cfg.head_dim
    pairs, key_pairs = heads // 2, kvh // 2
    h = _normed(x, layer, cfg)
    order = _diff_head_order(pairs, key_pairs)
    q = jnp.einsum("btd,dhk->bhtk", h, a["wq"][:, order].astype(h.dtype))
    if "bq" in a:
        q = q + a["bq"][order].astype(h.dtype)[:, None, :]
    if cross:
        trace_counts.count("xdec_kv_reads")
        k, v2 = shared
    else:
        k, v2 = _diff_keys_values(h, a)
    trace_counts.count("attn_diff_pairs", pairs)
    # one call, every pair's two score maps once each
    trace_counts.count("attn_diff_score_calls", heads // 2)
    o = _attention_of_two_widths(
        q, k, v2, mesh, window=cfg.attn_window if kind == "W" else None
    )
    with jax.named_scope("scope/layer/attn/diff"):
        f32 = jnp.float32
        B, _, T, wide = o.shape
        o = o.astype(f32).reshape(
            B, key_pairs, 2, pairs // key_pairs, T, wide
        )
        init = diff_lambda_init(published)
        lam = (
            jnp.exp(jnp.sum(
                a["lambda_q1"].astype(f32) * a["lambda_k1"].astype(f32)
            ))
            - jnp.exp(jnp.sum(
                a["lambda_q2"].astype(f32) * a["lambda_k2"].astype(f32)
            ))
            + init
        )
        o = (o[:, :, 0] - lam * o[:, :, 1]).reshape(B, pairs, T, wide)
        o = o * jax.lax.rsqrt(
            jnp.mean(o * o, -1, keepdims=True) + _norm_eps(cfg)
        )
        o = (o * a["subln"].astype(f32) * (1.0 - init)).astype(h.dtype)
    out = jnp.einsum(
        "bptk,pkd->btd", o,
        a["wo"].reshape(pairs, 2 * hd, -1).astype(o.dtype),
    )
    if "bo" in a:
        out = out + a["bo"].astype(out.dtype)
    return _residual(x, out, layer, cfg), (k, v2)


def _latent_attention(x, layer, cfg: TransformerConfig, mesh, positions,
                      norm: str):
    """``x + attention(norm(x))`` with keys and values from a latent
    (``cfg.attn_kind`` "latent"): ``[c | k_rope] = h W_kva``, ``[k_nope |
    v] = RMSNorm(c) W_kvb`` a head, the one ``k_rope`` shared by every
    head; the query projected whole, ``q = h W_q``, or where
    ``cfg.q_latent_dim`` through a latent of its own, ``q = RMSNorm(h
    W_qa) W_qb``; a head's q and k are ``[nope | rope]``, normed whole
    where ``qk_norm`` and then rotated on the rope dims alone (by the
    table and pairs ``_rope_table`` gives), the query then scaled by its
    position (``_pos_scale``); causal softmax over ``qk_nope_dim +
    qk_rope_dim`` wide scores, scaled by YaRN's ``m^2`` too where the
    configuration states one (``yarn_softmax_mscale``), values
    ``v_head_dim`` wide (``_attention_of_two_widths``)."""
    if mesh is not None and mesh.shape.get("sp", 1) > 1:
        raise NotImplementedError(
            "latent attention knows no sequence-parallel scheme"
        )
    a = layer["attn"]
    nope, rope, vd = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    latent = cfg.kv_latent_dim
    h = _normed(x, layer, cfg, norm)
    if cfg.q_latent_dim:
        trace_counts.count("attn_q_latent_sites")
        cq = _norm(h @ a["w_qa"].astype(h.dtype), a["q_latent_norm"], cfg)
        q = jnp.einsum("btc,chk->bhtk", cq, a["w_qb"].astype(h.dtype))
    else:
        q = jnp.einsum("btd,dhk->bhtk", h, a["wq"].astype(h.dtype))
    with jax.named_scope("scope/layer/attn/kv_down"):
        down = h @ a["w_kva"].astype(h.dtype)
    c = _norm(down[..., :latent], a["kv_norm"], cfg)
    with jax.named_scope("scope/layer/attn/kv_up"):
        kv = jnp.einsum("btc,chk->bhtk", c, a["w_kvb"].astype(h.dtype))
    k_rope = jnp.broadcast_to(
        down[:, None, :, latent:], (*kv.shape[:3], rope)
    )
    k = jnp.concatenate([kv[..., :nope], k_rope], axis=-1)
    v = kv[..., nope:]
    if cfg.qk_norm:
        q = _qk_norm(q, layer["q_norm"], cfg, "bhtd")
        k = _qk_norm(k, layer["k_norm"], cfg, "bhtd")
    if cfg.position_kind == "rope":
        table = _rope_table(cfg, rope)
        q, k = (jnp.concatenate([
            t[..., :nope],
            _rope(t[..., nope:], positions, cfg.rope_theta, "bhtd", **table),
        ], axis=-1) for t in (q, k))
        q = _pos_scale(q, positions, cfg, "bhtd")
    o = _attention_of_two_widths(
        q, k, v, mesh, mscale=yarn_softmax_mscale(cfg)
    )
    return _residual(
        x, jnp.einsum("bhtk,hkd->btd", o, a["wo"].astype(o.dtype)), layer,
        cfg,
    )


@jax.named_scope("scope/layer/ssm")
def _ssm_block(x, layer, cfg: TransformerConfig, mesh):
    h = _normed(x, layer, cfg)
    return _residual(
        x, mamba2_mixer(h, layer["ssm"], cfg, _norm_eps(cfg), mesh), layer,
        cfg,
    )


@jax.named_scope("scope/layer/sscan")
def _sscan_block(x, layer, cfg: TransformerConfig, mesh):
    """``(x + mixer(norm(x)), the scan's output before its gate)``."""
    check_window_mesh(cfg, mesh)
    h = _normed(x, layer, cfg)
    out, memory = selective_scan_mixer(h, layer["sscan"], cfg, mesh)
    return _residual(x, out, layer, cfg), memory


@jax.named_scope("scope/layer/gmu")
def _gmu_block(x, layer, cfg: TransformerConfig, memory):
    trace_counts.count("xdec_memory_reads")
    h = _normed(x, layer, cfg)
    return _residual(
        x, memory_unit_mixer(h, layer["gmu"], memory), layer, cfg
    )


@jax.named_scope("scope/layer/gdn")
def _gdn_block(x, layer, cfg: TransformerConfig, mesh):
    h = _normed(x, layer, cfg)
    return _residual(
        x, gated_delta_mixer(h, layer["gdn"], cfg, _norm_eps(cfg), mesh),
        layer, cfg,
    )


def _zero_aux(cfg: Optional[TransformerConfig] = None):
    """Aux-loss tree congruent with what MoE layers emit. With a MoE
    config the tree also carries the per-expert routing load vector
    and the capacity drop-rate scalar (ISSUE 13 telemetry — the
    CapacityRebalancer feeds on them); dense layers contribute
    zeros."""
    aux = {"balance": jnp.float32(0.0), "z": jnp.float32(0.0)}
    if cfg is not None and cfg.num_experts:
        aux["load"] = jnp.zeros((cfg.num_experts,), jnp.float32)
        aux["drop"] = jnp.float32(0.0)
        if cfg.layer_pattern:
            # each sparse layer's own load, for the rule that moves its
            # selection bias (parallel/moe.move_router_bias)
            aux["layer_load"] = jnp.zeros(
                (num_moe_layers(cfg), cfg.num_experts), jnp.float32
            )
    if cfg is not None and cfg.ut_steps > 1:
        # what ``loss_fn`` says of a looped model's exits (``ut_exits``)
        aux["ut_entropy"] = jnp.float32(0.0)
        aux["ut_exit_step"] = jnp.float32(0.0)
        aux["ut_exit_nll"] = jnp.zeros((cfg.ut_steps,), jnp.float32)
    if cfg is not None and cfg.objective:
        # what ``loss_fn`` says of a step's noise (``diffusion_noise``)
        aux["diffusion_masked_share"] = jnp.float32(0.0)
        aux["diffusion_mean_weight"] = jnp.float32(0.0)
    return aux


@jax.named_scope("scope/layer/mlp")
def _mlp_block(x, layer, cfg: TransformerConfig, mesh, moe_axis=None,
               norm: str = "mlp_norm"):
    h = _normed(x, layer, cfg, norm)
    if "moe" in layer:
        kw = dict(
            capacity_factor=cfg.capacity_factor,
            top_k=cfg.moe_top_k,
            expert_caps=cfg.capacity_splits or None,
            normalize=cfg.norm_topk_prob,
            router=cfg.router,
            routed_scale=cfg.routed_scale,
            held=cfg.held_experts,
        )
        if cfg.router_groups > 1:
            kw["groups"] = (cfg.router_groups, cfg.router_groups_kept)
        if cfg.mlp_activation == "relu2":
            kw["activation"] = relu2
        if mesh is not None and mesh.size > 1:
            out, aux = moe_layer(layer["moe"], h, mesh, **kw)
        else:
            # one device, or mesh=None inside a manual region;
            # ``moe_axis`` names the manual ep axis when expert
            # weights enter as LOCAL [E/ep, ...] slices (the
            # explicit-sync path), so the dispatch/combine
            # all-to-alls still run
            B, T, d = h.shape
            out, aux = moe_layer_local(
                layer["moe"], h.reshape(B * T, d), axis_name=moe_axis,
                **kw,
            )
            out = out.reshape(B, T, d)
        return _residual(x, out, layer, cfg), aux
    mlp = layer["mlp"]
    if cfg.int8_mlp:
        from dlrover_tpu.ops.int8_matmul import int8_einsum_btd_df as mm
    else:

        def mm(x, w):
            return jnp.einsum("btd,df->btf", x, w.astype(x.dtype))

    if cfg.swiglu:
        g = mm(h, mlp["w_gate"])
        u = mm(h, mlp["w_up"])
        z = jax.nn.silu(g) * u
    else:
        z = jax.nn.gelu(mm(h, mlp["w_up"]) + mlp["b_up"].astype(h.dtype))
    out = mm(z, mlp["w_down"])
    if not cfg.swiglu:
        out = out + mlp["b_down"].astype(h.dtype)
    return _residual(x, out, layer, cfg), _zero_aux(cfg)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _embed_lookup(table, tokens, mesh):
    return table[tokens]


def _embed_lookup_fwd(table, tokens, mesh):
    # residuals must be JAX types: the table's shape rides along as a
    # static int tuple; its dtype is recovered from dx (the lookup is
    # dtype-preserving)
    return table[tokens], (tokens, table.shape)


def _embed_lookup_bwd(mesh, res, dx):
    """Gather vjp (scatter-add), with the batch→feature reshard of the
    cotangent decomposed into single-axis hops.

    Under dp×fsdp, ``dx`` arrives with its batch dim sharded over BOTH
    axes while the table cotangent wants D sharded over fsdp; XLA's SPMD
    partitioner cannot move between those layouts in one step and falls
    back to "involuntary full rematerialization" (replicate, then
    re-partition — spmd_partitioner.cc:652). Pinning the intermediate
    layout (batch@dp, D@fsdp) turns it into two expressible all-to-alls.
    """
    tokens, tshape = res
    if (
        mesh is not None
        and mesh.shape.get("dp", 1) > 1
        and mesh.shape.get("fsdp", 1) > 1
    ):
        from jax.sharding import NamedSharding, PartitionSpec as P

        spec = P("dp", *([None] * (dx.ndim - 2)), "fsdp")
        dx = lax.with_sharding_constraint(dx, NamedSharding(mesh, spec))
    dtable = jnp.zeros(tshape, dx.dtype).at[tokens].add(dx)
    return dtable, None


_embed_lookup.defvjp(_embed_lookup_fwd, _embed_lookup_bwd)


@jax.named_scope("scope/embed")
def embed_tokens(
    params: Params, tokens: jnp.ndarray, cfg: TransformerConfig, mesh=None
):
    """tokens [B,T] → residual stream [B,T,D] (token + learned positions)."""
    dt = _dtype(cfg)
    T = tokens.shape[-1]
    x = _embed_lookup(params["embed"]["tokens"].astype(dt), tokens, mesh)
    if cfg.embed_scale:
        x = (x.astype(jnp.float32) * cfg.model_dim**0.5).astype(dt)
    if cfg.position_kind == "learned":
        x = x + params["embed"]["positions"].astype(dt)[:T][None]
    return x


@jax.named_scope("scope/final_norm")
def _final_norm(params: Params, x: jnp.ndarray, cfg: TransformerConfig):
    return _norm(x, params["final_norm"], cfg)


def lm_head(params: Params, x: jnp.ndarray, cfg: TransformerConfig):
    """final residual [B,T,D] → logits [B,T,vocab] fp32 (incl. final norm)."""
    return _head_logits(params, _final_norm(params, x, cfg), cfg)


def _head_logits(params: Params, x: jnp.ndarray, cfg: TransformerConfig):
    """normed stream [B,T,D] → logits [B,T,vocab] fp32."""
    dt = _dtype(cfg)
    with jax.named_scope("scope/lm_head"):
        if cfg.tie_embeddings:
            w = params["embed"]["tokens"].astype(dt)
            logits = jnp.einsum("btd,vd->btv", x, w)
        else:
            logits = jnp.einsum(
                "btd,dv->btv", x, params["lm_head"].astype(dt)
            )
        logits = logits.astype(jnp.float32)
        if cfg.mup_output_mult != 1.0:
            logits = logits * cfg.mup_output_mult
    return logits


def _nll_each(logits: jnp.ndarray, targets: jnp.ndarray) -> jnp.ndarray:
    """[B,T,V], [B,T] → each token's negative log-likelihood [B,T]."""
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return lse - tgt


@jax.named_scope("scope/xent")
def token_nll(
    logits: jnp.ndarray, targets: jnp.ndarray, row_weights=None,
    token_weights=None,
) -> jnp.ndarray:
    """Mean next-token negative log-likelihood; with ``token_weights``
    [B,T] each token's own weight on its term of that mean (0 leaves a
    token out; the divisor stays B T).

    Written as ``logsumexp(logits) - logits[target]`` (identical math
    and gradient — softmax minus one-hot) instead of gathering from
    ``log_softmax``: the log_softmax form materializes a second
    [B, T, vocab] fp32 tensor for the backward (3.3 GB of avoidable HBM
    traffic a step of the 124M model at batch 32)."""
    nll = _nll_each(logits, targets)
    if token_weights is not None:
        nll = token_weights.astype(nll.dtype) * nll
    if row_weights is not None:
        # weighted mean over rows (micro-batch rebalance: padded rows
        # carry weight 0, real rows batch_padded/batch_real — see
        # models/train.py pad_row_weights; the plain mean over the
        # padded batch then equals the mean over the real rows)
        return jnp.mean(row_weights[:, None].astype(nll.dtype) * nll)
    return jnp.mean(nll)


# -- diffusion over blocks: the noise of a row -------------------------------


@jax.named_scope("scope/embed")
def diffusion_noise(tokens: jnp.ndarray, cfg: TransformerConfig, step=None):
    """The noise of ``cfg.objective`` "block_diffusion" on rows ``x_0``
    [B,L]: ``(x_t, masked, weight)``, each [B,L]. A pure function of a row
    and ``cfg.diffusion_noise_seed``, in integers and float32: a row's key
    is ``fold_in(PRNGKey(seed), sum(x_0) mod 2^31)``; each block of
    ``cfg.diffusion_block`` positions draws ``t = t_min + (1 - t_min) U``,
    ``U ~ U[0, 1)`` from the key's first half, each position ``u ~ U[0,
    1)`` from its second, and a position is masked where ``u < t`` of its
    block (MDLM's linear schedule as BD3-LM trains it); ``x_t`` reads
    ``cfg.mask_id`` there and ``x_0`` elsewhere, and ``weight`` is ``1 /
    t`` on the masked positions and 0 on the rest: what the loss
    multiplies a position's cross-entropy by. ``step`` (a train step's
    own number, ``models/train``) is folded into the seed's key, so that a
    row met again in a later step or epoch is noised anew; without it the
    noise is the row's alone, which is what a reference that is handed
    ``(params, tokens, targets)`` can draw again."""
    length, block = tokens.shape[1], cfg.diffusion_block
    if length % block:
        raise ValueError(
            f"a row of {length} positions is no whole number of "
            f"diffusion blocks of {block}"
        )
    seed = jax.random.PRNGKey(cfg.diffusion_noise_seed)
    if step is not None:
        seed = jax.random.fold_in(seed, step)

    def one_row(row):
        total = jnp.sum(row.astype(jnp.uint32)) & jnp.uint32(0x7FFFFFFF)
        by_block, by_position = jax.random.split(
            jax.random.fold_in(seed, total)
        )
        t_min = jnp.float32(cfg.diffusion_t_min)
        t = t_min + (1.0 - t_min) * jax.random.uniform(
            by_block, (length // block,), jnp.float32
        )
        t = jnp.repeat(t, block)
        masked = jax.random.uniform(by_position, (length,), jnp.float32) < t
        return masked, jnp.where(masked, 1.0 / t, 0.0)

    masked, weight = jax.vmap(one_row)(tokens)
    noised = jnp.where(masked, jnp.asarray(cfg.mask_id, tokens.dtype), tokens)
    return noised, masked, weight


# -- a looped model's exits: the head and its loss, one rule -----------------


def _exit_nll(h, w, targets, cfg: TransformerConfig):
    """One exit, from its normed stream ``h`` [B,T,D] and the head ``w`` in
    the activation dtype as its leaf holds it (the table [V,D] where the
    embeddings are tied, else [D,V]): ``(z, lse, nll)``, each token's
    negative log-likelihood [B,T] beside the float32 logits
    (``_head_logits``'s arithmetic) and their logsumexp it came from."""
    with jax.named_scope("scope/lm_head"):
        spec = "btd,vd->btv" if cfg.tie_embeddings else "btd,dv->btv"
        z = jnp.einsum(spec, h, w).astype(jnp.float32)
        if cfg.mup_output_mult != 1.0:
            z = z * cfg.mup_output_mult
    with jax.named_scope("scope/xent"):
        lse = jax.scipy.special.logsumexp(z, axis=-1)
        tgt = jnp.take_along_axis(z, targets[..., None], axis=-1)[..., 0]
        return z, lse, lse - tgt


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _exits_nll(cfg: TransformerConfig, passes, w_head, targets, a):
    """``exits_nll`` where no gradient is asked: one exit at a time."""
    dt = _dtype(cfg)
    w = w_head.astype(dt)
    with jax.named_scope("scope/lm_head"):  # the loop itself
        nll = lax.map(lambda h: _exit_nll(h, w, targets, cfg)[2], passes)
    with jax.named_scope("scope/xent"):
        return jnp.sum(a * nll), nll


def _exits_nll_fwd(cfg: TransformerConfig, passes, w_head, targets, a):
    """The forward rule makes the gradients: an exit's logits are there
    once, so ``U = softmax(z) - onehot`` is made from them in the
    activation dtype beside the logsumexp and both gradient products read
    it. An exit's weight ``a_t`` rides on the [B,T,D] side of each
    product, in float32 after the one and before the rounding of ``h`` in
    the other, never on [B,T,V]. Kept for the backward rule: ``dh``
    [R,B,T,D], ``dW`` (float32, the leaf's layout) and ``nll``.

    The exits are a ``lax.scan`` that carries ``dW``, and not R unrolled
    bodies: at the Ouro cell's size the unrolled form compiles to less
    (the donating step 12.15 GiB against 12.40, ``benchmark/tests/
    aot_sizes.py``) and yet held more on the chip (13.67 GB at the peak
    against 13.20) for a head 3.7 ms a step slower."""
    dt = _dtype(cfg)
    trace_counts.count("ut_exit_fused_heads", passes.shape[0])
    w = w_head.astype(dt)
    to_h, to_w = (
        ("btv,vd->btd", "btv,btd->vd") if cfg.tie_embeddings
        else ("btv,dv->btd", "btd,btv->dv")
    )

    def one_exit(dw, exit_):
        h, a_t = exit_
        z, lse, nll = _exit_nll(h, w, targets, cfg)
        with jax.named_scope("scope/lm_head"):
            hit = lax.broadcasted_iota(jnp.int32, z.shape, 2) == (
                targets[..., None]
            )
            soft = jnp.exp(z - lse[..., None])
            u = jnp.where(hit, soft - 1.0, soft).astype(dt)
            scale = a_t[..., None] * cfg.mup_output_mult
            dh = scale * jnp.einsum(
                to_h, u, w, preferred_element_type=jnp.float32
            )
            weighed = (scale * h.astype(jnp.float32)).astype(dt)
            operands = (u, weighed) if cfg.tie_embeddings else (weighed, u)
            dw = dw + jnp.einsum(
                to_w, *operands, preferred_element_type=jnp.float32
            )
        return dw, (dh.astype(h.dtype), nll)

    with jax.named_scope("scope/lm_head"):  # the loop itself
        dw, (dh, nll) = lax.scan(
            one_exit, jnp.zeros(w_head.shape, jnp.float32), (passes, a)
        )
    with jax.named_scope("scope/xent"):
        total = jnp.sum(a * nll)
    return (total, nll), (dh, dw.astype(w_head.dtype), nll)


def _exits_nll_bwd(cfg: TransformerConfig, kept, cotangents):
    dh, dw, nll = kept
    c, _ = cotangents  # ``exits_nll`` stops the gradient of the second
    with jax.named_scope("scope/lm_head"):
        return (c.astype(dh.dtype) * dh, c.astype(dw.dtype) * dw, None,
                c * nll)


_exits_nll.defvjp(_exits_nll_fwd, _exits_nll_bwd)


def exits_nll(passes, w_head, targets, a, cfg: TransformerConfig):
    """The exits of a looped model through the one head, as one function
    with its own backward rule: every pass's normed stream ``passes``
    [R,B,T,D], the head's leaf ``w_head`` as it is held (float32; the
    table [V,D] where the embeddings are tied), ``targets`` [B,T] and each
    token's weight at each exit ``a`` [R,B,T] →
    ``(sum(a * nll), nll [R,B,T])``, the second under ``stop_gradient``
    (a report: the gradient to ``a`` is the first's).

    ``d nll / d z = softmax(z) - onehot`` does not depend on the
    cotangent, so the forward rule makes it where the logits are
    (``_exits_nll_fwd``): no exit's logits are made a second time, and no
    float32 [B,T,V] cotangent is written, cast or copied. The step holds
    one exit's [B,T,V] at a time (a ``lax.scan`` that carries ``dW``) and
    keeps nothing of the vocabulary's width but ``dW`` itself."""
    total, nll = _exits_nll(cfg, passes, w_head, targets, a)
    return total, lax.stop_gradient(nll)


# ONE policy object: ``jax`` caches a jaxpr's split into what is kept and
# what is made again by the policy's identity, and a policy a wrapper would
# split every layer's inner functions anew (twice the functions in the
# lowered step)
KEPT = ATTENTION_KEPT + SHARE_KEPT + DELTA_RULE_KEPT
_KEEP_BY_NAME = jax.checkpoint_policies.save_only_these_names(*KEPT)


def recomputed(layer_fn):
    """``layer_fn`` as a layer the backward pass makes again
    (``cfg.remat``; every site that wraps a layer for it comes here). It
    keeps its input and, of what it computes, what the modules named for
    it alone (``KEPT``): what its attention kernel read and returned
    (``ops/flash_attention.KEPT``: q, k, v after head norm and rotation,
    ``o`` and the logsumexp, O(T D) bytes that cost O(T^2 D) operations),
    what the first round of a share of the experts gathered and its
    grouped matmuls returned (``parallel/moe.KEPT``), and of a delta-rule
    mixer what its serial pass read and returned, the rule's ``o`` and
    the projection's ``[q | k | v]`` that its convolution reads
    (``ops/gated_delta.KEPT``). So the backward pass makes the
    projections, norms, gates, router and dense feed-forward again, runs
    the forward attention kernel, the held experts' grouped matmuls and
    the delta rule's forward kernels and serial pass no second time and
    does not remake the stretch that only feeds them. A layer without such
    a call (a Mamba scan, dropless experts, the jnp attention path, a
    ring) holds no such name and keeps its input, as a bare
    ``jax.checkpoint`` does."""

    @functools.wraps(layer_fn)
    def traced(*args):
        with trace_counts.keeping_outputs():
            return layer_fn(*args)

    return jax.checkpoint(traced, policy=_KEEP_BY_NAME)


def ut_passes(one_pass, x, steps: int):
    """The stream ``x`` through ``one_pass`` ``steps`` times, the output
    of each pass into the next: every pass's output, stacked [steps, ...].
    A Python loop, so the step holds ``steps`` copies of the pass (each
    layer under its own ``recomputed`` wrapper, each site traced and
    counted for itself) and not a ``lax.scan`` over one: at the Ouro
    cell's size the compiler gives the scan's stacked residuals a second
    home (the donating step 20.2 GiB against 12.7, ``benchmark/tests/
    aot_sizes.py``) for a compile of 48 s against 86."""
    passes = []
    for _ in range(steps):
        x = one_pass(x)
        passes.append(x)
    return jnp.stack(passes)


def ut_stopping(g):
    """The exit gate's logits ``g`` [R, ...] → ``log p`` [R, ...] of the
    stopping distribution over the R passes: ``p_t = sigmoid(g_t)
    prod_{j<t} (1 - sigmoid(g_j))`` for ``t < R`` and ``p_R`` the rest
    (``g_R`` is unused), every factor's log by ``log_sigmoid``."""
    ahead = jnp.zeros_like(g[0])  # log prod_{j<t} (1 - lambda_j)
    log_p = []
    for t in range(g.shape[0] - 1):
        log_p.append(jax.nn.log_sigmoid(g[t]) + ahead)
        ahead = ahead + jax.nn.log_sigmoid(-g[t])
    return jnp.stack(log_p + [ahead])


def ut_exits(params: Params, passes, targets, cfg: TransformerConfig,
             row_weights=None):
    """The loss of a looped model from every pass's normed stream
    ``passes`` [R,B,T,D], and what it reports of the exits.

    Each pass exits through the one head: ``nll_t`` a token's negative
    log-likelihood at pass ``t``, ``g_t = h_t . w + b`` the exit gate's
    logit, ``lambda_t = sigmoid(g_t)``. A token's stopping distribution
    is ``p_t = lambda_t prod_{j<t} (1 - lambda_j)`` for ``t < R`` and
    ``p_R = prod_{j<R} (1 - lambda_j)`` (``lambda_R`` is unused), from
    ``log_sigmoid`` and never from the log of a product. The loss is
    ``mean(sum_t p_t nll_t - ut_entropy_weight * H(p))``, ``row_weights``
    weighing a row's bracket as they weigh its NLL in ``token_nll``.

    The stopping distribution comes first, from all R gate logits (float32
    products and sums, no matmul): a token's weight at exit ``t`` is then
    known, ``a_t = p_t * row_weight / (B T)``, and the exits go through the
    head in ``exits_nll``, one at a time, whose forward rule makes their
    gradients beside their losses. The gradient to the gate flows through
    ``a`` (its cotangent is ``nll``) and through the entropy term."""
    steps, rows, length = passes.shape[:3]
    gate = params["exit_gate"]
    with jax.named_scope("scope/xent"):
        # float32 and no matmul: a product on the MXU would round h and w
        # to bfloat16 at the default precision
        g = jnp.sum(
            passes.astype(jnp.float32) * gate["w"].astype(jnp.float32), -1
        ) + gate["b"].astype(jnp.float32)
        log_p = ut_stopping(g)
        p = jnp.exp(log_p)
        entropy = -jnp.sum(p * log_p, 0)  # p = 0 at a finite log p: 0
        weights = 1.0 if row_weights is None else (
            row_weights[:, None].astype(jnp.float32)
        )
        a = p * (weights / (rows * length))
        entropy_term = jnp.mean(weights * entropy)
    trace_counts.count("ut_exit_heads", steps)
    w_head = (
        params["embed"]["tokens"] if cfg.tie_embeddings else params["lm_head"]
    )
    weighed, nll = exits_nll(passes, w_head, targets, a, cfg)
    with jax.named_scope("scope/xent"):
        at = jnp.arange(1, steps + 1, dtype=jnp.float32)[:, None, None]
        said = {
            "ut_entropy": jnp.mean(entropy),
            "ut_exit_step": jnp.mean(jnp.sum(at * p, 0)),
            "ut_exit_nll": jnp.mean(nll, (1, 2)),
        }
        return weighed - cfg.ut_entropy_weight * entropy_term, said


def forward(
    params: Params,
    tokens: jnp.ndarray,
    cfg: TransformerConfig,
    mesh=None,
    return_hidden: bool = False,
    moe_axis=None,
    return_passes: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """tokens [B,T] int32 → (logits [B,T,vocab] fp32, moe aux dict
    {"balance": load-balance loss, "z": router z-loss} — zeros for dense
    models).

    A looped model (``cfg.ut_steps`` > 1) runs the pattern's walk that
    many times over the same ``params["layers"]``, the final norm after
    every pass and the normed stream into the next; logits and
    ``return_hidden`` are the last pass's, and ``return_passes=True``
    returns every pass's normed stream [ut_steps,B,T,D] (what the exits
    of ``loss_fn`` read).

    ``return_hidden=True`` returns the final-norm'd residual stream
    [B,T,D] instead of logits and skips the vocab projection entirely —
    the trunk for value heads / probes (the RLHF critic uses this, so
    trunk math can never drift from the LM path).

    Under ``cfg.objective`` "block_diffusion" ``tokens`` is the doubled
    row ``[x_t ; x_0]`` [B,2L] (``diffusion_noise`` makes ``x_t``): both
    halves stand at positions 0..L-1, every layer sees all 2L positions,
    and the final norm and the head see the first L alone, so logits and
    ``return_hidden`` are [B,L,.]: the clean half never reaches the head.
    """
    B, T = tokens.shape
    x = embed_tokens(params, tokens, cfg, mesh)
    if cfg.objective:
        if T % 2 or (T // 2) % cfg.diffusion_block:
            raise ValueError(
                f"objective {cfg.objective!r}: {T} positions are no two "
                f"copies of a row of whole blocks of {cfg.diffusion_block}"
            )
        half = jnp.arange(T // 2)
        positions = jnp.broadcast_to(jnp.concatenate([half, half]), (B, T))
    else:
        positions = jnp.broadcast_to(jnp.arange(T), (B, T))

    aux_total = _zero_aux(cfg)

    def block(x, layer):
        x = _attention_block(x, layer, cfg, mesh, positions)
        x, aux = _mlp_block(x, layer, cfg, mesh, moe_axis=moe_axis)
        return x, aux

    def mixer_layer(x, layer, read, kind, published):
        """One layer of a ``layer_pattern``: ``x + mixer(norm(x))``.
        ``read``: what the layer reads of another layer's (``LAYER_READS``;
        None for most kinds). Returns ``(x, aux, what the layer hands to
        layers above it)``."""
        if kind == "M":
            return _ssm_block(x, layer, cfg, mesh), None, None
        if kind == "G":
            return _gdn_block(x, layer, cfg, mesh), None, None
        if kind == "S":
            x, memory = _sscan_block(x, layer, cfg, mesh)
            return x, None, memory
        if kind == "U":
            return _gmu_block(x, layer, cfg, read), None, None
        if kind in "*WC" and cfg.attn_kind == "diff":
            x, keys_values = _diff_attention(
                x, layer, cfg, mesh, kind, published, read
            )
            return x, None, keys_values if kind == "*" else None
        if kind in "*W":
            x = _attention_block(
                x, layer, cfg, mesh, positions, "norm", kind
            )
            return x, None, None
        x, aux = _mlp_block(x, layer, cfg, mesh, moe_axis, "norm")
        return x, (aux if kind == "E" else None), None

    if cfg.remat and not cfg.layer_pattern:
        block = recomputed(block)

    def pattern_walk(x, aux_total):
        """The stream through every layer of the ``layer_pattern`` once:
        ``(x, aux_total and the layers' aux)``."""
        loads = []
        # what the last layer of each kind handed on (``LAYER_READS``: a
        # "U" reads the last "S", a "C" the last "*"), carried beside x
        handed = {}
        published = cfg.first_layer
        for kind, layer in zip(cfg.layer_pattern, params["layers"]):
            one_layer = functools.partial(
                mixer_layer, kind=kind, published=published
            )
            published += kind not in "-E"
            if cfg.remat:
                # a wrapper a layer: ``jax.checkpoint`` keeps the trace of
                # a function it has seen at these shapes, and a layer that
                # came out of that cache is not traced, so what a trace
                # counts (``common/trace_counts``) would be of one layer
                # of each kind. What a layer reads of another is an input
                # of the recomputed layer like x, and is kept as x is
                one_layer = recomputed(one_layer)
            x, aux, handed[kind] = one_layer(
                x, layer, handed.get(LAYER_READS.get(kind))
            )
            if aux is not None:
                loads.append(aux["load"])
                aux_total = dict(
                    aux_total, **{k: aux_total[k] + aux[k] for k in aux}
                )
        if loads:
            aux_total["layer_load"] = jnp.stack(loads)
        return x, aux_total

    if cfg.ut_steps > 1:
        # no experts in a looped model (``TransformerConfig``): the walk's
        # aux is zeros, and the loss's terms of the exits are its own
        def one_pass(stream):
            trace_counts.count("ut_steps")
            trace_counts.count("ut_layer_passes", len(cfg.layer_pattern))
            return _final_norm(
                params, pattern_walk(stream, aux_total)[0], cfg
            )

        passes = ut_passes(one_pass, x, cfg.ut_steps)
        if return_passes:
            return passes, aux_total
        if return_hidden:
            return passes[-1], aux_total
        return _head_logits(params, passes[-1], cfg), aux_total
    if cfg.layer_pattern:
        x, aux_total = pattern_walk(x, aux_total)
    elif cfg.scan_layers:
        # one scanned block: the traced/compiled graph is O(1) in depth
        # — 48-layer remat compiles where the unrolled graph cannot
        def sbody(carry, layer):
            x, aux_t = carry
            x, aux = block(x, layer)
            return (x, jax.tree_util.tree_map(jnp.add, aux_t, aux)), None

        (x, aux_total), _ = lax.scan(
            sbody, (x, aux_total), params["layers"]
        )
    else:
        for layer in params["layers"]:
            x, aux = block(x, layer)
            aux_total = jax.tree_util.tree_map(jnp.add, aux_total, aux)

    if cfg.objective:
        x = x[:, :T // 2]
    if return_hidden:
        return _final_norm(params, x, cfg), aux_total
    return lm_head(params, x, cfg), aux_total


def loss_fn(
    params: Params,
    tokens: jnp.ndarray,
    targets: jnp.ndarray,
    cfg: TransformerConfig,
    mesh=None,
    moe_aux_weight: float = 0.01,
    return_aux: bool = False,
    moe_axis=None,
    row_weights=None,
    noise_step=None,
):
    """Mean NLL + weighted MoE aux losses (load balance at
    ``moe_aux_weight``, router z at ``cfg.router_z_weight``); of a looped
    model (``cfg.ut_steps`` > 1) the exits' loss (``ut_exits``).
    ``return_aux=True`` → (loss, aux dict) for metric surfacing.

    Under ``cfg.objective`` "block_diffusion" ``tokens`` [B,L] is the row
    of data ``x_0``: it is noised (``diffusion_noise``), fed as ``[x_t ;
    x_0]``, and the loss is ``mean(weight * nll)`` over its B L positions,
    a noised position's logits scoring that position's own token;
    ``targets`` (the row shifted by one) is not read; ``noise_step`` is
    ``diffusion_noise``'s ``step`` (a train step hands its own number)."""
    if cfg.ut_steps > 1:
        passes, aux = forward(params, tokens, cfg, mesh, return_passes=True)
        loss, said = ut_exits(params, passes, targets, cfg, row_weights)
        return (loss, dict(aux, **said)) if return_aux else loss
    token_weights = None
    if cfg.objective:
        noised, masked, token_weights = diffusion_noise(
            tokens, cfg, noise_step
        )
        trace_counts.count("diffusion_positions", 2 * tokens.size)
        trace_counts.count("diffusion_data_tokens", tokens.size)
        targets = tokens
        tokens = jnp.concatenate([noised, tokens], axis=1)
    logits, aux = forward(params, tokens, cfg, mesh, moe_axis=moe_axis)
    if cfg.objective:
        aux = dict(
            aux,
            diffusion_masked_share=jnp.mean(masked.astype(jnp.float32)),
            diffusion_mean_weight=jnp.mean(token_weights),
        )
    if cfg.router_balance_weight is not None:
        moe_aux_weight = cfg.router_balance_weight
    loss = (
        token_nll(
            logits, targets, row_weights=row_weights,
            token_weights=token_weights,
        )
        + moe_aux_weight * aux["balance"]
        + cfg.router_z_weight * aux["z"]
    )
    if return_aux:
        return loss, aux
    return loss


# ---------------------------------------------------------------------------
# cached autoregressive decoding (generation / RLHF rollouts)
# ---------------------------------------------------------------------------
def _refuse_cached_loop(cfg: TransformerConfig):
    if cfg.objective:
        raise NotImplementedError(
            f"cached decoding yields one token a sequence a step: a model "
            f"trained by {cfg.objective!r} generates a block of "
            f"{cfg.diffusion_block} positions by denoising it over "
            "several forward passes against a cache of the finished "
            "blocks, which no cache or decode step here does"
        )
    if cfg.ut_steps > 1:
        raise NotImplementedError(
            f"cached decoding knows one visit of a layer a token: a "
            f"looped model (ut_steps {cfg.ut_steps}) keeps one set of "
            f"keys and values a layer AND pass ({cfg.ut_steps} x "
            f"{cfg.num_layers} layers of cache) and leaves the loop where "
            "a token's accumulated stopping probability passes a "
            "threshold, which no cache or decode step here does"
        )


def init_kv_cache(cfg: TransformerConfig, batch: int, max_len: int):
    """Per-layer K/V buffers [L, B, S, kv_heads, head_dim]. Static shape:
    the whole decode loop stays inside one compiled ``lax.scan``."""
    _refuse_cached_loop(cfg)
    if set(cfg.layer_pattern) & set("SUC"):
        raise NotImplementedError(
            f"cached decoding knows no layer_pattern {cfg.layer_pattern!r}: "
            "a selective scan's cache is its state and its convolution's "
            "last taps, a gated memory unit reads the scan output of the "
            "token being decoded, and a cross-attention reads ONE layer's "
            "keys and values, which no cache here holds once for all its "
            "readers"
        )
    if cfg.attn_window:
        raise NotImplementedError(
            f"cached decoding knows no window: the \"W\" layers' "
            f"attn_window of {cfg.attn_window} keys would be decoded as "
            "full attention (a window layer's cache is its last "
            "attn_window keys, which no cache here holds)"
        )
    if (
        cfg.layer_pattern or cfg.attn_gate or cfg.rope_dim
        or cfg.qk_norm_span != "token" or cfg.attn_kind or cfg.embed_scale
    ):
        raise NotImplementedError(
            "cached decoding knows the attention + FFN block only, "
            "ungated, wholly rotated, its QK-norm over the token, its "
            "keys and values projected and not from a latent, its "
            "embedding unscaled"
        )
    dt = _dtype(cfg)
    shape = (cfg.num_layers, batch, max_len, cfg.kv_heads, cfg.head_dim)
    return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}


def _cached_decode_layer(
    x, layer, k_cache, v_cache, *, positions, mask, cfg, dt, write_kv
):
    """One cached transformer block: (x, this layer's K/V buffers) →
    (x', K', V'). The ONLY thing that varies between the all-equal
    decode (``forward_step``) and the per-slot ragged decode
    (``forward_step_ragged``) is how new K/V lands in the cache —
    ``write_kv`` — and the ``positions``/``mask`` the caller computed;
    everything else (QKV, rope, muP scale, GQA attention, wo, MLP) is
    this shared body, so the two entries cannot drift."""
    B, t = x.shape[0], x.shape[1]
    g = cfg.num_heads // cfg.kv_heads
    h = _norm(x, layer["attn_norm"], cfg)
    q = jnp.einsum("btd,dhk->bthk", h, layer["attn"]["wq"].astype(dt))
    k = jnp.einsum("btd,dhk->bthk", h, layer["attn"]["wk"].astype(dt))
    v = jnp.einsum("btd,dhk->bthk", h, layer["attn"]["wv"].astype(dt))
    if cfg.qk_norm:
        q = _qk_norm(q, layer["q_norm"], cfg)
        k = _qk_norm(k, layer["k_norm"], cfg)
    if cfg.position_kind == "rope":
        q, k = _rotate(q, k, positions, cfg)
    if cfg.mup_attn_scale is not None:
        # same muP 1/d fold as _attention_block — decode must score
        # with the training attention math
        q = q * (cfg.mup_attn_scale * cfg.head_dim**0.5)
    k_all = write_kv(k_cache, k)
    v_all = write_kv(v_cache, v)
    # GQA: fold the head group next to kv heads, no KV replication.
    # fp32 accumulation throughout, matching the flash path's
    # numerics (a bf16-accumulated decode would diverge from the
    # teacher-forced re-scoring and bias PPO ratios)
    qg = q.reshape(B, t, cfg.kv_heads, g, cfg.head_dim)
    scores = jnp.einsum(
        "btkgh,bskh->bkgts", qg, k_all,
        preferred_element_type=jnp.float32,
    ) * (cfg.head_dim**-0.5)
    scores = jnp.where(mask[:, None, None, :, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum(
        "bkgts,bskh->btkgh", probs, v_all,
        preferred_element_type=jnp.float32,
    ).astype(dt)
    o = o.reshape(B, t, cfg.num_heads, cfg.head_dim)
    x = x + jnp.einsum(
        "bthk,hkd->btd", o, layer["attn"]["wo"].astype(dt)
    )
    x, _ = _mlp_block(x, layer, cfg, None)
    return x, k_all, v_all


def _run_cached_layers(x, params, cache, cfg, decode_layer):
    """Drive ``decode_layer`` over every layer — scanned or unrolled —
    returning (x, updated cache). Shared by both cached entries."""
    if cfg.scan_layers:

        def sbody(x, inp):
            layer, k_cache, v_cache = inp
            x, k_all, v_all = decode_layer(x, layer, k_cache, v_cache)
            return x, (k_all, v_all)

        x, (k_new, v_new) = lax.scan(
            sbody, x, (params["layers"], cache["k"], cache["v"])
        )
        return x, {"k": k_new, "v": v_new}

    new_k, new_v = [], []
    for i, layer in enumerate(params["layers"]):
        x, k_all, v_all = decode_layer(
            x, layer, cache["k"][i], cache["v"][i]
        )
        new_k.append(k_all)
        new_v.append(v_all)
    return x, {"k": jnp.stack(new_k), "v": jnp.stack(new_v)}


def forward_step(
    params: Params,
    tokens: jnp.ndarray,
    cfg: TransformerConfig,
    cache,
    cur_len,
) -> Tuple[jnp.ndarray, Any]:
    """Cached forward: ``tokens`` [B, t] occupy positions
    ``cur_len .. cur_len+t-1`` (t>1 = prefill chunk, t=1 = decode step).
    Returns (logits [B, t, vocab] fp32, updated cache). Same weights and
    math as ``forward`` — attention just reads K/V from the cache buffer
    instead of recomputing them, the standard decode memory/FLOPs trade.
    """
    _refuse_cached_loop(cfg)
    dt = _dtype(cfg)
    B, t = tokens.shape
    S = cache["k"].shape[2]

    x = params["embed"]["tokens"].astype(dt)[tokens]
    positions = cur_len + jnp.arange(t)[None, :]  # [1, t] broadcasts to B
    positions = jnp.broadcast_to(positions, (B, t))
    if cfg.position_kind == "learned":
        pos_emb = lax.dynamic_slice_in_dim(
            params["embed"]["positions"].astype(dt), cur_len, t
        )
        x = x + pos_emb[None]

    # key-position mask: a query at cur_len+i sees keys 0..cur_len+i
    key_pos = jnp.arange(S)[None, None, :]  # [1, 1, S]
    q_pos = positions[:, :, None]  # [B, t, 1]
    mask = key_pos <= q_pos  # [B, t, S]

    def write_kv(c, val):
        return lax.dynamic_update_slice(
            c, val.astype(c.dtype), (0, cur_len, 0, 0)
        )

    decode_layer = functools.partial(
        _cached_decode_layer,
        positions=positions, mask=mask, cfg=cfg, dt=dt, write_kv=write_kv,
    )
    x, new_cache = _run_cached_layers(x, params, cache, cfg, decode_layer)
    return lm_head(params, x, cfg), new_cache


def forward_step_ragged(
    params: Params,
    tokens: jnp.ndarray,  # [S] int32 — ONE token per slot
    cfg: TransformerConfig,
    cache,
    cur_lens: jnp.ndarray,  # [S] int32 — per-slot cache fill
) -> Tuple[jnp.ndarray, Any]:
    """Per-slot-position decode step: slot ``s``'s token occupies
    position ``cur_lens[s]`` of ITS sequence. The continuous-batching
    engine (rl/continuous_batching.py) needs this because its slots sit
    at different depths — some mid-prefill, some decoding. Same math as
    ``forward_step`` (which this generalizes: scalar ``cur_len`` is the
    all-equal special case), with the cache write becoming a per-slot
    scatter and the causal mask reading per-slot positions. Stale cache
    entries from a slot's PREVIOUS occupant need no clearing: position
    ``i`` is rewritten before any later query can attend to it.
    """
    _refuse_cached_loop(cfg)
    dt = _dtype(cfg)
    S_slots = tokens.shape[0]
    T = cache["k"].shape[2]
    slot_ix = jnp.arange(S_slots)

    x = params["embed"]["tokens"].astype(dt)[tokens][:, None]  # [S,1,D]
    positions = cur_lens[:, None]  # [S, 1]
    if cfg.position_kind == "learned":
        x = x + params["embed"]["positions"].astype(dt)[cur_lens][:, None]

    key_pos = jnp.arange(T)[None, None, :]  # [1, 1, T]
    mask = key_pos <= positions[:, :, None]  # [S, 1, T]

    def write_kv(c, val):
        # per-slot scatter: cache[s, cur_lens[s]] = val[s, 0]
        return c.at[slot_ix, cur_lens].set(val[:, 0].astype(c.dtype))

    decode_layer = functools.partial(
        _cached_decode_layer,
        positions=positions, mask=mask, cfg=cfg, dt=dt, write_kv=write_kv,
    )
    x, new_cache = _run_cached_layers(x, params, cache, cfg, decode_layer)
    return lm_head(params, x, cfg)[:, 0], new_cache
