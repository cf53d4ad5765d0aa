"""Model-family configs.

Covers the reference's benchmark families: GPT-2 (nanogpt / GPT-2 xl 1.5B
flash-ckpt benchmarks, BASELINE.md) and Llama-2 (atorch/examples/llama2).
One config dataclass switches the architectural differences (learned vs
rotary positions, LayerNorm vs RMSNorm, GELU-MLP vs SwiGLU, MHA vs GQA,
optional MoE blocks).
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Optional


@dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 50257
    num_layers: int = 12
    model_dim: int = 768
    num_heads: int = 12
    num_kv_heads: Optional[int] = None  # None => MHA
    mlp_dim: Optional[int] = None  # None => 4*model_dim (gpt) / swiglu dim
    max_seq_len: int = 1024
    # architecture switches
    rope: bool = False  # False => learned positional embeddings
    rope_theta: float = 10000.0
    rmsnorm: bool = False
    swiglu: bool = False
    tie_embeddings: bool = True
    # eps of every norm; None => 1e-6 under rmsnorm, 1e-5 under LayerNorm
    norm_eps: Optional[float] = None
    # RMSNorm over each token's whole query and key projections, all
    # heads together, before RoPE (OLMoE's QK-norm)
    qk_norm: bool = False
    # MoE: every `moe_every`-th block uses an expert FFN (SwiGLU experts
    # where `swiglu`, the GELU pair where not)
    num_experts: int = 0
    moe_every: int = 2
    # sizes the per-expert buckets of the all-to-all where experts are
    # sharded over an ep axis of more than one device, and with them
    # how many assignments are dropped there. Where every expert is
    # local (one device, ep=1) the layer is dropless and this has no
    # effect (parallel/moe.py).
    capacity_factor: float = 1.25
    # per-expert capacity re-split ([num_experts] ints, static): ()
    # keeps the uniform capacity_factor sizing; a non-empty tuple
    # (parallel/moe.py CapacityRebalancer.splits from measured load)
    # gives each expert its own cutoff — the bucket dim becomes
    # max(splits), so hot experts stop overflowing while cold ones
    # ship padding. Changing it is a recompile (static shapes).
    capacity_splits: tuple = ()
    # experts per token (1 = Switch, 2 = GShard-style top-2; parity:
    # switch_gating.py:154 covers both) and the router z-loss weight
    # (keeps gate logits small; 0 disables)
    moe_top_k: int = 1
    router_z_weight: float = 1e-3
    # renormalise the k chosen experts' gate values to sum to one
    # (GShard); False keeps their softmax probabilities (OLMoE)
    norm_topk_prob: bool = True
    # sequence-parallel attention scheme when the mesh has sp > 1:
    # "ring" (P2P pipeline, any head count) or "ulysses" (two
    # all-to-alls; needs (heads/tp) % sp == 0) — parallel/{ring_
    # attention,ulysses}.py
    sp_scheme: str = "ring"
    # numerics
    dtype: str = "bfloat16"  # activation/compute dtype
    param_dtype: str = "float32"
    remat: bool = False  # checkpoint each block (HBM <-> FLOPs trade)
    # store layer params STACKED ([L, ...] leaves) and run the blocks
    # under ONE lax.scan: the traced graph is O(1) in depth instead of
    # O(L), which is what lets a 48-layer model compile WITH remat
    # (parity: the reference's activation-checkpoint optimization,
    # optimization_library.py:39-58, is only usable at depth because
    # torch re-executes python; XLA needs the scan). Homogeneous blocks
    # only (no MoE interleave — same restriction as the pipeline).
    scan_layers: bool = False
    # muP forward multipliers (models/mup.py sets these; defaults = SP)
    mup_attn_scale: Optional[float] = None  # None => 1/sqrt(head_dim)
    mup_output_mult: float = 1.0
    # int8 MXU path for the MLP projections (ops/int8_matmul.py — the
    # TPU-native analog of the reference's FP8 optimization)
    int8_mlp: bool = False

    def __post_init__(self):
        if self.scan_layers and self.num_experts:
            raise ValueError(
                "scan_layers needs homogeneous blocks; MoE interleave "
                "(num_experts > 0) makes every moe_every-th block a "
                "different pytree"
            )

    @property
    def kv_heads(self) -> int:
        return self.num_kv_heads or self.num_heads

    @property
    def head_dim(self) -> int:
        return self.model_dim // self.num_heads

    @property
    def ffn_dim(self) -> int:
        if self.mlp_dim:
            return self.mlp_dim
        return 4 * self.model_dim


def gpt2_small() -> TransformerConfig:
    return TransformerConfig()


def gpt2_xl() -> TransformerConfig:
    """GPT-2 xl 1.5B — the reference's flash-ckpt benchmark model
    (docs/blogs/flash_checkpoint.md:292, megatron_flash_checkpoint.md)."""
    return TransformerConfig(
        num_layers=48, model_dim=1600, num_heads=25, max_seq_len=1024
    )


def llama2_7b() -> TransformerConfig:
    """Llama-2-7B — the reference's atorch throughput benchmark model
    (atorch/examples/llama2/README.md:398)."""
    return TransformerConfig(
        vocab_size=32000,
        num_layers=32,
        model_dim=4096,
        num_heads=32,
        num_kv_heads=32,
        mlp_dim=11008,
        max_seq_len=4096,
        rope=True,
        rmsnorm=True,
        swiglu=True,
        tie_embeddings=False,
    )


def is_moe_layer(cfg: TransformerConfig, i: int) -> bool:
    """THE layer-placement rule: block ``i`` carries an expert FFN.
    Every consumer (init/forward layout, metric normalization, the
    dry-runner's all-to-all pricing, the analytic profiler) routes
    through here so the rule cannot drift between them."""
    return bool(
        cfg.num_experts and i % cfg.moe_every == cfg.moe_every - 1
    )


def num_moe_layers(cfg: TransformerConfig) -> int:
    return sum(
        1 for i in range(cfg.num_layers) if is_moe_layer(cfg, i)
    )


def tiny(**overrides) -> TransformerConfig:
    """Test config: small every-feature model."""
    cfg = TransformerConfig(
        vocab_size=256,
        num_layers=2,
        model_dim=32,
        num_heads=4,
        num_kv_heads=2,
        mlp_dim=64,
        max_seq_len=64,
        rope=True,
        rmsnorm=True,
        swiglu=True,
        tie_embeddings=False,
        dtype="float32",
    )
    return replace(cfg, **overrides)
