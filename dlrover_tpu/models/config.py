"""Model-family configs.

One config dataclass switches the architectural differences. Without a
``layer_pattern`` every layer is the attention + FFN block of the
reference's benchmark families, GPT-2 (nanogpt / GPT-2 xl 1.5B flash-ckpt
benchmarks, BASELINE.md) and Llama-2 (atorch/examples/llama2): learned vs
rotary positions, LayerNorm vs RMSNorm, GELU-MLP vs SwiGLU, MHA vs GQA,
optional MoE blocks. With one, every layer is ONE mixer of the kind its
letter names (``LAYER_KINDS``), which is how the hybrid families are
written: ``nemotron_h`` (Mamba-2, attention, experts), ``qwen3_next``
(Gated DeltaNet, gated attention, experts), Ling's (Kimi Delta Attention,
latent attention), ``afmoe`` (window and global attention) and
``phi4flash`` (Mamba-1 scans, differential attention through a window or
whole, and a cross-decoder whose layers read what an earlier layer
computed).
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Optional, Tuple

# layer_pattern's alphabet (nemotron_h's, and "G", "W", "S", "U", "C") ->
# the key of the layer's one mixer in the parameter tree. Nine kinds:
# "M" Mamba-2, "G" the gated delta rule (Gated DeltaNet's, or with
# ``gdn_decay`` "channel" Kimi Delta Attention's), "*" attention, "E"
# experts, "-" the dense feed-forward, "W" attention through a window of
# ``attn_window`` keys (the parameters of "*", so its key), "S" a Mamba-1
# selective scan (``ops/selective_scan.py``), "U" a gated memory unit that
# reads the scan output of the last "S" before it, and "C" a
# cross-attention that projects queries only and reads the keys and
# values of the last "*" before it
LAYER_KINDS = {
    "M": "ssm", "G": "gdn", "*": "attn", "E": "moe", "-": "mlp",
    "W": "attn", "S": "sscan", "U": "gmu", "C": "xattn",
}
# the kinds whose layer reads what another layer computed, and the kind
# of the layer each reads (``TransformerConfig.__post_init__`` refuses a
# pattern that has no such layer before the reader)
LAYER_READS = {"U": "S", "C": "*"}


@dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 50257
    num_layers: int = 12
    model_dim: int = 768
    num_heads: int = 12
    num_kv_heads: Optional[int] = None  # None => MHA
    # width of one attention head where the source states it;
    # None => model_dim // num_heads
    attn_head_dim: Optional[int] = None
    mlp_dim: Optional[int] = None  # None => 4*model_dim (gpt) / swiglu dim
    max_seq_len: int = 1024
    # the kind of every layer, one character a layer, in the alphabet of
    # the ``nemotron_h`` configs (``hybrid_override_pattern``): "M" a
    # Mamba-2 layer, "*" an attention layer, "E" an expert layer, and
    # "-" a dense feed-forward layer of ``dense_mlp_dim``, and "G" a
    # Gated DeltaNet layer, "W" an attention layer whose queries see
    # themselves and the ``attn_window - 1`` keys before them, "S" a
    # Mamba-1 selective-scan layer, "U" a gated memory unit and "C" a
    # cross-attention layer (``LAYER_KINDS``; the pattern alone says
    # which layer reads which: ``LAYER_READS``); each layer
    # is ONE mixer, ``x + mixer(norm(x))``, so a block of mixer then
    # experts is two entries ("GEGEGE*E": one period of ``qwen3_next``;
    # "WEWE*EWE": one of ``afmoe``; "S-W-S-*-U-C-": ``phi4flash``'s two
    # periods around its one full attention). "" = every layer is the
    # attention + FFN block (``moe_every`` places the experts).
    layer_pattern: str = ""
    # the published index of the pattern's first mixer layer, for what
    # depends on a layer's place in the whole model (differential
    # attention's ``lambda_init``): mixer n of the pattern (the layers
    # that are no "-" and no "E", from 0) is published layer
    # ``first_layer + n``
    first_layer: int = 0
    # keys a query of a "W" layer sees, itself among them (a "*" layer
    # sees every key before it); 0 = the pattern has no "W"
    attn_window: int = 0
    # a norm on every mixer's output before the residual add, ``x +
    # norm_out(mixer(norm(x)))`` (``layer_pattern`` models; "out_norm" in
    # a layer's parameters)
    mixer_out_norm: bool = False
    # the kinds of mixer (letters of ``layer_pattern``) whose PUBLISHED
    # layer has its norms on the sub-layers' outputs and none on their
    # inputs (Olmo 3's reordered norm): the mixer's entry and the
    # feed-forward entry ("-" or "E") right after it are ``x +
    # norm_out(f(x))``, with an "out_norm" and no "norm" in their
    # parameters; every other entry stays what ``mixer_out_norm`` says.
    # "" => no entry is reordered (``reordered_norm_entries``)
    reordered_norm_kinds: str = ""
    # how often the whole stack of a ``layer_pattern`` is applied to the
    # residual stream, with the same weights every pass and the final
    # norm after each (a looped language model, arXiv:2510.25741; the
    # source's ``total_ut_steps``): pass ``t`` reads the normed stream of
    # pass ``t - 1``, every pass exits through the one head, and a
    # learned gate (``exit_gate`` in the parameters) says how much of a
    # token's probability of stopping falls on each pass. 1 => a plain
    # model: no gate, one exit
    ut_steps: int = 1
    # the weight of the entropy of that stopping distribution in the
    # loss: ``mean(sum_t p_t nll_t - ut_entropy_weight * H(p))``
    ut_entropy_weight: float = 0.0
    # the token embedding is multiplied by ``sqrt(model_dim)`` as it
    # enters the residual stream (and nothing else is: an untied head
    # reads its own table)
    embed_scale: bool = False
    # architecture switches
    rope: bool = False  # False => learned positional embeddings
    # "" => what ``rope`` says; "none" => no positions anywhere (the
    # attention layers of a Mamba-2 hybrid: the scan carries the order);
    # "window" => by the kind of layer: rotary in the "W" layers, none in
    # the "*" layers, whose keys' order the window layers below carry
    positions: str = ""
    rope_theta: float = 10000.0
    # the leading dims of each head that are rotated (pairs ``(i, i +
    # rope_dim / 2)``), the rest passing untouched; 0 => the whole head
    rope_dim: int = 0
    # how the rotary table's frequencies are stretched past the length
    # they were trained for: "" => ``theta^(-2j/D)`` as they are; "yarn"
    # => YaRN's blend (arXiv:2309.00071, ``models/transformer.
    # yarn_frequencies``): a pair that turns more than ``rope_beta_fast``
    # times over ``rope_original_len`` positions keeps its frequency, one
    # that turns fewer than ``rope_beta_slow`` times is slowed
    # ``rope_factor`` times, a linear ramp over the pairs between
    rope_scaling: str = ""
    rope_factor: float = 1.0
    # the positions the unscaled table was trained over; 0 => not stated
    # (YaRN and ``attn_pos_scale_beta`` need it)
    rope_original_len: int = 0
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    # YaRN's ``mscale_all_dim``: the softmax scale of a latent attention
    # is multiplied by ``(0.1 * rope_mscale_all_dim * ln(rope_factor) +
    # 1)^2`` (DeepSeek-V3's reading of the key); 0 => by 1
    rope_mscale_all_dim: float = 0.0
    # which dims of the rotated stretch make a pair: "" => ``(i, i +
    # D/2)`` (rotate-half); "interleaved" => ``(2i, 2i + 1)``
    rope_pairs: str = ""
    # a query is multiplied by ``1 + attn_pos_scale_beta * ln(1 +
    # floor(pos / rope_original_len))`` after its rotation (Llama 4's
    # position-dependent scale): 1 below ``rope_original_len``; 0 => none
    attn_pos_scale_beta: float = 0.0
    rmsnorm: bool = False
    # how an RMSNorm's weight enters (the residual stream's norms, the
    # final norm and a head's q / k norm): "" => ``x * w``, w from 1;
    # "one_plus" => ``x * (1 + w)``, w from 0 (zero-centred: weight
    # decay pulls the scale to 1 and not to 0)
    norm_weight: str = ""
    swiglu: bool = False
    tie_embeddings: bool = True
    # eps of every norm; None => 1e-6 under rmsnorm, 1e-5 under LayerNorm
    norm_eps: Optional[float] = None
    # RMSNorm of the query and key projections before RoPE, and what one
    # mean square spans: "token" => a token's whole projection, all heads
    # together, a weight a head and dim (OLMoE's QK-norm); "head" =>
    # each head's own width, one weight vector for all heads
    qk_norm: bool = False
    qk_norm_span: str = "token"
    # "sigmoid" => the query projection is twice as wide, a head's
    # second half a gate: ``attention * sigmoid(gate)`` before ``wo``
    attn_gate: str = ""
    # "latent" => keys and values come from one ``kv_latent_dim`` wide
    # latent a token (down-projection, RMSNorm, up-projection) beside one
    # rotated key of ``qk_rope_dim`` that every head shares: a head's
    # query and key are ``qk_nope_dim`` unrotated dims then
    # ``qk_rope_dim`` rotated ones, its value ``v_head_dim`` (MLA,
    # DeepSeek-V2's; the query projected whole, or through a latent of
    # its own where ``q_latent_dim``); as many key/value heads
    # as query heads, ``attn_head_dim`` and ``rope_dim`` unused.
    # "diff" => differential attention (arXiv:2410.05258): query heads
    # ``(2i, 2i+1)`` and key heads ``(2j, 2j+1)`` are pairs, the two
    # values of a key pair one value twice as wide, and a pair's output
    # is ``(softmax(q1 k1) - lambda softmax(q2 k2)) v`` through an RMSNorm
    # of its own (``models/transformer._diff_attention``); even head
    # counts, no output gate, no q / k norm, no rotation
    attn_kind: str = ""
    # the projected attention's q, k, v and output projections carry a
    # bias (the "diff" kind's and the "C" layers' alone)
    attn_bias: bool = False
    kv_latent_dim: int = 0
    # a latent attention's query comes from a latent too: down-projection
    # to this width, RMSNorm, up-projection to the heads (``w_qa``,
    # ``q_latent_norm``, ``w_qb`` in place of ``wq``); 0 => the query
    # projected whole
    q_latent_dim: int = 0
    qk_nope_dim: int = 0
    qk_rope_dim: int = 0
    v_head_dim: int = 0
    # width of a dense feed-forward layer ("-"); 0 => ``ffn_dim``
    dense_mlp_dim: int = 0
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
    # how the router scores: "softmax" over the logits, or "sigmoid" of
    # each logit with a selection bias a expert (chosen by score + bias,
    # weighted by the score alone; parallel/moe.route)
    router: str = "softmax"
    # the routed experts' summed output is multiplied by this
    routed_scale: float = 1.0
    # step of the auxiliary-loss-free balance rule that moves the
    # selection bias after every train step; 0 leaves it where it is
    router_bias_rate: float = 0.0
    # group-limited selection: the experts lie in ``router_groups`` equal
    # groups by index, a group scores the sum of its two best selection
    # scores, and a token chooses among the experts of its
    # ``router_groups_kept`` best groups only (DeepSeek-V3's). 1 => none
    router_groups: int = 1
    router_groups_kept: int = 1
    # weight of the load-balance loss; None => ``loss_fn``'s argument
    router_balance_weight: Optional[float] = None
    # width of the one shared expert every token passes beside its
    # routed ones (SwiGLU where ``swiglu``, as they are); 0 = none
    shared_expert_dim: int = 0
    # "sigmoid" => its output times ``sigmoid(x . w)``, a scalar a token
    shared_expert_gate: str = ""
    # "" => SwiGLU where ``swiglu``, else the GELU pair; "relu2" =>
    # the ungated pair with relu(x)^2 (expert and shared-expert FFNs)
    mlp_activation: str = ""
    # a chip's share of the experts: this many of ``num_experts``, from
    # ``experts_offset`` on, are held (and computed) here; the router
    # still scores all of them. 0 => all
    experts_held: int = 0
    experts_offset: int = 0
    # Mamba-2 layers ("M"): heads x head width = the inner width; B and
    # C are shared by the heads of one of ``ssm_groups`` groups
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_state: int = 0
    ssm_groups: int = 1
    ssm_conv: int = 4
    ssm_chunk: int = 128
    # the step size's range at init (``time_step_min/max/floor``)
    ssm_dt_min: float = 1e-3
    ssm_dt_max: float = 0.1
    ssm_dt_floor: float = 1e-4
    # Mamba-1 layers ("S"): ``sscan_inner`` channels, each with a state of
    # ``sscan_state`` numbers and a decay matrix ``A[channel, state]``;
    # the step a channel comes through a bottleneck of ``sscan_dt_rank``;
    # ``sscan_conv`` taps; the plain statement's chunk. The step's range
    # at init is ``ssm_dt_min / max / floor``. A "U" layer's width is the
    # scan's inner width
    sscan_inner: int = 0
    sscan_state: int = 16
    sscan_dt_rank: int = 0
    sscan_conv: int = 4
    sscan_chunk: int = 128
    # Gated DeltaNet layers ("G"): value heads of ``gdn_value_dim``, each
    # ``gdn_value_heads / gdn_key_heads`` of them reading one key head of
    # ``gdn_key_dim``; the causal convolution's taps and the chunk of the
    # chunked delta rule (ops/gated_delta.py)
    gdn_value_heads: int = 0
    gdn_key_heads: int = 0
    gdn_key_dim: int = 0
    gdn_value_dim: int = 0
    gdn_conv: int = 4
    gdn_chunk: int = 64
    # what decays the state: "head" => one scalar a value head and step,
    # ``-exp(A_log) * softplus(a + dt_bias)`` (Gated DeltaNet); "channel"
    # => a vector over the key's channels a head and step, the log-decay
    # ``gdn_decay_bound * sigmoid(exp(A_log) * (f + dt_bias))`` in
    # ``(gdn_decay_bound, 0)`` (Kimi Delta Attention; as many key as
    # value heads). The chunked rule divides by no more than 16 steps'
    # decay, so the bound must keep that in float32: at least -5
    gdn_decay: str = "head"
    gdn_decay_bound: float = 0.0
    # the output gate: "silu" => ``silu`` of a projection a channel;
    # "head_sigmoid" => ``sigmoid`` of one projection a head
    gdn_gate: str = "silu"
    # the scale of the write strength, ``beta = gdn_beta_scale *
    # sigmoid(b)`` in ``(0, gdn_beta_scale)``: 1 => Gated DeltaNet's; 2 =>
    # the transition ``alpha (I - beta k k^T)`` has eigenvalues down to
    # ``-alpha`` along the key (the source's ``allow_neg_eigval``), and
    # the chunk's unit triangle is inverted by halves, not as a product
    # (``ops/gated_delta.py``)
    gdn_beta_scale: float = 1.0
    # what a row of data is trained to do: "" => next-token prediction
    # under the causal mask (``loss_fn`` embeds ``tokens`` and scores
    # ``targets``); "block_diffusion" => diffusion over blocks (BD3-LM,
    # arXiv:2503.09573, over MDLM's masked objective): the stack sees
    # the row twice, a noised copy ``x_t`` before the clean one ``x_0``
    # (2 L positions, both halves at positions 0..L-1), attention runs
    # under the block-diffusion rule (``ops/flash_attention.
    # block_diffusion_attention``), the head sees the noised half alone
    # and the loss is the masked positions' cross-entropy on their own
    # token, weighted by ``1 / t`` (``models/transformer.diffusion_noise``)
    objective: str = ""
    # positions a block of the diffusion: a noised position sees its own
    # block's noised copies and the clean blocks before it, a clean one
    # the clean blocks up to its own. 0 => no such objective
    diffusion_block: int = 0
    # the id a noised position reads; None => the table's last row
    diffusion_mask_id: Optional[int] = None
    # the least noise level: a block's ``t`` is uniform in [t_min, 1),
    # and its masked positions weigh ``1 / t`` in the loss; the default
    # is MDLM's
    diffusion_t_min: float = 1e-3
    # the seed of the noise, which is a pure function of it, of the row
    # and, in a train step, of the step's number; 0 is a seed like any other
    diffusion_noise_seed: int = 0
    # sequence-parallel attention scheme when the mesh has sp > 1:
    # "ring" (P2P pipeline, any head count) or "ulysses" (two
    # all-to-alls; needs (heads/tp) % sp == 0) — parallel/{ring_
    # attention,ulysses}.py
    sp_scheme: str = "ring"
    # numerics
    dtype: str = "bfloat16"  # activation/compute dtype
    param_dtype: str = "float32"
    # every layer is made again in the backward pass from its input (HBM
    # <-> FLOPs trade); of what a layer computes it keeps what its
    # attention kernel read and returned (q, k, v, ``o``, the logsumexp)
    # alone (``models/transformer.recomputed``)
    remat: bool = False
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
        if self.layer_pattern:
            if set(self.layer_pattern) - set(LAYER_KINDS):
                raise ValueError(
                    f"layer_pattern {self.layer_pattern!r}: kinds are "
                    f"{sorted(LAYER_KINDS)}"
                )
            if len(self.layer_pattern) != self.num_layers:
                raise ValueError(
                    f"layer_pattern {self.layer_pattern!r} names "
                    f"{len(self.layer_pattern)} layers, num_layers is "
                    f"{self.num_layers}"
                )
            if self.scan_layers:
                raise ValueError(
                    "scan_layers needs homogeneous blocks; a "
                    "layer_pattern makes them differ"
                )
        if self.positions not in ("", "none", "window"):
            raise ValueError(f"unknown positions {self.positions!r}")
        seen = set()
        for kind in self.layer_pattern:
            if kind in LAYER_READS and LAYER_READS[kind] not in seen:
                raise ValueError(
                    f"layer_pattern {self.layer_pattern!r}: a "
                    f"{kind!r} layer reads what the last "
                    f"{LAYER_READS[kind]!r} layer before it computed, "
                    "and there is none"
                )
            seen.add(kind)
        if set("SU") & seen and min(
            self.sscan_inner, self.sscan_state, self.sscan_dt_rank,
            self.sscan_conv,
        ) < 1:
            raise ValueError(
                "the \"S\" and \"U\" layers need sscan_inner "
                f"({self.sscan_inner}), sscan_state ({self.sscan_state}), "
                f"sscan_dt_rank ({self.sscan_dt_rank}) and sscan_conv "
                f"({self.sscan_conv})"
            )
        windowed = "W" in self.layer_pattern
        if isinstance(self.attn_window, bool) or (
            self.attn_window < 1 if windowed else self.attn_window
        ):
            raise ValueError(
                f"attn_window {self.attn_window!r} is the window of the "
                f"\"W\" layers of layer_pattern {self.layer_pattern!r}: "
                "1 or more keys where there are such layers, else 0"
            )
        if windowed and self.attn_kind not in ("", "diff"):
            raise ValueError(
                "a window is of the projected attention, plain or "
                f"differential: attn_kind {self.attn_kind!r} knows none"
            )
        if "C" in self.layer_pattern and self.attn_kind != "diff":
            raise ValueError(
                "a \"C\" layer is a differential cross-attention: "
                f"attn_kind is {self.attn_kind!r}"
            )
        if self.attn_kind == "diff" and (
            self.num_heads % 2 or self.kv_heads % 2
            or (self.num_heads // 2) % (self.kv_heads // 2)
            or self.attn_gate or self.qk_norm or self.rope_dim
            or not self.layer_pattern
            or self.position_kind == "rope"
        ):
            raise ValueError(
                "differential attention pairs its heads: an even number "
                f"of query ({self.num_heads}) and of key/value heads "
                f"({self.kv_heads}), the query pairs a multiple of the "
                "key pairs, in a layer_pattern, with no output gate, no "
                "q / k norm and no rotation"
            )
        if self.attn_bias and self.attn_kind != "diff":
            raise ValueError(
                "attn_bias is of the differential attention's projections"
            )
        if self.positions == "window" and not windowed:
            raise ValueError(
                "positions \"window\" are the window layers' rotary "
                "positions: layer_pattern has no \"W\""
            )
        if not isinstance(self.embed_scale, bool):
            raise ValueError(
                f"embed_scale is on or off, not {self.embed_scale!r}"
            )
        if self.mixer_out_norm and not self.layer_pattern:
            raise ValueError(
                "mixer_out_norm is of the one-mixer layers of a "
                "layer_pattern"
            )
        mixers = set(self.layer_pattern) - set("-E")
        if set(self.reordered_norm_kinds) - mixers:
            raise ValueError(
                f"reordered_norm_kinds {self.reordered_norm_kinds!r} names "
                f"a kind of mixer that layer_pattern "
                f"{self.layer_pattern!r} lacks"
            )
        if self.ut_steps < 1 or self.ut_entropy_weight < 0:
            raise ValueError(
                f"ut_steps {self.ut_steps} is a count of passes from 1, "
                f"ut_entropy_weight {self.ut_entropy_weight} no less "
                "than 0"
            )
        if self.ut_steps == 1 and self.ut_entropy_weight:
            raise ValueError(
                "ut_entropy_weight is of the stopping distribution over "
                "several passes: ut_steps is 1"
            )
        if self.ut_steps > 1 and not self.layer_pattern:
            raise ValueError(
                f"ut_steps {self.ut_steps}: the stack that is looped is "
                "a layer_pattern's walk; the attention + FFN blocks "
                "(and their scan_layers form, which scans over the "
                "layers' leaves and not over passes of the same leaves) "
                "run once"
            )
        if self.ut_steps > 1 and self.num_experts:
            raise ValueError(
                f"ut_steps {self.ut_steps} with experts: a router's load "
                "and drop rate, and the rule that moves its selection "
                "bias, are of one visit a step"
            )
        if self.router not in ("softmax", "sigmoid"):
            raise ValueError(f"unknown router {self.router!r}")
        self._check_objective()
        for name, kinds in (
            ("norm_weight", ("", "one_plus")),
            ("qk_norm_span", ("token", "head")),
            ("attn_gate", ("", "sigmoid")),
            ("attn_kind", ("", "latent", "diff")),
            ("shared_expert_gate", ("", "sigmoid")),
            ("gdn_decay", ("head", "channel")),
            ("gdn_gate", ("silu", "head_sigmoid")),
            ("rope_scaling", ("", "yarn")),
            ("rope_pairs", ("", "interleaved")),
        ):
            if getattr(self, name) not in kinds:
                raise ValueError(
                    f"unknown {name} {getattr(self, name)!r}"
                )
        if self.norm_weight and not self.rmsnorm:
            raise ValueError("norm_weight is of an RMSNorm: rmsnorm is off")
        if self.rope_dim % 2 or not 0 <= self.rope_dim <= self.head_dim:
            raise ValueError(
                f"rope_dim {self.rope_dim} is not an even share of a head "
                f"of {self.head_dim}"
            )
        if "G" in self.layer_pattern and (
            min(self.gdn_key_heads, self.gdn_key_dim, self.gdn_value_dim) < 1
            or self.gdn_value_heads % max(self.gdn_key_heads, 1)
            or self.gdn_value_heads < 1
        ):
            raise ValueError(
                f"Gated DeltaNet layers need gdn_value_heads "
                f"({self.gdn_value_heads}) a multiple of gdn_key_heads "
                f"({self.gdn_key_heads}) and both head widths"
            )
        if not 0.0 < self.gdn_beta_scale <= 2.0:
            raise ValueError(
                f"gdn_beta_scale {self.gdn_beta_scale} is outside (0, 2]: "
                "past 2 the delta rule's transition along the key is "
                "larger than the decay"
            )
        if self.gdn_decay == "channel" and "G" in self.layer_pattern and (
            self.gdn_value_heads != self.gdn_key_heads
            or not -5.0 <= self.gdn_decay_bound < 0.0
        ):
            raise ValueError(
                f"a decay a key channel needs as many key heads "
                f"({self.gdn_key_heads}) as value heads "
                f"({self.gdn_value_heads}) and gdn_decay_bound "
                f"({self.gdn_decay_bound}) in [-5, 0)"
            )
        if self.attn_kind == "latent" and (
            min(self.kv_latent_dim, self.qk_nope_dim, self.v_head_dim) < 1
            or self.qk_rope_dim < 2 or self.qk_rope_dim % 2
            or self.num_kv_heads not in (None, self.num_heads)
            or self.attn_gate or self.rope_dim
            or (self.qk_norm and self.qk_norm_span != "head")
        ):
            raise ValueError(
                "latent attention needs kv_latent_dim, qk_nope_dim, an even "
                "qk_rope_dim and v_head_dim, as many key/value heads as "
                "query heads, no output gate, no rope_dim and a q / k norm "
                "a head"
            )
        if self.q_latent_dim < 0 or (
            self.q_latent_dim and self.attn_kind != "latent"
        ):
            raise ValueError(
                f"q_latent_dim {self.q_latent_dim} is the width of a latent "
                f"attention's query latent: attn_kind is {self.attn_kind!r}"
            )
        rotary = self.position_kind in ("rope", "window")
        if (
            self.rope_scaling or self.rope_pairs or self.attn_pos_scale_beta
        ) and not rotary:
            raise ValueError(
                f"rope_scaling {self.rope_scaling!r}, rope_pairs "
                f"{self.rope_pairs!r} and attn_pos_scale_beta "
                f"{self.attn_pos_scale_beta} are of rotary positions: "
                f"position_kind is {self.position_kind!r}"
            )
        if self.rope_scaling == "yarn" and (
            self.rope_factor < 1 or self.rope_original_len < 1
            or not 0 < self.rope_beta_slow < self.rope_beta_fast
        ):
            raise ValueError(
                f"YaRN stretches a table made for rope_original_len "
                f"({self.rope_original_len}) positions rope_factor "
                f"({self.rope_factor}) times, 1 or more, between "
                f"rope_beta_slow ({self.rope_beta_slow}) and a larger "
                f"rope_beta_fast ({self.rope_beta_fast}) turns"
            )
        if self.rope_mscale_all_dim < 0 or (
            self.rope_mscale_all_dim
            and (self.rope_scaling != "yarn" or self.attn_kind != "latent")
        ):
            raise ValueError(
                f"rope_mscale_all_dim {self.rope_mscale_all_dim} scales a "
                "latent attention's softmax under YaRN: rope_scaling is "
                f"{self.rope_scaling!r}, attn_kind {self.attn_kind!r}"
            )
        if self.attn_pos_scale_beta < 0 or (
            self.attn_pos_scale_beta and self.rope_original_len < 1
        ):
            raise ValueError(
                f"attn_pos_scale_beta {self.attn_pos_scale_beta} scales a "
                "query by how many times its position passes "
                f"rope_original_len, which is {self.rope_original_len}"
            )
        if self.router_groups < 1 or not (
            1 <= self.router_groups_kept <= self.router_groups
        ) or (self.router_groups > 1 and (
            self.num_experts % self.router_groups
            or self.num_experts // self.router_groups < 2
            or self.moe_top_k
            > self.router_groups_kept * self.num_experts // self.router_groups
        )):
            raise ValueError(
                f"router_groups {self.router_groups} (kept "
                f"{self.router_groups_kept}) do not split {self.num_experts} "
                f"experts into groups of two or more that hold "
                f"{self.moe_top_k} a token"
            )
        if self.mlp_activation not in ("", "relu2"):
            raise ValueError(
                f"unknown mlp_activation {self.mlp_activation!r}"
            )
        if self.experts_held and not (
            0 <= self.experts_offset
            and self.experts_offset + self.experts_held <= self.num_experts
        ):
            raise ValueError(
                f"experts held [{self.experts_offset}, "
                f"{self.experts_offset + self.experts_held}) are not "
                f"among the {self.num_experts} routed over"
            )
        if self.scan_layers and self.num_experts:
            raise ValueError(
                "scan_layers needs homogeneous blocks; MoE interleave "
                "(num_experts > 0) makes every moe_every-th block a "
                "different pytree"
            )

    def _check_objective(self):
        """Refuse what the diffusion over blocks cannot mean: its fields
        without the objective, a block that does not divide the row, a
        mask id outside the table, a layer that carries state from token
        to token or sees through a window (over a row fed twice neither
        means anything), a looped stack, learned absolute positions (the
        table has one row a position of ONE copy) and an attention that is
        not the plain projected one."""
        if self.objective not in ("", "block_diffusion"):
            raise ValueError(f"unknown objective {self.objective!r}")
        if not self.objective:
            if self.diffusion_block:
                raise ValueError(
                    f"diffusion_block {self.diffusion_block} is of the "
                    "objective \"block_diffusion\": objective is \"\""
                )
            return
        block = self.diffusion_block
        if isinstance(block, bool) or not isinstance(block, int) or (
            block < 1 or self.max_seq_len % block
        ):
            raise ValueError(
                f"diffusion_block {block!r} does not divide a row of "
                f"max_seq_len {self.max_seq_len} into whole blocks"
            )
        if not 0 <= self.mask_id < self.vocab_size:
            raise ValueError(
                f"diffusion_mask_id {self.diffusion_mask_id} is outside "
                f"the table's {self.vocab_size} rows"
            )
        if not 0.0 < self.diffusion_t_min < 1.0:
            raise ValueError(
                f"diffusion_t_min {self.diffusion_t_min} is outside (0, 1)"
            )
        carried = sorted(set(self.layer_pattern) & set("MGSUCW"))
        if carried:
            raise ValueError(
                f"objective {self.objective!r} feeds a row twice, the "
                f"noised copy before the clean one: a layer of kind "
                f"{carried} carries state from token to token or sees "
                "through a window, and over the doubled row that means "
                "nothing"
            )
        if self.ut_steps > 1:
            raise ValueError(
                f"objective {self.objective!r} with ut_steps "
                f"{self.ut_steps}: the exits' loss is next-token "
                "prediction's"
            )
        if self.position_kind == "learned":
            raise ValueError(
                f"objective {self.objective!r} with learned absolute "
                "positions: both copies of a row stand at positions "
                "0..L-1, which the table's slice [:T] cannot say"
            )
        if self.attn_kind:
            raise ValueError(
                f"objective {self.objective!r} is of the plain projected "
                f"attention: attn_kind is {self.attn_kind!r}"
            )

    @property
    def mask_id(self) -> int:
        """The id a noised position reads (``diffusion_mask_id``)."""
        if self.diffusion_mask_id is None:
            return self.vocab_size - 1
        return self.diffusion_mask_id

    @property
    def kv_heads(self) -> int:
        return self.num_kv_heads or self.num_heads

    @property
    def head_dim(self) -> int:
        return self.attn_head_dim or self.model_dim // self.num_heads

    @property
    def qk_head_dim(self) -> int:
        """Width of a head's query and key: what the scores contract."""
        if self.attn_kind == "latent":
            return self.qk_nope_dim + self.qk_rope_dim
        return self.head_dim

    @property
    def position_kind(self) -> str:
        """"rope", "learned", "none", or "window": by the kind of layer
        (``layer_positions``)."""
        return self.positions or ("rope" if self.rope else "learned")

    def layer_positions(self, kind: str) -> str:
        """``position_kind`` of an attention layer of ``kind`` ("*" or
        "W"; "" the attention of the attention + FFN block)."""
        if self.positions == "window":
            return "rope" if kind == "W" else "none"
        return self.position_kind

    @property
    def reordered_norm_entries(self) -> Tuple[bool, ...]:
        """For every entry of ``layer_pattern``, whether it is ``x +
        norm_out(f(x))`` with no input norm (``reordered_norm_kinds``): a
        mixer of a named kind, and the feed-forward entry right after
        one."""
        named, entries = self.reordered_norm_kinds, []
        for i, kind in enumerate(self.layer_pattern):
            entries.append(
                kind in named if kind not in "-E"
                else i > 0 and self.layer_pattern[i - 1] in named
            )
        return tuple(entries)

    @property
    def held_experts(self) -> Tuple[int, int]:
        """(offset, count) of the experts this chip holds."""
        return self.experts_offset, self.experts_held or self.num_experts

    @property
    def ssm_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

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
    if cfg.layer_pattern:
        return cfg.layer_pattern[i] == "E"
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
