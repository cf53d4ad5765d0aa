"""The family module (``"flops": "flops_ling3"`` in a configuration) of the
models whose ``layer_pattern`` names ONE mixer a layer in the alphabet
``G`` a Kimi Delta Attention layer (``gdn_value_heads`` = ``gdn_key_heads``
heads of ``gdn_key_dim`` / ``gdn_value_dim``, the decay a vector over the
key's channels projected at full rank, one output gate a head, chunks of
``gdn_chunk``), ``*`` a latent attention layer (``num_heads`` heads whose
scores are ``qk_nope_dim + qk_rope_dim`` wide and whose values are
``v_head_dim`` wide, keys and values from a ``kv_latent_dim`` wide latent
beside one shared rotated key), ``-`` a dense SwiGLU feed-forward of
``dense_mlp_dim``, ``E`` ``num_experts`` SwiGLU experts of ``mlp_dim``
beside one ungated SwiGLU shared expert of ``shared_expert_dim``,
``moe_top_k`` a token, of which this chip holds ``experts_held`` (``ling3``:
a published layer is two entries, mixer then feed-forward). ``count`` and
``step_work`` are what ``run.py`` and the trace readers ask (``flops.py``);
each layer kind is counted once a layer of its kind, at its own widths, and
no other layer is.

**The share**, as ``flops_qwen3_next.py`` has it: everything here is what
THIS chip holds and runs: of the routed experts the ``experts_held``
matrices, and of a token's ``moe_top_k`` assignments the ``experts_held /
num_experts`` that fall on them when the routing is balanced (the program
reports what really fell on them: ``moe.held_share_pct``).

**The attention's widths**: the scores contract ``qk_nope_dim +
qk_rope_dim`` (192) and the values are ``v_head_dim`` (128) wide; the
program's call pads both to 256 for its kernels, and that padding is not
work: ``kernel.attn_roofline`` therefore shows what the pad costs
(``attn.score_lanes_used_pct`` names it).

``model`` is the ``model`` group of a configuration file (the fields of
``TransformerConfig``). Nothing here imports the program or JAX; of
``flops_moe.py`` only the per-layer piece.
"""

from flops_moe import grouped_matmul_work

ACT_BYTES = 2
KINDS = "G*-E"


def _sizes(model: dict) -> dict:
    pattern = model["layer_pattern"]
    if len(pattern) != model["num_layers"] or set(pattern) - set(KINDS):
        raise ValueError(f"layer_pattern {pattern!r} is not the layers")
    experts = model["num_experts"]
    H, dk, dv = (
        model["gdn_value_heads"], model["gdn_key_dim"], model["gdn_value_dim"]
    )
    if model["gdn_key_heads"] != H:
        raise ValueError("a decay a key channel: as many key as value heads")
    return {
        "d": model["model_dim"], "heads": model["num_heads"],
        "latent": model["kv_latent_dim"], "nope": model["qk_nope_dim"],
        "rope": model["qk_rope_dim"], "vd": model["v_head_dim"],
        "qk": model["qk_nope_dim"] + model["qk_rope_dim"],
        "H": H, "dk": dk, "dv": dv, "key_w": H * dk, "val_w": H * dv,
        "K": model.get("gdn_conv", 4), "C": model.get("gdn_chunk", 64),
        "f": model["mlp_dim"], "fd": model["dense_mlp_dim"],
        "fs": model["shared_expert_dim"],
        "experts": experts, "held": model.get("experts_held") or experts,
        "k": model["moe_top_k"], "vocab": model["vocab_size"],
        "n": {kind: pattern.count(kind) for kind in KINDS},
    }


def layer_params(model: dict) -> dict:
    """Parameters of one layer of each kind, its one norm included, and
    of one routed expert; ``matmul`` the part of each a token passes
    through as a matmul (all of it but norms, the convolution, the
    per-head and per-channel vectors, the selection bias)."""
    s = _sizes(model)
    d = s["d"]
    conv_ch = 2 * s["key_w"] + s["val_w"]
    # q, k, v; the decay's projection; beta and the gate a head; out
    kda_mm = d * (conv_ch + s["key_w"] + 2 * s["H"]) + s["val_w"] * d
    mla_mm = (
        d * s["heads"] * s["qk"] + d * (s["latent"] + s["rope"])
        + s["latent"] * s["heads"] * (s["nope"] + s["vd"])
        + s["heads"] * s["vd"] * d
    )
    moe_mm = d * s["experts"] + 3 * d * s["fs"]
    return {
        # + convolution, A_log a head, dt_bias a channel, the gated norm
        "G": kda_mm + conv_ch * s["K"] + s["H"] + s["key_w"] + s["dv"] + d,
        # + the latent's norm, the two head norms
        "*": mla_mm + s["latent"] + 2 * s["qk"] + d,
        "-": 3 * d * s["fd"] + d,
        "E": moe_mm + s["experts"] + d,
        "expert": 3 * d * s["f"],
        "matmul": {
            "G": kda_mm, "*": mla_mm, "-": 3 * d * s["fd"], "E": moe_mm,
        },
    }


def scan_flops_per_token(model: dict) -> float:
    """Forward matmul operations of the chunked delta rule for one token
    of one layer, the least the chunked form needs
    (``flops_qwen3_next.scan_flops_per_token`` with a key head a value
    head). A head, in its chunk of C steps: the causal halves of ``K_beta
    K^T`` and ``Q K^T`` (2 C d_k / 2 each), the unit triangle's inverse by
    forward substitution (C^3 / 3 multiply-adds a chunk), the triangle's
    products ``T V_beta``, ``T K_beta`` and ``tril(Q K^T) V'`` (2 C d / 2
    each), and the three products with the [d_k, d_v] state (2 d_k d_v
    each). The vector decay adds no matmul: it multiplies operands
    elementwise (a few d_k a token and head), which is not counted."""
    s = _sizes(model)
    C, dk, dv = s["C"], s["dk"], s["dv"]
    a_head = 2 * C * dk + 2 * C * C / 3 + C * (2 * dv + dk) + 6 * dk * dv
    return float(s["H"] * a_head)


def attention_flops_per_token(model: dict, seq: int) -> float:
    """Forward operations of one latent attention layer's causal scores
    and values for one token of a ``seq`` long row: ``Q K^T`` over the
    stated score width, ``P V`` over the value width, half of each under
    the mask."""
    s = _sizes(model)
    return 2.0 * seq * s["heads"] * (s["qk"] + s["vd"]) / 2


def count(model: dict, seq: int) -> dict:
    """The hook's first function. ``params``: everything held here (the
    held experts, the rows of the vocabulary in ``vocab_size``, both
    tables). ``active_params``: what one token passes through here: all
    of it but the routed experts, of which ``moe_top_k * experts_held /
    num_experts`` (the balanced expectation). ``train_flops_per_token``:
    6 for each matmul parameter of those (the token table's lookup costs
    nothing, the head does), 3 x the scan's forward operations a KDA
    layer, 3 x the causal scores' and values' an attention layer.
    ``by_kind`` splits the last by layer kind and the head."""
    s = _sizes(model)
    p = layer_params(model)
    n = s["n"]
    tables = 2 * s["vocab"] * s["d"] + s["d"]
    outside = tables + sum(n[kind] * p[kind] for kind in KINDS)
    routed_here = s["k"] * s["held"] / s["experts"]
    by_kind = {
        "G": n["G"] * (
            6.0 * p["matmul"]["G"] + 3.0 * scan_flops_per_token(model)
        ),
        "*": n["*"] * (
            6.0 * p["matmul"]["*"]
            + 3.0 * attention_flops_per_token(model, seq)
        ),
        "-": n["-"] * 6.0 * p["matmul"]["-"],
        "E": n["E"] * 6.0 * (p["matmul"]["E"] + routed_here * p["expert"]),
        "head": 6.0 * s["d"] * s["vocab"],
    }
    return {
        "params": outside + n["E"] * s["held"] * p["expert"],
        "active_params": outside + n["E"] * routed_here * p["expert"],
        "train_flops_per_token": sum(by_kind.values()),
        "by_kind": by_kind,
    }


def attention_work(model: dict, batch: int, seq: int) -> dict:
    """One latent attention layer, forward + backward, as flash attention
    computes it at the STATED widths: the score matmuls (``Q K^T``
    forward, ``dQ`` and ``dK`` backward) contract or produce ``qk`` = 192,
    the value matmuls (``P V`` forward, ``dV`` and ``dP`` backward)
    ``vd`` = 128, each 2 T^2 wide a head, halved by the mask. Bytes:
    forward reads q, k (``qk``) and v and writes o (``vd``); backward
    reads q, k, v, do and writes dq, dk, dv. The padding to the kernels'
    one width is the program's choice and not counted."""
    s = _sizes(model)
    square = 2.0 * seq * seq * 0.5 * batch * s["heads"]
    token = batch * seq * s["heads"] * ACT_BYTES
    return {
        "flops": square * 3 * (s["qk"] + s["vd"]),
        "bytes": float(token * (
            (2 * s["qk"] + 2 * s["vd"]) + (4 * s["qk"] + 3 * s["vd"])
        )),
    }


def held_rows(model: dict, tokens: int) -> float:
    """Assignments that fall on the held experts of one layer when the
    routing is balanced."""
    s = _sizes(model)
    return tokens * s["k"] * s["held"] / s["experts"]


def experts_work(model: dict, tokens: int) -> dict:
    """One expert block's grouped matmuls, forward + backward
    (``flops_moe.grouped_matmul_work``): ``held_rows`` rows through the
    three projections of the ``experts_held`` matrices held here. The
    shared expert is a plain matmul and not counted."""
    s = _sizes(model)
    return grouped_matmul_work(
        {"model_dim": s["d"], "mlp_dim": s["f"], "swiglu": True,
         "moe_top_k": 1, "num_experts": s["held"]},
        held_rows(model, tokens),
    )


def scan_work(model: dict, tokens: int) -> dict:
    """One KDA layer's chunked scan, forward + backward: 3 x the forward
    operations; bytes: forward reads q, k, v (activation dtype), beta a
    head and g a head and key channel (float32) and writes o, backward
    reads them and do and writes dq, dk, dv, dbeta, dg."""
    s = _sizes(model)
    ins = (2 * s["key_w"] + s["val_w"]) * ACT_BYTES + 4 * (
        s["H"] + s["key_w"]
    )
    o = s["val_w"] * ACT_BYTES
    return {
        "flops": 3.0 * scan_flops_per_token(model) * tokens,
        "bytes": float(tokens * ((ins + o) + (ins + o + ins))),
    }


def step_work(model: dict, batch: int, seq: int) -> dict:
    """The hook's second function: each kind of kernel over the layers
    that run it. ``gdn_scan`` is a kind no reader asks for yet."""
    n = _sizes(model)["n"]

    def times(count, work):
        if not count:
            return None
        return {k: v * count for k, v in work.items()}

    return {
        "attention": times(n["*"], attention_work(model, batch, seq)),
        "grouped_matmul": times(n["E"], experts_work(model, batch * seq)),
        "gdn_scan": times(n["G"], scan_work(model, batch * seq)),
    }
