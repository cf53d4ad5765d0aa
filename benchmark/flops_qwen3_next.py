"""The family module (``"flops": "flops_qwen3_next"`` in a configuration)
of the models whose ``layer_pattern`` names ONE mixer a layer in the
alphabet ``G`` a Gated DeltaNet layer (``gdn_value_heads`` value and
``gdn_key_heads`` key heads of ``gdn_value_dim`` / ``gdn_key_dim``,
chunks of ``gdn_chunk``), ``*`` an attention layer of ``num_heads`` query
and ``num_kv_heads`` key/value heads of the stated ``attn_head_dim`` whose
query projection is twice as wide (the output gate), ``E`` ``num_experts``
SwiGLU experts of ``mlp_dim`` beside one gated SwiGLU shared expert of
``shared_expert_dim``, ``moe_top_k`` a token, of which this chip holds
``experts_held`` (``qwen3_next``: a published layer is two entries, mixer
then experts). ``count`` and ``step_work`` are what ``run.py`` and the
trace readers ask (``flops.py``); each layer kind is counted once a layer
of its kind, at its own widths, and no other layer is.

**The share**, as ``flops_nemotron_h.py`` has it: everything here is what
THIS chip holds and runs: of the routed experts the ``experts_held``
matrices, and of a token's ``moe_top_k`` assignments the ``experts_held /
num_experts`` that fall on them when the routing is balanced. That
expectation is the best the module can do from ``model``, ``batch`` and
``seq``; the rows a step really sends to the held experts depend on the
router's weights and the batch (the program reports them:
``moe.held_share_pct``). Cold held experts mean fewer rows than counted
here, and ``kernel.moe_gmm_roofline`` then reads high; hot ones, low.

``model`` is the ``model`` group of a configuration file (the fields of
``TransformerConfig``). Nothing here imports the program or JAX; of
``flops.py`` and ``flops_moe.py`` only the per-layer pieces.
"""

from flops import attention_kernel_work
from flops_moe import grouped_matmul_work

ACT_BYTES = 2
KINDS = "G*E"


def _sizes(model: dict) -> dict:
    pattern = model["layer_pattern"]
    if len(pattern) != model["num_layers"] or set(pattern) - set(KINDS):
        raise ValueError(f"layer_pattern {pattern!r} is not the layers")
    heads = model["num_heads"]
    experts = model["num_experts"]
    Hv, Hk = model["gdn_value_heads"], model["gdn_key_heads"]
    dk, dv = model["gdn_key_dim"], model["gdn_value_dim"]
    return {
        "d": model["model_dim"], "heads": heads,
        "kv": model.get("num_kv_heads") or heads,
        "hd": model["attn_head_dim"],
        "q_width": 2 if model.get("attn_gate") else 1,
        "Hv": Hv, "Hk": Hk, "dk": dk, "dv": dv,
        "conv_ch": 2 * Hk * dk + Hv * dv, "val_w": Hv * dv,
        "K": model.get("gdn_conv", 4), "C": model.get("gdn_chunk", 64),
        "f": model["mlp_dim"], "fs": model["shared_expert_dim"],
        "experts": experts, "held": model.get("experts_held") or experts,
        "k": model["moe_top_k"], "vocab": model["vocab_size"],
        "n": {kind: pattern.count(kind) for kind in KINDS},
    }


def layer_params(model: dict) -> dict:
    """Parameters of one layer of each kind, its one norm included, and
    of one routed expert; ``matmul`` the part of each a token passes
    through as a matmul (all of it but norms, the convolution, the
    per-head scalars)."""
    s = _sizes(model)
    d = s["d"]
    gdn_mm = d * (s["conv_ch"] + s["val_w"] + 2 * s["Hv"]) + s["val_w"] * d
    attn_mm = (
        (s["q_width"] + 1) * d * s["heads"] * s["hd"]
        + 2 * d * s["kv"] * s["hd"]
    )
    # router, the shared expert's three matrices and its gate's vector
    moe_mm = d * s["experts"] + 3 * d * s["fs"] + d
    return {
        "G": gdn_mm + s["conv_ch"] * s["K"] + 2 * s["Hv"] + s["dv"] + d,
        "*": attn_mm + 2 * s["hd"] + d,
        "E": moe_mm + d,
        "expert": 3 * d * s["f"],
        "matmul": {"G": gdn_mm, "*": attn_mm, "E": moe_mm},
    }


def scan_flops_per_token(model: dict) -> float:
    """Forward operations of the chunked gated delta rule for one token
    of one layer, the least the chunked form needs. In its chunk of C
    steps: the causal halves of ``K K^T`` and ``Q K^T`` a key head
    (2 C d_k / 2 each); a value head, the unit triangle's inverse by
    forward substitution (C^3 / 3 multiply-adds a chunk), the triangle's
    products ``T V_beta``, ``T K_beta`` and ``tril(Q K^T) V'`` (2 C d / 2
    each), and the three products with the [d_k, d_v] state (``W S`` and
    ``K^T V'`` in the pass, ``Q S`` in the read-out: 2 d_k d_v each)."""
    s = _sizes(model)
    C = s["C"]
    a_key_head = 2 * C * s["dk"]
    a_value_head = (
        2 * C * C / 3 + C * (2 * s["dv"] + s["dk"])
        + 6 * s["dk"] * s["dv"]
    )
    return float(s["Hk"] * a_key_head + s["Hv"] * a_value_head)


def count(model: dict, seq: int) -> dict:
    """The hook's first function. ``params``: everything held here (the
    held experts, the rows of the vocabulary in ``vocab_size``, both
    tables). ``active_params``: what one token passes through here: all
    of it but the routed experts, of which ``moe_top_k * experts_held /
    num_experts`` (the balanced expectation; the module's docstring).
    ``train_flops_per_token``: 6 for each matmul parameter of those (the
    token table's lookup costs nothing, the head does), 3 x the scan's
    forward operations a DeltaNet layer, and causal attention's score
    and value matmuls an attention layer, 12 * T * heads * head_dim for
    the whole square and half of it under the mask. ``by_kind`` splits
    the last by layer kind and the head."""
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
            + 12.0 * seq * s["heads"] * s["hd"] / 2
        ),
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
    """One attention layer, forward + backward: the operations of
    ``flops.attention_kernel_work`` at this layer's own head count and
    head width (q / k width = v width = ``attn_head_dim``; the gate is
    outside the kernel); the bytes with the key and value tensors at
    their own (fewer) heads: q, o forward and q, do, dq backward are
    query-sized, k, v forward and k, v, dk, dv backward key/value-sized."""
    s = _sizes(model)
    work = attention_kernel_work(batch, s["heads"], seq, s["hd"])
    token = batch * seq * s["hd"] * ACT_BYTES
    return {
        "flops": work["flops"],
        "bytes": float((5 * s["heads"] + 6 * s["kv"]) * token),
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
    """One DeltaNet layer's chunked scan, forward + backward: 3 x the
    forward operations; bytes: forward reads q, k, v (activation dtype)
    and beta, g (float32) and writes o, backward reads them and do and
    writes dq, dk, dv, dbeta, dg."""
    s = _sizes(model)
    qkv = s["conv_ch"] * ACT_BYTES + 2 * 4 * s["Hv"]
    o = s["val_w"] * ACT_BYTES
    return {
        "flops": 3.0 * scan_flops_per_token(model) * tokens,
        "bytes": float(tokens * ((qkv + o) + (qkv + o + qkv))),
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
