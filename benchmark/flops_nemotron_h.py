"""The family module (``"flops": "flops_nemotron_h"`` in a configuration)
of the models whose layers are ONE mixer each, of a kind the ``model``
group's ``layer_pattern`` names a layer (``nemotron_h``'s alphabet): ``M``
a Mamba-2 layer, ``*`` an attention layer of ``num_heads`` query and
``num_kv_heads`` key/value heads of the stated ``attn_head_dim``, ``E``
``num_experts`` ungated experts of ``mlp_dim`` beside one shared expert of
``shared_expert_dim``, ``moe_top_k`` a token, of which this chip holds
``experts_held``. ``count`` and ``step_work`` are what ``run.py`` and the
trace readers ask (``flops.py``); each layer kind is counted once a layer
of its kind and no other layer is.

**The share.** Everything here is what THIS chip holds and runs: of the
routed experts the ``experts_held`` matrices, and of a token's
``moe_top_k`` assignments the ``experts_held / num_experts`` that fall on
them when the routing is balanced. That expectation is the best the module
can do from ``model``, ``batch`` and ``seq``: the rows a step really sends
to the held experts depend on the router's weights and the batch (the
program reports them: ``moe.held_share_pct``). Cold held experts mean
fewer rows than counted here, and ``kernel.moe_gmm_roofline`` then reads
high; hot ones, low.

``model`` is the ``model`` group of a configuration file (the fields of
``TransformerConfig``). Nothing here imports the program or JAX; of
``flops.py`` and ``flops_moe.py`` only the per-layer pieces.
"""

from flops import attention_kernel_work
from flops_moe import grouped_matmul_work

ACT_BYTES = 2


def _sizes(model: dict) -> dict:
    d = model["model_dim"]
    heads, hd = model["num_heads"], model["attn_head_dim"]
    kv = model.get("num_kv_heads") or heads
    H, P = model["ssm_heads"], model["ssm_head_dim"]
    G, N = model["ssm_groups"], model["ssm_state"]
    experts = model["num_experts"]
    pattern = model["layer_pattern"]
    if len(pattern) != model["num_layers"] or set(pattern) - set("M*E"):
        raise ValueError(f"layer_pattern {pattern!r} is not the layers")
    return {
        "d": d, "heads": heads, "kv": kv, "hd": hd, "H": H, "P": P,
        "G": G, "N": N, "d_in": H * P, "conv_ch": H * P + 2 * G * N,
        "K": model.get("ssm_conv", 4), "Q": model.get("ssm_chunk", 128),
        "f": model["mlp_dim"], "fs": model["shared_expert_dim"],
        "experts": experts, "held": model.get("experts_held") or experts,
        "k": model["moe_top_k"], "vocab": model["vocab_size"],
        "n": {kind: pattern.count(kind) for kind in "M*E"},
    }


def layer_params(model: dict) -> dict:
    """Parameters of one layer of each kind, its one norm included, and
    of one routed expert; ``matmul`` the part of each a token passes
    through as a matmul (all of it but norms, the convolution, the
    per-head scalars and the selection bias)."""
    s = _sizes(model)
    d, d_in = s["d"], s["d_in"]
    ssm_mm = d * (d_in + s["conv_ch"] + s["H"]) + d_in * d
    attn_mm = 2 * d * s["heads"] * s["hd"] + 2 * d * s["kv"] * s["hd"]
    moe_mm = d * s["experts"] + 2 * d * s["fs"]
    return {
        "M": ssm_mm + s["conv_ch"] * (s["K"] + 1) + 3 * s["H"] + d_in + d,
        "*": attn_mm + d,
        "E": moe_mm + s["experts"] + d,
        "expert": 2 * d * s["f"],
        "matmul": {"M": ssm_mm, "*": attn_mm, "E": moe_mm},
    }


def scan_flops_per_token(model: dict) -> float:
    """Forward operations of the chunked state-space scan for one token
    of one Mamba-2 layer, the least the chunked form needs: inside its
    chunk of Q steps the causal half of ``C B^T`` (2 Q G N / 2) and of
    ``(C B^T * L) x`` (2 Q H P / 2), its share of the chunk's end state
    (``x (outer) B``: 2 H P N) and its reading of the entering state
    (``S C``: 2 H P N)."""
    s = _sizes(model)
    return float(
        s["Q"] * (s["G"] * s["N"] + s["H"] * s["P"])
        + 4 * s["H"] * s["P"] * s["N"]
    )


def count(model: dict, seq: int) -> dict:
    """The hook's first function. ``params``: everything held here (the
    held experts, the rows of the vocabulary in ``vocab_size``, both
    tables). ``active_params``: what one token passes through here: all
    of it but the routed experts, of which ``moe_top_k * experts_held /
    num_experts`` (the balanced expectation; the module's docstring).
    ``train_flops_per_token``: 6 for each matmul parameter of those (the
    token table's lookup costs nothing, the head does), 3 x the scan's
    forward operations a Mamba-2 layer, and causal attention's score and
    value matmuls a attention layer, 12 * T * heads * head_dim for the
    whole square and half of it under the mask. ``by_kind`` splits the
    last by layer kind and the head."""
    s = _sizes(model)
    p = layer_params(model)
    n = s["n"]
    tables = 2 * s["vocab"] * s["d"] + s["d"]
    outside = tables + sum(n[kind] * p[kind] for kind in "M*E")
    routed_here = s["k"] * s["held"] / s["experts"]
    by_kind = {
        "M": n["M"] * (
            6.0 * p["matmul"]["M"] + 3.0 * scan_flops_per_token(model)
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
    head width; the bytes with the key and value tensors at their own
    (fewer) heads: q, o forward and q, do, dq backward are query-sized,
    k, v forward and k, v, dk, dv backward key/value-sized."""
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
    """One expert layer's grouped matmuls, forward + backward
    (``flops_moe.grouped_matmul_work``): ``held_rows`` rows through the
    two projections of the ``experts_held`` matrices held here. The
    shared expert is a plain matmul and not counted."""
    s = _sizes(model)
    return grouped_matmul_work(
        {"model_dim": s["d"], "mlp_dim": s["f"], "swiglu": False,
         "moe_top_k": 1, "num_experts": s["held"]},
        held_rows(model, tokens),
    )


def scan_work(model: dict, tokens: int) -> dict:
    """One Mamba-2 layer's scan, forward + backward: 3 x the forward
    operations; bytes: forward reads x, B, C (activation dtype) and dt
    (float32) and writes y, backward reads them and dy and writes dx,
    dB, dC, ddt."""
    s = _sizes(model)
    xbc = (s["d_in"] + 2 * s["G"] * s["N"]) * ACT_BYTES + 4 * s["H"]
    y = s["d_in"] * ACT_BYTES
    return {
        "flops": 3.0 * scan_flops_per_token(model) * tokens,
        "bytes": float(tokens * ((xbc + y) + (xbc + y + xbc))),
    }


def step_work(model: dict, batch: int, seq: int) -> dict:
    """The hook's second function: each kind of kernel over the layers
    that run it. ``ssm_scan`` is a kind no reader asks for yet."""
    n = _sizes(model)["n"]

    def times(count, work):
        if not count:
            return None
        return {k: v * count for k, v in work.items()}

    return {
        "attention": times(n["*"], attention_work(model, batch, seq)),
        "grouped_matmul": times(n["E"], experts_work(model, batch * seq)),
        "ssm_scan": times(n["M"], scan_work(model, batch * seq)),
    }
