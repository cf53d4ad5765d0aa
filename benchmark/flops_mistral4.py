"""The family module (``"flops": "flops_mistral4"`` in a configuration) of
the models whose ``layer_pattern`` names ONE mixer a layer in the alphabet
``*`` a latent attention layer (MLA: ``num_heads`` heads whose query comes
through a latent of ``q_latent_dim`` and whose keys and values come from a
latent of ``kv_latent_dim`` beside one rotated key of ``qk_rope_dim``;
scores ``qk_nope_dim + qk_rope_dim`` wide, values ``v_head_dim``) and ``E``
``num_experts`` SwiGLU experts of ``mlp_dim`` beside one ungated SwiGLU
shared expert of ``shared_expert_dim``, ``moe_top_k`` a token, of which
this chip holds ``experts_held`` (``mistral4``, Mistral-Small-4's: a
published layer is two entries, attention then experts, and every layer is
alike). ``count`` and ``step_work`` are what ``run.py`` and the trace
readers ask (``flops.py``); each layer kind is counted once a layer of its
kind, at its own widths, and no other layer is.

**The share**, as ``flops_afmoe.py`` has it: everything here is what THIS
chip holds and runs: of the routed experts the ``experts_held`` matrices,
and of a token's ``moe_top_k`` assignments the ``experts_held /
num_experts`` that fall on them when the routing is balanced (the program
reports what really fell on them: ``moe.held_share_pct``).

**The attention kernel's width.** Scores contract ``qk_nope_dim +
qk_rope_dim`` and values are ``v_head_dim`` wide; ``step_work`` counts the
score matmuls at the one and the value matmuls at the other, which at
Mistral-Small-4's 64 + 64 / 128 is ``flops.attention_kernel_work`` at 128.

``model`` is the ``model`` group of a configuration file (the fields of
``TransformerConfig``). Nothing here imports the program or JAX; of
``flops.py`` and ``flops_moe.py`` only the per-layer pieces.
"""

from flops import attention_kernel_work
from flops_moe import grouped_matmul_work

KINDS = "*E"


def _sizes(model: dict) -> dict:
    pattern = model["layer_pattern"]
    if len(pattern) != model["num_layers"] or set(pattern) - set(KINDS):
        raise ValueError(f"layer_pattern {pattern!r} is not the layers")
    if model.get("attn_kind") != "latent" or not model.get("q_latent_dim"):
        raise ValueError("flops_mistral4 counts a latent attention whose "
                         "query passes a latent of its own")
    experts = model["num_experts"]
    return {
        "d": model["model_dim"], "heads": model["num_heads"],
        "rq": model["q_latent_dim"], "rkv": model["kv_latent_dim"],
        "nope": model["qk_nope_dim"], "rope": model["qk_rope_dim"],
        "vd": model["v_head_dim"],
        "f": model["mlp_dim"], "fs": model["shared_expert_dim"],
        "experts": experts, "held": model.get("experts_held") or experts,
        "k": model["moe_top_k"], "vocab": model["vocab_size"],
        "n": {kind: pattern.count(kind) for kind in KINDS},
    }


def layer_params(model: dict) -> dict:
    """Parameters of one layer of each kind, its norm included, and of one
    routed expert; ``matmul`` the part of each a token passes through as a
    matmul (all of it but the norms)."""
    s = _sizes(model)
    d, h, qk = s["d"], s["heads"], s["nope"] + s["rope"]
    attn_mm = (
        d * s["rq"] + s["rq"] * h * qk            # w_qa, w_qb
        + d * (s["rkv"] + s["rope"])              # w_kva
        + s["rkv"] * h * (s["nope"] + s["vd"])    # w_kvb
        + h * s["vd"] * d                         # wo
    )
    moe_mm = d * s["experts"] + 3 * d * s["fs"]
    return {
        # + the two latents' norms and the layer's
        "*": attn_mm + s["rq"] + s["rkv"] + d,
        "E": moe_mm + d,
        "expert": 3 * d * s["f"],
        "matmul": {"*": attn_mm, "E": moe_mm},
    }


def attention_flops_per_token(model: dict, seq: int) -> float:
    """Forward operations of one attention layer's scores and values for
    one token of a ``seq`` long row: ``Q K^T`` over the score width and
    ``P V`` over the value width, the causal half."""
    s = _sizes(model)
    return 2.0 * s["heads"] * (s["nope"] + s["rope"] + s["vd"]) * seq / 2.0


def count(model: dict, seq: int) -> dict:
    """The hook's first function. ``params``: everything held here (the
    held experts, the rows of the vocabulary in ``vocab_size``, both
    tables). ``active_params``: what one token passes through here: all
    of it but the routed experts, of which ``moe_top_k * experts_held /
    num_experts`` (the balanced expectation). ``train_flops_per_token``:
    6 for each matmul parameter of those (the token table's lookup costs
    nothing, the head does), 3 x the scores' and values' forward
    operations an attention layer. ``by_kind`` splits the last: the
    attention's scores and values, its projections, the shared experts,
    the routers, the held experts' rows and the head."""
    s = _sizes(model)
    p = layer_params(model)
    n = s["n"]
    tables = 2 * s["vocab"] * s["d"] + s["d"]
    outside = tables + sum(n[kind] * p[kind] for kind in KINDS)
    routed_here = s["k"] * s["held"] / s["experts"]
    by_kind = {
        "scores_values": n["*"] * 3.0 * attention_flops_per_token(model, seq),
        "projections": n["*"] * 6.0 * p["matmul"]["*"],
        "shared": n["E"] * 6.0 * 3 * s["d"] * s["fs"],
        "router": n["E"] * 6.0 * s["d"] * s["experts"],
        "held_experts": n["E"] * 6.0 * routed_here * p["expert"],
        "head": 6.0 * s["d"] * s["vocab"],
    }
    return {
        "params": outside + n["E"] * s["held"] * p["expert"],
        "active_params": outside + n["E"] * routed_here * p["expert"],
        "train_flops_per_token": sum(by_kind.values()),
        "by_kind": by_kind,
    }


def attention_work(model: dict, batch: int, seq: int) -> dict:
    """One latent attention layer's kernel, forward + backward, as flash
    attention computes it: ``flops.attention_kernel_work`` with the score
    matmuls (``Q K^T``, ``dQ``, ``dK``) at the score width and the value
    matmuls (``P V``, ``dV``, ``dP``) at the value width, q and k moved at
    the one and v and o at the other."""
    s = _sizes(model)
    qk, vd = s["nope"] + s["rope"], s["vd"]
    scores = attention_kernel_work(batch, s["heads"], seq, qk)
    values = attention_kernel_work(batch, s["heads"], seq, vd)
    return {
        "flops": (scores["flops"] + values["flops"]) / 2.0,
        "bytes": (scores["bytes"] + values["bytes"]) / 2.0,
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


def step_work(model: dict, batch: int, seq: int) -> dict:
    """The hook's second function: each kind of kernel over the layers
    that run it."""
    n = _sizes(model)["n"]

    def times(count, work):
        if not count:
            return None
        return {k: v * count for k, v in work.items()}

    return {
        "attention": times(n["*"], attention_work(model, batch, seq)),
        "grouped_matmul": times(n["E"], experts_work(model, batch * seq)),
    }
