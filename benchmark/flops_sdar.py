"""The family module (``"flops": "flops_sdar"`` in a configuration) of the
models TRAINED BY DIFFUSION OVER BLOCKS (``objective`` "block_diffusion";
SDAR's, ``model_type: sdar_moe``) whose ``layer_pattern`` names one mixer a
layer in the alphabet ``*`` an attention layer (``num_heads`` query heads
on ``num_kv_heads`` key/value heads of ``attn_head_dim``, a norm a head on
q and k) and ``E`` ``num_experts`` SwiGLU experts of ``mlp_dim``,
``moe_top_k`` a token, of which this chip holds ``experts_held``, no shared
expert; every layer behind one norm (a published layer is two entries,
attention then experts). ``count`` and ``step_work`` are what ``run.py``
and the trace readers ask (``flops.py``).

**The doubled row.** A row of ``seq`` = L data tokens is fed twice, its
noised copy before the clean one: every layer of the stack sees 2L
positions, the final norm and the head see the noised L alone, and a
head's attention sees ``L^2 + L B`` (query, key) pairs a row, ``B`` the
``diffusion_block``: a clean query the clean keys up to its own block's
end (``L (L + B) / 2`` pairs), a noised query the clean keys of the blocks
before its own (``L (L - B) / 2``) and the noised keys of its own block
(``L B``). **Everything here is counted a DATA token**: ``seq`` is the row
of data, ``run.py`` counts ``batch * seq`` tokens a step, and the noised
copy is work, never tokens.

**The share**, as ``flops_afmoe.py`` has it: of the routed experts the
``experts_held`` matrices, and of a position's ``moe_top_k`` assignments
the ``experts_held / num_experts`` that fall on them when the routing is
balanced (the program reports what really fell: ``moe.held_share_pct``).

``model`` is the ``model`` group of a configuration file (the fields of
``TransformerConfig``). Nothing here imports the program or JAX; of
``flops_moe.py`` only the per-layer piece.
"""

from flops_moe import grouped_matmul_work

KINDS = "*E"


def _sizes(model: dict) -> dict:
    pattern = model["layer_pattern"]
    if len(pattern) != model["num_layers"] or set(pattern) - set(KINDS):
        raise ValueError(f"layer_pattern {pattern!r} is not the layers")
    if model.get("objective") != "block_diffusion":
        raise ValueError(
            f"objective {model.get('objective')!r}: flops_sdar counts a "
            "row fed twice under the block-diffusion rule"
        )
    experts = model["num_experts"]
    heads = model["num_heads"]
    return {
        "d": model["model_dim"], "heads": heads,
        "kv": model.get("num_kv_heads") or heads,
        "hd": model["attn_head_dim"], "f": model["mlp_dim"],
        "experts": experts, "held": model.get("experts_held") or experts,
        "k": model["moe_top_k"], "vocab": model["vocab_size"],
        "block": model["diffusion_block"],
        "n": {kind: pattern.count(kind) for kind in KINDS},
    }


def layer_params(model: dict) -> dict:
    """Parameters of one layer of each kind, its norm included, and of one
    routed expert; ``matmul`` the part of each a position passes through
    as a matmul (all of it but the norms)."""
    s = _sizes(model)
    d = s["d"]
    attn_mm = (
        d * s["heads"] * s["hd"] + 2 * d * s["kv"] * s["hd"]
        + s["heads"] * s["hd"] * d
    )
    router = d * s["experts"]
    return {
        "*": attn_mm + 2 * s["hd"] + d,  # + the two head norms, the norm
        "E": router + d,
        "expert": 3 * d * s["f"],
        "matmul": {"*": attn_mm, "E": router},
    }


def visible_pairs(seq: int, block: int) -> int:
    """(query, key) pairs of one doubled row and head under the
    block-diffusion rule: ``L^2 + L B``."""
    if seq % block:
        raise ValueError(f"blocks of {block} do not divide a row of {seq}")
    return seq * seq + seq * block


def count(model: dict, seq: int) -> dict:
    """The hook's first function, a DATA token of a ``seq`` long row.
    ``params``: everything held here (the held experts, the rows of the
    vocabulary in ``vocab_size``, both tables). ``active_params``: what
    one position passes through here: all of it but the routed experts,
    of which ``moe_top_k * experts_held / num_experts``.
    ``train_flops_per_token``: three times a row's forward operations over
    its ``seq`` data tokens: 2 for each matmul parameter of the stack at
    each of the 2 ``seq`` positions, of the head at each of the ``seq``,
    and ``Q K^T`` and ``P V`` over the visible pairs. ``by_kind`` splits
    the last."""
    s = _sizes(model)
    p = layer_params(model)
    n = s["n"]
    tables = 2 * s["vocab"] * s["d"] + s["d"]
    outside = tables + sum(n[kind] * p[kind] for kind in KINDS)
    routed_here = s["k"] * s["held"] / s["experts"]
    pairs = visible_pairs(seq, s["block"])
    by_kind = {
        "projections": n["*"] * 2 * 6.0 * p["matmul"]["*"],
        "scores_values": (
            n["*"] * 3 * 2 * 2.0 * s["heads"] * s["hd"] * pairs / seq
        ),
        "routers": n["E"] * 2 * 6.0 * p["matmul"]["E"],
        "experts": n["E"] * 2 * 6.0 * routed_here * p["expert"],
        "head": 6.0 * s["d"] * s["vocab"],
    }
    return {
        "params": outside + n["E"] * s["held"] * p["expert"],
        "active_params": outside + n["E"] * routed_here * p["expert"],
        "train_flops_per_token": sum(by_kind.values()),
        "by_kind": by_kind,
    }


def block_diffusion_attention_work(model: dict, batch: int, seq: int,
                                   act_bytes: int = 2) -> dict:
    """One attention layer, forward + backward, the least the walk needs:
    the six matmuls of flash attention (``flops.attention_kernel_work``:
    ``Q K^T`` and ``P V`` forward, dV, dP, dQ and dK backward; the
    backward's second ``Q K^T`` is the kernel's choice) over the visible
    pairs, and q, k, v, o and their cotangents of the 2 ``seq`` positions
    read or written once, at the query heads' count as that function
    counts them."""
    s = _sizes(model)
    pairs = visible_pairs(seq, s["block"])
    tensor = batch * s["heads"] * 2 * seq * s["hd"] * act_bytes
    return {
        "flops": 6 * 2.0 * pairs * s["hd"] * batch * s["heads"],
        "bytes": float((4 + 7) * tensor),
    }


def held_rows(model: dict, tokens: int) -> float:
    """Assignments of ``tokens`` data tokens' 2 x ``tokens`` positions
    that fall on the held experts of one layer when the routing is
    balanced."""
    s = _sizes(model)
    return 2 * tokens * s["k"] * s["held"] / s["experts"]


def experts_work(model: dict, tokens: int) -> dict:
    """One expert layer's grouped matmuls, forward + backward
    (``flops_moe.grouped_matmul_work``): ``held_rows`` rows through the
    three projections of the ``experts_held`` matrices held here."""
    s = _sizes(model)
    return grouped_matmul_work(
        {"model_dim": s["d"], "mlp_dim": s["f"], "swiglu": True,
         "moe_top_k": 1, "num_experts": s["held"]},
        held_rows(model, tokens),
    )


def step_work(model: dict, batch: int, seq: int) -> dict:
    """The hook's second function: each kind of kernel over the layers
    that run it. Every attention layer runs under the block-diffusion
    rule, so ``attention`` is ``attention_block_diffusion``
    (``kernel.attn_bd_roofline`` asks for the latter by name)."""
    n = _sizes(model)["n"]

    def times(count, work):
        if not count:
            return None
        return {k: v * count for k, v in work.items()}

    walk = times(n["*"], block_diffusion_attention_work(model, batch, seq))
    return {
        "attention": walk,
        "attention_block_diffusion": walk,
        "grouped_matmul": times(n["E"], experts_work(model, batch * seq)),
    }
