"""The family module (``"flops": "flops_afmoe"`` in a configuration) of the
models whose ``layer_pattern`` names ONE mixer a layer in the alphabet ``W``
a window attention layer (``num_heads`` query heads on ``num_kv_heads``
key/value heads of ``attn_head_dim``, the query projection twice as wide for
the output gate, a query seeing itself and the ``attn_window - 1`` keys
before it), ``*`` the same layer seeing every key before it, ``-`` a dense
SwiGLU feed-forward of ``dense_mlp_dim``, ``E`` ``num_experts`` SwiGLU
experts of ``mlp_dim`` beside one ungated SwiGLU shared expert of
``shared_expert_dim``, ``moe_top_k`` a token, of which this chip holds
``experts_held``; every layer between two norms (``afmoe``, Trinity-Mini's:
a published layer is two entries, attention then feed-forward). ``count``
and ``step_work`` are what ``run.py`` and the trace readers ask
(``flops.py``); each layer kind is counted once a layer of its kind, at its
own widths, and no other layer is.

**The window.** A window layer's least work is over the pairs a query can
see, ``T W - W (W - 1) / 2`` of them a head where ``W < T`` (the first ``W``
queries see fewer) and not the causal ``T^2 / 2``: blocks a window skips are
not work (``benchmark/README.md``), and the half-masked blocks on the band's
two edges are the kernels' cost, which ``kernel.attn_window_roofline``
therefore shows. ``step_work`` gives the window layers alone a key of their
own, ``attention_window``, beside ``attention`` (window and global layers
together).

**The share**, as ``flops_ling3.py`` has it: everything here is what THIS
chip holds and runs: of the routed experts the ``experts_held`` matrices,
and of a token's ``moe_top_k`` assignments the ``experts_held /
num_experts`` that fall on them when the routing is balanced (the program
reports what really fell on them: ``moe.held_share_pct``).

``model`` is the ``model`` group of a configuration file (the fields of
``TransformerConfig``). Nothing here imports the program or JAX; of
``flops.py`` and ``flops_moe.py`` only the per-layer pieces.
"""

from flops import attention_kernel_work
from flops_moe import grouped_matmul_work

KINDS = "W*-E"


def _sizes(model: dict) -> dict:
    pattern = model["layer_pattern"]
    if len(pattern) != model["num_layers"] or set(pattern) - set(KINDS):
        raise ValueError(f"layer_pattern {pattern!r} is not the layers")
    window = model.get("attn_window") or 0
    if ("W" in pattern) != (window > 0):
        raise ValueError(f"attn_window {window} and pattern {pattern!r}")
    experts = model["num_experts"]
    heads = model["num_heads"]
    return {
        "d": model["model_dim"], "heads": heads,
        "kv": model.get("num_kv_heads") or heads,
        "hd": model["attn_head_dim"], "window": window,
        "gate": 2 if model.get("attn_gate") else 1,
        "out_norm": 1 if model.get("mixer_out_norm") else 0,
        "f": model["mlp_dim"], "fd": model["dense_mlp_dim"],
        "fs": model["shared_expert_dim"],
        "experts": experts, "held": model.get("experts_held") or experts,
        "k": model["moe_top_k"], "vocab": model["vocab_size"],
        "n": {kind: pattern.count(kind) for kind in KINDS},
    }


def layer_params(model: dict) -> dict:
    """Parameters of one layer of each kind, its norms included, and of
    one routed expert; ``matmul`` the part of each a token passes through
    as a matmul (all of it but norms and the selection bias)."""
    s = _sizes(model)
    d = s["d"]
    norms = (1 + s["out_norm"]) * d
    attn_mm = (
        d * s["heads"] * s["hd"] * s["gate"] + 2 * d * s["kv"] * s["hd"]
        + s["heads"] * s["hd"] * d
    )
    moe_mm = d * s["experts"] + 3 * d * s["fs"]
    attn = attn_mm + 2 * s["hd"] + norms  # + the two head norms
    return {
        "W": attn, "*": attn,
        "-": 3 * d * s["fd"] + norms,
        "E": moe_mm + s["experts"] + norms,
        "expert": 3 * d * s["f"],
        "matmul": {
            "W": attn_mm, "*": attn_mm, "-": 3 * d * s["fd"], "E": moe_mm,
        },
    }


def visible_pairs(seq: int, window: int) -> float:
    """(query, key) pairs of one sequence and head that a causal layer
    sees: through a window of ``window`` keys ``T W - W (W - 1) / 2``
    (all ``T (T + 1) / 2`` where the window is as long as the row),
    without one (``window`` 0) the ``T^2 / 2`` that
    ``flops.attention_kernel_work`` counts of a causal layer."""
    if not window:
        return seq * seq / 2.0
    w = min(window, seq)
    return seq * w - w * (w - 1) / 2.0


def attention_flops_per_token(model: dict, seq: int, window: int) -> float:
    """Forward operations of one attention layer's scores and values for
    one token of a ``seq`` long row: ``Q K^T`` and ``P V`` over the pairs
    the layer sees."""
    s = _sizes(model)
    return 2 * 2.0 * s["heads"] * s["hd"] * visible_pairs(seq, window) / seq


def count(model: dict, seq: int) -> dict:
    """The hook's first function. ``params``: everything held here (the
    held experts, the rows of the vocabulary in ``vocab_size``, both
    tables). ``active_params``: what one token passes through here: all
    of it but the routed experts, of which ``moe_top_k * experts_held /
    num_experts`` (the balanced expectation). ``train_flops_per_token``:
    6 for each matmul parameter of those (the token table's lookup costs
    nothing, the head does), 3 x the scores' and values' forward
    operations an attention layer, over the pairs it sees. ``by_kind``
    splits the last by layer kind and the head."""
    s = _sizes(model)
    p = layer_params(model)
    n = s["n"]
    tables = 2 * s["vocab"] * s["d"] + s["d"]
    outside = tables + sum(n[kind] * p[kind] for kind in KINDS)
    routed_here = s["k"] * s["held"] / s["experts"]
    by_kind = {
        "W": n["W"] * (
            6.0 * p["matmul"]["W"]
            + 3.0 * attention_flops_per_token(model, seq, s["window"])
        ),
        "*": n["*"] * (
            6.0 * p["matmul"]["*"]
            + 3.0 * attention_flops_per_token(model, seq, 0)
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


def window_attention_work(model: dict, batch: int, seq: int) -> dict:
    """One window layer, forward + backward, as flash attention computes
    it: the six matmuls of ``flops.attention_kernel_work`` over the pairs
    the window lets a query see instead of the causal half, and the same
    bytes (q, k, v, o and their gradients are read and written whole,
    whatever is skipped between them)."""
    s = _sizes(model)
    causal = attention_kernel_work(batch, s["heads"], seq, s["hd"])
    seen = visible_pairs(seq, s["window"]) / visible_pairs(seq, 0)
    return {"flops": causal["flops"] * seen, "bytes": causal["bytes"]}


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
    that run it. ``attention`` is every attention layer, the window layers
    at the pairs they see and the global ones at the causal half;
    ``attention_window`` the window layers alone
    (``kernel.attn_window_roofline``)."""
    s = _sizes(model)
    n = s["n"]

    def times(count, work):
        if not count:
            return None
        return {k: v * count for k, v in work.items()}

    window = times(n["W"], window_attention_work(model, batch, seq))
    full = times(
        n["*"], attention_kernel_work(batch, s["heads"], seq, s["hd"])
    )
    both = [w for w in (window, full) if w]
    return {
        "attention": {
            k: sum(w[k] for w in both) for k in ("flops", "bytes")
        } if both else None,
        "attention_window": window,
        "grouped_matmul": times(n["E"], experts_work(model, batch * seq)),
    }
