"""The family module (``"flops": "flops_ouro"`` in a configuration) of the
looped language models whose ``layer_pattern`` is blocks of ``*`` a causal
attention layer (``num_heads`` query heads on ``num_kv_heads`` key/value
heads of ``attn_head_dim``, rotary, no bias, no QK-norm, no window) and
``-`` a dense SwiGLU feed-forward of ``dense_mlp_dim``, every layer between
two RMSNorms (``mixer_out_norm``), and whose whole stack is applied
``ut_steps`` times to the residual stream over the same weights, the one
final norm after every pass, an exit through the one untied head and a
gate of ``model_dim + 1`` parameters after each (``ouro``, Ouro-2.6B's,
arXiv:2510.25741: a published layer is two entries, attention then
feed-forward). ``count`` and ``step_work`` are what ``run.py`` and the
trace readers ask (``flops.py``).

**The loop.** A weight is held once and used ``ut_steps`` times a step:
``params`` counts it once, ``train_flops_per_token`` counts every matmul
parameter but the token table ``ut_steps`` times (the lookup costs nothing
and happens once; the head runs after every pass), and ``step_work`` counts
``ut_steps`` attention kernels a layer. A module that counted ``6 N`` would
read a looped model's MFU at a quarter of what the chip does.

``model`` is the ``model`` group of a configuration file (the fields of
``TransformerConfig``). Nothing here imports the program or JAX; of
``flops.py`` only the per-layer pieces.
"""

from flops import attention_kernel_work

KINDS = "*-"


def _sizes(model: dict) -> dict:
    pattern = model["layer_pattern"]
    if len(pattern) != model["num_layers"] or set(pattern) - set(KINDS):
        raise ValueError(f"layer_pattern {pattern!r} is not the layers")
    if not model.get("mixer_out_norm") or not model.get("rmsnorm"):
        raise ValueError("the family's layers lie between two RMSNorms")
    if model.get("tie_embeddings", True) or not model.get("swiglu"):
        raise ValueError("the family has an untied head and SwiGLU")
    if model.get("ut_steps", 1) < 2:
        raise ValueError("the family loops: ut_steps is 2 or more")
    heads = model["num_heads"]
    return {
        "d": model["model_dim"], "heads": heads,
        "kv": model.get("num_kv_heads") or heads,
        "hd": model["attn_head_dim"], "fd": model["dense_mlp_dim"],
        "vocab": model["vocab_size"], "passes": model["ut_steps"],
        "n": {kind: pattern.count(kind) for kind in KINDS},
    }


def layer_params(model: dict) -> dict:
    """Parameters of one layer of each kind, its two norms included;
    ``matmul`` the part of each a token passes through as a matmul."""
    s = _sizes(model)
    d = s["d"]
    attn_mm = (
        d * (s["heads"] + 2 * s["kv"]) * s["hd"] + s["heads"] * s["hd"] * d
    )
    mlp_mm = 3 * d * s["fd"]
    return {
        "*": attn_mm + 2 * d, "-": mlp_mm + 2 * d,
        "matmul": {"*": attn_mm, "-": mlp_mm},
    }


def attention_flops_per_token(model: dict, seq: int) -> float:
    """Forward operations of one attention layer's scores and values for
    one token of a ``seq`` long row in one pass: ``Q K^T`` and ``P V``
    over the causal half."""
    s = _sizes(model)
    return 2 * 2.0 * s["heads"] * s["hd"] * seq / 2.0


def count(model: dict, seq: int) -> dict:
    """The hook's first function. ``params``: everything held here, once
    (the layers, both tables, the final norm, the exit gate's
    ``model_dim + 1``); ``active_params`` the same (a dense model).
    ``train_flops_per_token``: 6 for each matmul parameter of the layers
    and of the head and 3 x the scores' and values' forward operations an
    attention layer, all of it ``ut_steps`` times (module docstring).
    ``by_kind`` splits it by layer kind and the head."""
    s = _sizes(model)
    p = layer_params(model)
    n, passes = s["n"], s["passes"]
    params = (
        sum(n[kind] * p[kind] for kind in KINDS)
        + 2 * s["vocab"] * s["d"] + s["d"] + s["d"] + 1
    )
    by_kind = {
        "*": passes * n["*"] * (
            6.0 * p["matmul"]["*"]
            + 3.0 * attention_flops_per_token(model, seq)
        ),
        "-": passes * n["-"] * 6.0 * p["matmul"]["-"],
        "head": passes * 6.0 * s["d"] * s["vocab"],
    }
    return {
        "params": params,
        "active_params": params,
        "train_flops_per_token": sum(by_kind.values()),
        "by_kind": by_kind,
    }


def step_work(model: dict, batch: int, seq: int) -> dict:
    """The hook's second function. ``attention``: every attention layer
    once a pass, ``ut_steps`` x layers of ``flops.attention_kernel_work``
    at the layer's own heads and head width, causal, forward + backward;
    no grouped matmul."""
    s = _sizes(model)
    one = attention_kernel_work(batch, s["heads"], seq, s["hd"])
    runs = s["passes"] * s["n"]["*"]
    return {
        "attention": {k: v * runs for k, v in one.items()} if runs else None,
        "grouped_matmul": None,
    }
