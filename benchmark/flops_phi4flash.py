"""The family module (``"flops": "flops_phi4flash"`` in a configuration) of
the models whose ``layer_pattern`` names ONE mixer a layer in the alphabet
``S`` a Mamba-1 selective-scan layer (``sscan_inner`` channels with
``sscan_state`` states each, a step through a bottleneck of
``sscan_dt_rank``, ``sscan_conv`` taps), ``W`` differential attention
(``num_heads`` query heads on ``num_kv_heads`` key/value heads of
``attn_head_dim``, in pairs, a key pair's value twice as wide; biases on
every projection) through a window of ``attn_window`` keys, ``*`` the same
layer seeing every key before it, ``U`` a gated memory unit as wide as the
scan it reads, ``C`` a differential cross-attention that projects queries
only, ``-`` a dense SwiGLU feed-forward of ``dense_mlp_dim`` without bias;
every layer behind one LayerNorm with weight and bias, one tied table
(``phi4flash``, Phi-4-mini-flash-reasoning's: a published layer is two
entries, mixer then feed-forward). ``count`` and ``step_work`` are what
``run.py`` and the trace readers ask (``flops.py``); each layer kind is
counted once a layer of its kind, at its own widths, and no other layer is.

**Differential attention.** A pair computes two score maps over ``hd``
wide queries and keys and applies each to a value ``2 hd`` wide: forward
``2 (2 hd + 2 * 2 hd) = 12 hd`` operations a visible (query, key) pair and
head pair, 1.5 times what two plain heads of ``hd`` need. A call that pads
its scores to the values' width does more; that is the program's cost and
shows in ``kernel.attn_roofline``. The bytes are a plain layer's of
``num_heads`` heads of ``hd`` (q, the pairs' outputs and their gradients
are that wide; k and v are narrower and counted as wide, as
``flops.attention_kernel_work`` counts a grouped-query layer).

**The window**, as ``flops_afmoe.py`` has it: a window layer's least work
is over the pairs a query can see, ``T W - W (W - 1) / 2`` a head where ``W
< T``; ``step_work`` gives the window layers alone a key of their own,
``attention_window``, beside ``attention`` (window, full and cross layers
together).

**The scan** (``selective_scan``, what ``kernel.sscan_roofline`` asks
for): the recurrence's least arithmetic and bytes, forward and backward.
An element ``(t, c, n)`` costs 7 operations forward (``dt A``, its ``exp``,
``decay S``, ``(dt x) B``, the sum, ``C S``, and its sum over ``n``) and
twice that backward: 21. The bytes are what must cross HBM a token and
channel: forward ``x`` in and ``y`` out in the activation dtype, ``dt`` in
float32 (8 bytes); backward ``x``, ``dt`` and ``dy`` in, ``dx`` and ``d
dt`` out (14); ``B``, ``C`` and their gradients are ``N`` a token and
added. By the published peaks the bound is HBM (22 bytes against 21 x 16
operations a token and channel: 0.45 us against 0.03 us at 5120 x 16), so
a kernel bound by the vector unit reads a low share.

``model`` is the ``model`` group of a configuration file (the fields of
``TransformerConfig``). Nothing here imports the program or JAX; of
``flops.py`` only the per-layer pieces.
"""

from flops import attention_kernel_work

KINDS = "SW*UC-"
SCAN_FLOPS_PER_ELEMENT = 21.0


def _sizes(model: dict) -> dict:
    pattern = model["layer_pattern"]
    if len(pattern) != model["num_layers"] or set(pattern) - set(KINDS):
        raise ValueError(f"layer_pattern {pattern!r} is not the layers")
    window = model.get("attn_window") or 0
    if ("W" in pattern) != (window > 0):
        raise ValueError(f"attn_window {window} and pattern {pattern!r}")
    if model.get("attn_kind") != "diff" or not model.get("attn_bias"):
        raise ValueError("the family's attention is differential, biased")
    if model.get("rmsnorm") or not model.get("tie_embeddings", True):
        raise ValueError("the family has LayerNorms and one tied table")
    heads = model["num_heads"]
    return {
        "d": model["model_dim"], "heads": heads,
        "kv": model.get("num_kv_heads") or heads,
        "hd": model["attn_head_dim"], "window": window,
        "d_in": model["sscan_inner"], "N": model.get("sscan_state", 16),
        "R": model["sscan_dt_rank"], "K": model.get("sscan_conv", 4),
        "fd": model["dense_mlp_dim"], "vocab": model["vocab_size"],
        "n": {kind: pattern.count(kind) for kind in KINDS},
    }


def layer_params(model: dict) -> dict:
    """Parameters of one layer of each kind, its LayerNorm included;
    ``matmul`` the part of each a token passes through as a matmul (all of
    it but norms, biases, the convolution, ``A_log``, ``D``, the
    ``lambda`` vectors and the pair norm)."""
    s = _sizes(model)
    d, hd, d_in = s["d"], s["hd"], s["d_in"]
    norm = 2 * d
    q = d * s["heads"] * hd
    kv = 2 * d * s["kv"] * hd
    o = s["heads"] * hd * d
    differential = 4 * hd + 2 * hd  # four lambda vectors, the pair norm
    scan_mm = (
        2 * d * d_in + d_in * (s["R"] + 2 * s["N"]) + s["R"] * d_in
        + d_in * d
    )
    scan_rest = (
        s["K"] * d_in + d_in  # the convolution and its bias
        + d_in  # dt_bias
        + d_in * s["N"] + d_in  # A_log, D
    )
    attn = (
        q + kv + o + (s["heads"] + 2 * s["kv"]) * hd + d + differential
        + norm
    )
    return {
        "S": scan_mm + scan_rest + norm,
        "W": attn, "*": attn,
        "U": 2 * d * d_in + norm,
        "C": q + o + s["heads"] * hd + d + differential + norm,
        "-": 3 * d * s["fd"] + norm,
        "matmul": {
            "S": scan_mm, "W": q + kv + o, "*": q + kv + o,
            "U": 2 * d * d_in, "C": q + o, "-": 3 * d * s["fd"],
        },
    }


def visible_pairs(seq: int, window: int) -> float:
    """(query, key) pairs of one sequence and head that a causal layer
    sees (``flops_afmoe.visible_pairs``): through a window ``T W - W (W -
    1) / 2``, without one the ``T^2 / 2`` that
    ``flops.attention_kernel_work`` counts of a causal layer."""
    if not window:
        return seq * seq / 2.0
    w = min(window, seq)
    return seq * w - w * (w - 1) / 2.0


def attention_flops_per_token(model: dict, seq: int, window: int) -> float:
    """Forward operations of one differential attention layer's scores
    and values for one token of a ``seq`` long row: two score maps a pair
    over ``hd``, each applied to a value ``2 hd`` wide."""
    s = _sizes(model)
    pairs = s["heads"] // 2
    return 12.0 * s["hd"] * pairs * visible_pairs(seq, window) / seq


def scan_flops_per_token(model: dict) -> float:
    """Forward + backward operations of one scan layer's recurrence for
    one token (element-wise: the vector unit's, not the MXU's)."""
    s = _sizes(model)
    return SCAN_FLOPS_PER_ELEMENT * s["d_in"] * s["N"]


def count(model: dict, seq: int) -> dict:
    """The hook's first function. ``params``: everything held here (the
    rows of the vocabulary in ``vocab_size``, the one tied table);
    ``active_params`` the same (a dense model). ``train_flops_per_token``:
    6 for each matmul parameter (the tied table once: the lookup costs
    nothing, the head does), 3 x the scores' and values' forward
    operations an attention layer over the pairs it sees, and the scans'
    element-wise recurrence. ``by_kind`` splits it by layer kind and the
    head."""
    s = _sizes(model)
    p = layer_params(model)
    n = s["n"]
    params = s["vocab"] * s["d"] + 2 * s["d"] + sum(
        n[kind] * p[kind] for kind in KINDS
    )

    def attention(kind, window):
        return n[kind] * (
            6.0 * p["matmul"][kind]
            + 3.0 * attention_flops_per_token(model, seq, window)
        )

    by_kind = {
        "S": n["S"] * (6.0 * p["matmul"]["S"] + scan_flops_per_token(model)),
        "W": attention("W", s["window"]),
        "*": attention("*", 0),
        "U": n["U"] * 6.0 * p["matmul"]["U"],
        "C": attention("C", 0),
        "-": n["-"] * 6.0 * p["matmul"]["-"],
        "head": 6.0 * s["d"] * s["vocab"],
    }
    return {
        "params": params,
        "active_params": params,
        "train_flops_per_token": sum(by_kind.values()),
        "by_kind": by_kind,
    }


def attention_work(model: dict, batch: int, seq: int, window: int) -> dict:
    """One differential attention layer, forward + backward, as flash
    attention computes it: 1.5 times the six matmuls of
    ``flops.attention_kernel_work`` at ``num_heads`` heads of ``hd`` (a
    value pair is twice as wide), over the pairs the layer sees, and that
    layer's bytes."""
    s = _sizes(model)
    causal = attention_kernel_work(batch, s["heads"], seq, s["hd"])
    seen = visible_pairs(seq, window) / visible_pairs(seq, 0)
    return {"flops": 1.5 * causal["flops"] * seen, "bytes": causal["bytes"]}


def selective_scan_work(model: dict, batch: int, seq: int,
                        act_bytes: int = 2) -> dict:
    """One scan layer's recurrence, forward + backward (module
    docstring)."""
    s = _sizes(model)
    tokens = batch * seq
    a, f = act_bytes, 4
    a_channel = (a + f + a) + (a + f + a + a + f)  # fwd, bwd
    a_state = 2 * a + 4 * a  # B, C in twice; their gradients out
    return {
        "flops": SCAN_FLOPS_PER_ELEMENT * tokens * s["d_in"] * s["N"],
        "bytes": float(
            tokens * (s["d_in"] * a_channel + s["N"] * a_state)
        ),
    }


def step_work(model: dict, batch: int, seq: int) -> dict:
    """The hook's second function: each kind of kernel over the layers
    that run it. ``attention`` is every attention layer, the window layers
    at the pairs they see and the full and cross layers at the causal
    half; ``attention_window`` the window layers alone
    (``kernel.attn_window_roofline``); ``selective_scan`` the scan layers'
    recurrences (``kernel.sscan_roofline``); no grouped matmul."""
    s = _sizes(model)
    n = s["n"]

    def times(count, work):
        if not count:
            return None
        return {k: v * count for k, v in work.items()}

    window = times(
        n["W"], attention_work(model, batch, seq, s["window"])
    )
    full = times(n["*"] + n["C"], attention_work(model, batch, seq, 0))
    both = [w for w in (window, full) if w]
    return {
        "attention": {
            k: sum(w[k] for w in both) for k in ("flops", "bytes")
        } if both else None,
        "attention_window": window,
        "grouped_matmul": None,
        "selective_scan": times(
            n["S"], selective_scan_work(model, batch, seq)
        ),
    }
