"""The family module (``"flops": "flops_moe"`` in a configuration) of the
models whose layers are all one sparse block: attention over ``num_heads``
heads of ``model_dim / num_heads``, then ``num_experts`` experts, all held
here, ``moe_top_k`` of them a token, gated where ``swiglu`` (OLMoE's
block). ``count`` and ``step_work`` are what ``run.py`` and the trace
readers ask; the rest are the per-layer pieces they are built on. A model
whose layers are not all alike, or that holds a share of its experts,
brings its own ``flops_<family>.py`` (see ``flops.py``).

``model`` is the ``model`` group of a configuration file (the fields of
``TransformerConfig``). Nothing here imports the program or JAX; of
``flops.py`` only ``step_work``, for the attention, which is the dense
block's (its ``n_params`` counts one ``mlp_dim`` wide MLP a layer).
"""

from flops import step_work as dense_step_work


def _layer_parts(model: dict) -> dict:
    d = model["model_dim"]
    h = model["num_heads"]
    kvh = model.get("num_kv_heads") or h
    hd = d // h
    f = model.get("mlp_dim") or 4 * d
    per_expert = (3 if model.get("swiglu") else 2) * d * f
    norms = 2 * (d if model.get("rmsnorm") else 2 * d)
    if model.get("qk_norm"):
        norms += (h + kvh) * hd
    return {
        "attention": d * h * hd + 2 * d * kvh * hd + h * hd * d,
        "router": d * model["num_experts"],
        "expert": per_expert,
        "norms": norms,
    }


def _every_layer_sparse(model: dict) -> None:
    if model.get("moe_every", 2) != 1:
        raise ValueError("flops_moe counts models whose every layer is sparse")


def n_params(model: dict) -> dict:
    """``total`` parameters as ``init_params`` builds them with every
    layer sparse (``moe_every`` 1), and those ``active`` for one token:
    its ``moe_top_k`` experts of each layer and everything that is not an
    expert."""
    _every_layer_sparse(model)
    d, v = model["model_dim"], model["vocab_size"]
    p = _layer_parts(model)
    outside = v * d + (d if model.get("rmsnorm") else 2 * d)
    if not model.get("rope"):
        outside += model["max_seq_len"] * d
    if not model.get("tie_embeddings", True):
        outside += d * v
    shared = p["attention"] + p["router"] + p["norms"]
    layers = model["num_layers"]
    return {
        "total": outside + layers * (
            shared + model["num_experts"] * p["expert"]),
        "active": outside + layers * (
            shared + model["moe_top_k"] * p["expert"]),
    }


def train_flops_per_token(model: dict, seq: int) -> float:
    """Forward + backward operations one token needs: 6 for each matmul
    parameter it passes through (attention, router, its ``moe_top_k``
    experts, the output head; the token table's lookup costs nothing)
    plus causal attention's score and value matmuls, 12 * L * T * d for
    the whole square and half of it under the mask. Recomputed
    operations are not counted."""
    d, v = model["model_dim"], model["vocab_size"]
    p = _layer_parts(model)
    matmul = model["num_layers"] * (
        p["attention"] + p["router"] + model["moe_top_k"] * p["expert"]
    ) + d * v
    attn = 12.0 * model["num_layers"] * seq * d / 2
    return 6.0 * matmul + attn


def grouped_matmul_work(model: dict, tokens: int, act_bytes: int = 2) -> dict:
    """What one layer's grouped expert matmuls need for ``tokens``
    tokens, forward + backward.

    Operations: the tokens make A = tokens * moe_top_k assignments, each
    a row through its expert's projections (gate, up, down where the
    experts are gated; up, down where not), [A, d] x [d, f] or the
    transpose: 2 * A * d * f a projection forward, and twice that
    backward (the row's gradient and the weight's). Bytes: each of the
    three matmuls of a projection reads or writes the [A, d] side, the
    [A, f] side and all E [d, f] matrices once, in the compute dtype.
    The forward that recomputation runs again is the program's choice
    and is not counted."""
    d = model["model_dim"]
    f = model.get("mlp_dim") or 4 * d
    projections = 3 if model.get("swiglu") else 2
    rows = tokens * model["moe_top_k"]
    flops = projections * 3 * 2.0 * rows * d * f
    moved = rows * d + rows * f + model["num_experts"] * d * f
    return {
        "flops": flops,
        "bytes": float(projections * 3 * moved * act_bytes),
    }


def count(model: dict, seq: int) -> dict:
    """The hook's first function (``flops.count``): every parameter held,
    those one token passes through, and its forward + backward
    operations in a ``seq`` long row."""
    n = n_params(model)
    return {
        "params": n["total"],
        "active_params": n["active"],
        "train_flops_per_token": train_flops_per_token(model, seq),
    }


def step_work(model: dict, batch: int, seq: int) -> dict:
    """The hook's second function (``flops.step_work``): every layer runs
    the one attention and the grouped matmuls of all its experts."""
    _every_layer_sparse(model)
    layer = grouped_matmul_work(model, batch * seq)
    return dict(
        dense_step_work(model, batch, seq),
        grouped_matmul={
            k: v * model["num_layers"] for k, v in layer.items()
        },
    )
