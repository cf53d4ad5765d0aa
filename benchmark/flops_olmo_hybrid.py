"""The family module (``"flops": "flops_olmo_hybrid"`` in a configuration)
of the dense linear-attention hybrids whose ``layer_pattern`` names ONE
sub-layer an entry in the alphabet ``G`` a Gated DeltaNet mixer
(``gdn_value_heads`` value and ``gdn_key_heads`` key heads of
``gdn_value_dim`` / ``gdn_key_dim``, which need not be equal nor whole lane
tiles; chunks of ``gdn_chunk``), ``*`` an attention mixer of ``num_heads``
query and ``num_kv_heads`` key/value heads of the stated ``attn_head_dim``
with a norm over a token's whole q and k projection, ``-`` a SwiGLU of
``mlp_dim`` (``olmo_hybrid``: a published layer is two entries, mixer then
feed-forward). Every entry holds one norm vector, on its input or on its
output (``reordered_norm_kinds``): the count is the same either way.
``count`` and ``step_work`` are what ``run.py`` and the trace readers ask
(``flops.py``); each kind is counted once an entry of its kind, at its own
widths, and no other is.

``model`` is the ``model`` group of a configuration file (the fields of
``TransformerConfig``). Nothing here imports the program or JAX; of
``flops.py`` only the per-layer piece of the attention kernel.
"""

from flops import attention_kernel_work

ACT_BYTES = 2
F32_BYTES = 4
KINDS = "G*-"


def _sizes(model: dict) -> dict:
    pattern = model["layer_pattern"]
    if len(pattern) != model["num_layers"] or set(pattern) - set(KINDS):
        raise ValueError(f"layer_pattern {pattern!r} is not the layers")
    heads = model["num_heads"]
    Hv, Hk = model["gdn_value_heads"], model["gdn_key_heads"]
    dk, dv = model["gdn_key_dim"], model["gdn_value_dim"]
    return {
        "d": model["model_dim"], "heads": heads,
        "kv": model.get("num_kv_heads") or heads,
        "hd": model["attn_head_dim"],
        "Hv": Hv, "Hk": Hk, "dk": dk, "dv": dv,
        "conv_ch": 2 * Hk * dk + Hv * dv, "val_w": Hv * dv,
        "K": model.get("gdn_conv", 4), "C": model.get("gdn_chunk", 64),
        "f": model["mlp_dim"], "vocab": model["vocab_size"],
        "n": {kind: pattern.count(kind) for kind in KINDS},
    }


def layer_params(model: dict) -> dict:
    """Parameters of one entry of each kind, its one norm vector included;
    ``matmul`` the part of each a token passes through as a matmul (all of
    it but norms, the convolution, the per-head scalars)."""
    s = _sizes(model)
    d = s["d"]
    # [q | k | v], the gate, [b | a], the out-projection
    gdn_mm = d * (s["conv_ch"] + s["val_w"] + 2 * s["Hv"]) + s["val_w"] * d
    attn_mm = 2 * d * s["heads"] * s["hd"] + 2 * d * s["kv"] * s["hd"]
    mlp_mm = 3 * d * s["f"]
    return {
        # convolution, A_log and dt_bias, the gated norm's weight
        "G": gdn_mm + s["conv_ch"] * s["K"] + 2 * s["Hv"] + s["dv"] + d,
        # the q and k norms over the whole projection
        "*": attn_mm + (s["heads"] + s["kv"]) * s["hd"] + d,
        "-": mlp_mm + d,
        "matmul": {"G": gdn_mm, "*": attn_mm, "-": mlp_mm},
    }


def chunk_local_flops_per_token(model: dict) -> float:
    """Forward operations of the two chunk-local stretches of the chunked
    gated delta rule for one token of one layer, the least the chunked form
    needs. In its chunk of C steps, a key head: the causal half of ``K
    K^T`` (2 C d_k / 2); a value head: the unit triangle's inverse by
    forward substitution (C^3 / 3 multiply-adds a chunk), the triangle's
    products ``T V_beta`` and ``T K_beta`` (2 C d / 2 each), and after the
    pass the causal half of ``Q K^T``, ``tril(Q K^T) V'`` and the entered
    state's ``Q S`` (2 d_k d_v)."""
    s = _sizes(model)
    C, dk, dv = s["C"], s["dk"], s["dv"]
    a_key_head = C * dk
    a_value_head = (
        2 * C * C / 3 + C * (dv + dk)  # the inverse, U, W
        + C * dk + C * dv + 2 * dk * dv  # Q K^T, the read-out, Q S
    )
    return float(s["Hk"] * a_key_head + s["Hv"] * a_value_head)


def scan_flops_per_token(model: dict) -> float:
    """... and with the serial pass's two products with the [d_k, d_v]
    state a value head (``W S`` and ``K^T V'``: 2 d_k d_v each)."""
    s = _sizes(model)
    return chunk_local_flops_per_token(model) + float(
        s["Hv"] * 4 * s["dk"] * s["dv"]
    )


def count(model: dict, seq: int) -> dict:
    """The hook's first function. ``params``: everything held here (the
    rows of the vocabulary in ``vocab_size``, both tables, the final norm);
    a dense model, so ``active_params`` is the same.
    ``train_flops_per_token``: 6 for each matmul parameter (the token
    table's lookup costs nothing, the head does), 3 x the chunked rule's
    forward operations a DeltaNet entry, and causal attention's score and
    value matmuls an attention entry, 12 * T * heads * head_dim for the
    whole square and half of it under the mask. ``by_kind`` splits the
    last by kind and the head."""
    s = _sizes(model)
    p = layer_params(model)
    n = s["n"]
    params = 2 * s["vocab"] * s["d"] + s["d"] + sum(
        n[kind] * p[kind] for kind in KINDS
    )
    by_kind = {
        "G": n["G"] * (
            6.0 * p["matmul"]["G"] + 3.0 * scan_flops_per_token(model)
        ),
        "*": n["*"] * (
            6.0 * p["matmul"]["*"] + 12.0 * seq * s["heads"] * s["hd"] / 2
        ),
        "-": n["-"] * 6.0 * p["matmul"]["-"],
        "head": 6.0 * s["d"] * s["vocab"],
    }
    return {
        "params": params,
        "active_params": params,
        "train_flops_per_token": sum(by_kind.values()),
        "by_kind": by_kind,
    }


def attention_work(model: dict, batch: int, seq: int) -> dict:
    """One attention entry, forward + backward: the operations of
    ``flops.attention_kernel_work`` at its own head count and head width;
    the bytes with the key and value tensors at their own heads (q, o
    forward and q, do, dq backward are query-sized, k, v forward and k, v,
    dk, dv backward key/value-sized)."""
    s = _sizes(model)
    work = attention_kernel_work(batch, s["heads"], seq, s["hd"])
    token = batch * seq * s["hd"] * ACT_BYTES
    return {
        "flops": work["flops"],
        "bytes": float((5 * s["heads"] + 6 * s["kv"]) * token),
    }


def gated_delta_work(model: dict, tokens: int) -> dict:
    """One DeltaNet entry's two chunk-local stretches, the work of the
    ``gdn_chunk_*`` kernels, forward + backward, AT THE STATED head widths:
    3 x ``chunk_local_flops_per_token``; the bytes of what the four
    kernels must read and write once. Before the pass (``wy``): k, v in
    the activation dtype, beta and g float32 in; ``U`` float32, ``W`` and
    the chunk's keys in the activation dtype, the two decays out. After it
    (``read_out``): q, k, g, ``V'`` and a chunk's entered state ``[d_k,
    d_v]`` in, ``o`` out. Each backward kernel reads its forward's inputs
    and the cotangents of its outputs and writes the cotangents of its
    inputs. **The serial pass over the chunk states between the two is
    XLA's (``chunk_state_pass``: a scan of small matmuls) and is not in
    here**, nor are lanes a kernel pads a head to."""
    s = _sizes(model)
    C, dk, dv, r = s["C"], s["dk"], s["dv"], s["Hv"] // s["Hk"]
    a, f = ACT_BYTES, F32_BYTES
    # a token of one key head with its r value heads
    wy_in = a * dk + r * (a * dv + 2 * f)
    wy_out = r * (f * dv + a * dk + f + f / C) + a * dk
    read_in = 2 * a * dk + r * (f + a * dv + a * dk * dv / C)
    read_out = r * a * dv
    forward = wy_in + wy_out + read_in + read_out
    backward = (
        (wy_in + wy_out + wy_in) + (read_in + read_out + read_in)
    )
    return {
        "flops": 3.0 * chunk_local_flops_per_token(model) * tokens,
        "bytes": float(tokens * s["Hk"] * (forward + backward)),
    }


def step_work(model: dict, batch: int, seq: int) -> dict:
    """The hook's second function: each kind of kernel over the entries
    that run it. No grouped matmul: the model is dense."""
    n = _sizes(model)["n"]

    def times(count, work):
        if not count:
            return None
        return {k: v * count for k, v in work.items()}

    return {
        "attention": times(n["*"], attention_work(model, batch, seq)),
        "grouped_matmul": None,
        "gated_delta": times(n["G"], gated_delta_work(model, batch * seq)),
    }
