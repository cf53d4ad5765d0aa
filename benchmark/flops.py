"""Operations and bytes from shapes: the benchmark's own arithmetic, and
the family module of the models whose layers are all one dense block:
attention over ``num_heads`` heads of ``model_dim / num_heads``, then one
MLP (GPT-2's block, and what ``init_params`` builds without experts).

A configuration names its family module with ``"flops": "<module>"``
(absent: this one), and ``run.py`` and the trace readers ask that module's
two functions, ``count`` and ``step_work``, and reckon no model's shape
themselves. A family of another block brings its own ``flops_<family>.py``
and builds on the per-layer pieces here (``attention_kernel_work``,
``roofline_seconds``), which know nothing of any model's layout.

``model`` is the ``model`` group of a configuration file (the fields of
``TransformerConfig``). Nothing here imports the program or JAX.
"""


def n_params(model: dict) -> int:
    """Parameters of a dense GPT-style model as ``init_params`` builds it:
    token table (tied to the output head unless ``tie_embeddings`` is
    false), learned positions unless ``rope``, per layer the attention
    projections, the MLP and two norms, and the final norm."""
    d = model["model_dim"]
    v = model["vocab_size"]
    h = model["num_heads"]
    kvh = model.get("num_kv_heads") or h
    hd = d // h
    f = model.get("mlp_dim") or 4 * d
    norm = d if model.get("rmsnorm") else 2 * d
    n = v * d + norm
    if not model.get("rope"):
        n += model["max_seq_len"] * d
    if not model.get("tie_embeddings", True):
        n += d * v
    attn = d * h * hd + 2 * d * kvh * hd + h * hd * d
    if model.get("swiglu"):
        mlp = 3 * d * f
    else:
        mlp = 2 * d * f + f + d
    n += model["num_layers"] * (attn + mlp + 2 * norm)
    return n


def train_flops_per_token(model: dict, seq: int) -> float:
    """Forward + backward operations one token needs: 6 per parameter
    (the tied table counted once: the lookup costs nothing, the head
    does) plus causal attention's score and value matmuls, which the
    6-per-parameter rule leaves out: 12 * L * T * d for the whole
    square, half of it under the causal mask. Recomputed operations are
    not counted."""
    attn = 12.0 * model["num_layers"] * seq * model["model_dim"] / 2
    return 6.0 * n_params(model) + attn


def count(model: dict, seq: int) -> dict:
    """The hook's first function: parameters held, parameters one token
    passes through (a dense model: all of them), and the forward +
    backward operations a token of a ``seq`` long row needs."""
    n = n_params(model)
    return {
        "params": n,
        "active_params": n,
        "train_flops_per_token": train_flops_per_token(model, seq),
    }


def step_work(model: dict, batch: int, seq: int) -> dict:
    """The hook's second function: the least one whole training step of
    ``batch`` rows of ``seq`` tokens needs in each kind of kernel, every
    layer that runs it added up, forward and backward, recomputation not
    counted; ``None`` for a kind the model does not run. Here every layer
    runs the one attention and none a grouped matmul."""
    heads = model["num_heads"]
    layer = attention_kernel_work(
        batch, heads, seq, model["model_dim"] // heads
    )
    return {
        "attention": {
            k: v * model["num_layers"] for k, v in layer.items()
        },
        "grouped_matmul": None,
    }


def mfu_pct(tokens_per_s: float, flops_per_token: float, peak_flops: float,
            chips: int = 1) -> float:
    return 100.0 * tokens_per_s * flops_per_token / (peak_flops * chips)


def attention_kernel_work(batch: int, heads: int, seq: int, head_dim: int,
                          act_bytes: int = 2) -> dict:
    """What one layer's causal attention needs, forward + backward, as
    flash attention computes it, for ``batch`` sequences.

    Operations: 2 matmuls forward (QK^T, PV) and 4 backward (dV, dP, dQ,
    dK), each 2*T*T*D per head, halved by the causal mask. The backward's
    recomputation of QK^T is not counted: it is the kernel's choice, not
    the algorithm's need. Bytes: forward reads q, k, v and writes o;
    backward reads q, k, v, o-gradient and writes dq, dk, dv (the [T]
    row statistics are under 1% and left out)."""
    per_matmul = 2.0 * seq * seq * head_dim * 0.5
    flops = 6 * per_matmul * batch * heads
    tensor = batch * heads * seq * head_dim * act_bytes
    return {"flops": flops, "bytes": float((4 + 7) * tensor)}


def roofline_seconds(work: dict, peak: dict) -> dict:
    """Least time the chip could take, and which bound holds."""
    t_flops = work["flops"] / peak["bf16_flops"]
    t_bytes = work["bytes"] / peak["hbm_bytes_per_s"]
    return {
        "seconds": max(t_flops, t_bytes),
        "bound": "flops" if t_flops >= t_bytes else "bytes",
        "flops_s": t_flops,
        "bytes_s": t_bytes,
    }
