"""The first round of a chip's share of the experts
(``parallel/moe._moe_share``) is differentiated in line and keeps what its
backward pass reads; only the rounds an overloaded share needs past it are
made again. Held to the plain statement of the share (every held expert's
FFN over every token, masked by the assignments), gated and not, from no
held row to three rounds' worth, with and without ``recomputed``; what the
``vjp`` keeps, and by which names under the wrapper; how often the grouped
matmul is called. Small shapes, on the CPU."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax._src.ad_checkpoint import saved_residuals

from dlrover_tpu.common import trace_counts
from dlrover_tpu.models import transformer as tr
from dlrover_tpu.models.config import TransformerConfig
from dlrover_tpu.parallel import moe

T, K, E, MODEL, FFN = 1024, 2, 32, 16, 24
OFFSET, COUNT = 5, 2
R = moe.share_rows(T * K, COUNT, E)
# held rows of the k*T assignments: none, a part of a round, a round to
# the row, one row more, three rounds' worth
DRAWS = {
    "no_row": 0, "part_of_a_round": 300, "a_round_exactly": R,
    "a_row_more": R + 1, "three_rounds": 2 * R + 376,
}


def relu2(x):
    return jnp.square(jax.nn.relu(x))


def _share(gated, n_held):
    """A share's arguments with ``n_held`` of the assignments on its two
    held experts: the first ``n_held // 2`` tokens choose both, one more
    the first alone where the count is odd, every other choice falls on
    the experts past them."""
    keys = jax.random.split(jax.random.PRNGKey(0), 6)
    params = moe.MoEParams(
        gate=None,
        w_up=jax.random.normal(keys[0], (COUNT, MODEL, FFN)) / 4,
        w_down=jax.random.normal(keys[1], (COUNT, FFN, MODEL)) / 5,
        w_gate=(
            jax.random.normal(keys[2], (COUNT, MODEL, FFN)) / 4
            if gated else None
        ),
    )
    x = jax.random.normal(keys[3], (T, MODEL))
    gates = jax.random.uniform(keys[4], (T, K), minval=0.1)
    others = jax.random.randint(keys[5], (T, K), OFFSET + COUNT, E)
    token = jnp.arange(T)[:, None]
    both, odd = divmod(n_held, 2)
    held = (token < both) | ((token == both) & (jnp.arange(K) < odd))
    idx = jnp.where(held, OFFSET + jnp.arange(K), others)
    counts = jnp.bincount(idx.reshape(-1), length=E)
    assert int(counts[OFFSET:OFFSET + COUNT].sum()) == n_held
    return params, x, idx, gates, counts


def _run(params, x, gates, idx, counts):
    return moe._moe_share(
        params, x, idx, gates, counts, relu2, (OFFSET, COUNT)
    )


def _plain(params, x, gates, idx, counts):
    """Every held expert over every token, times the gate where the
    token chose it."""
    out = jnp.zeros_like(x)
    for e in range(COUNT):
        h = x @ params.w_up[e]
        if params.w_gate is not None:
            h = jax.nn.silu(x @ params.w_gate[e]) * h
        else:
            h = relu2(h)
        weight = jnp.sum(jnp.where(idx == OFFSET + e, gates, 0.0), axis=1)
        out = out + (h @ params.w_down[e]) * weight[:, None]
    return out


def _loss(fn):
    return lambda *diff, idx, counts: jnp.sum(
        jnp.sin(fn(*diff, idx, counts))
    )


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-6)


@pytest.mark.parametrize("wrapped", [False, True], ids=["bare", "recomputed"])
@pytest.mark.parametrize("draw", sorted(DRAWS))
@pytest.mark.parametrize("gated", [False, True], ids=["relu2", "swiglu"])
def test_loss_and_every_gradient_leaf_are_the_plain_share_s(
    gated, draw, wrapped
):
    params, x, idx, gates, counts = _share(gated, DRAWS[draw])
    wrap = tr.recomputed if wrapped else (lambda f: f)

    def both(fn):
        return jax.jit(jax.value_and_grad(
            lambda *diff: _loss(wrap(fn))(*diff, idx=idx, counts=counts),
            (0, 1, 2),
        ))(params, x, gates)

    (got, g_got), (want, g_want) = both(_run), both(_plain)
    assert abs(float(got) - float(want)) <= 1e-5 * max(abs(float(want)), 1)
    leaves = jax.tree_util.tree_leaves_with_path(g_got)
    assert len(leaves) == (5 if gated else 4)
    for (path, a), b in zip(leaves, jax.tree_util.tree_leaves(g_want)):
        assert np.all(np.isfinite(np.asarray(a)))
        assert _rel(a, b) <= 1e-5, jax.tree_util.keystr(path)
        # nothing held: nothing flows to the experts, as in the plain share
        if not DRAWS[draw]:
            assert not np.any(np.asarray(a))


@pytest.mark.parametrize("gated", [False, True], ids=["relu2", "swiglu"])
@pytest.mark.parametrize(
    "draw, rounds_past_the_first",
    [("no_row", 0), ("part_of_a_round", 0), ("a_round_exactly", 0),
     ("a_row_more", 1), ("three_rounds", 2)],
)
def test_the_grouped_matmul_is_called_once_for_the_first_round(
    gated, draw, rounds_past_the_first, monkeypatch
):
    """Run step by step (no ``jit``), a gradient calls ``lax.ragged_dot``
    for the first round's forward and never again for it; the loop takes
    no trip where the held rows fit one round, and each trip it takes is
    one forward and one more in the backward pass."""
    calls = []
    ragged_dot = lax.ragged_dot
    monkeypatch.setattr(
        lax, "ragged_dot",
        lambda a, w, sizes: calls.append(a.shape) or ragged_dot(a, w, sizes),
    )
    params, x, idx, gates, counts = _share(gated, DRAWS[draw])
    a_round = 3 if gated else 2
    with jax.disable_jit():
        _run(params, x, gates, idx, counts)
        assert len(calls) == a_round * (1 + rounds_past_the_first)
        del calls[:]
        grads = jax.grad(
            lambda *diff: _loss(_run)(*diff, idx=idx, counts=counts),
            (0, 1, 2),
        )(params, x, gates)
    assert len(calls) == a_round * (1 + 2 * rounds_past_the_first)
    assert all(
        np.all(np.isfinite(np.asarray(g)))
        for g in jax.tree_util.tree_leaves(grads)
    )


def _kept(wrap, gated):
    """What a share's ``vjp`` keeps of what the share computes, by shape,
    dtype and where it is from (arguments, constants and the probe around
    the share aside)."""
    params, x, idx, gates, counts = _share(gated, DRAWS["part_of_a_round"])
    return sorted(
        (aval.shape, str(aval.dtype), where)
        for aval, where in saved_residuals(
            lambda *diff: _loss(wrap(_run))(*diff, idx=idx, counts=counts),
            params, x, gates,
        )
        if "from the argument" not in where and "_loss" not in where
        and "from a constant" not in where
    )


def _rows(gated):
    """A round's rows as the three names hold them: the gathered ones and
    the returned ones, and between them what the up projection (and the
    gate's) hands the activation."""
    return sorted(
        [((R, MODEL), "float32")] * 2
        + [((R, FFN), "float32")] * (2 if gated else 1)
    )


@pytest.mark.parametrize("gated", [False, True], ids=["relu2", "swiglu"])
def test_a_share_s_vjp_keeps_the_first_round_s_rows(gated):
    """In line, ``jax.grad`` keeps the gathered rows and what the round
    returns under their names, and ``[R, ffn]`` arrays between them (what
    the activation's own rule wants of what it read), among its natural
    residuals."""
    kept = _kept(lambda f: f, gated)
    named = {
        re.search(r"named '(\w+)'", where).group(1): (shape, dtype)
        for shape, dtype, where in kept if "named '" in where
    }
    xs, h, ys = moe.KEPT
    assert named[xs] == named[ys] == ((R, MODEL), "float32")
    assert named.get(h, ((R, FFN), "float32")) == ((R, FFN), "float32")
    assert [k[:2] for k in kept].count(((R, FFN), "float32")) >= 2
    assert all(shape[0] in (COUNT, R, T, T * K) for shape, _, _ in kept)


@pytest.mark.parametrize("gated", [False, True], ids=["relu2", "swiglu"])
def test_a_recomputed_layer_keeps_the_named_rows_and_nothing_else(
    gated, monkeypatch
):
    """Under ``recomputed``: the three names' arrays, one copy each, and
    nothing without a name; without the names, and under a bare
    ``jax.checkpoint``, nothing the share computes."""
    assert set(moe.KEPT) <= set(tr.KEPT)
    kept = _kept(tr.recomputed, gated)
    assert [(shape, dtype) for shape, dtype, _ in kept] == _rows(gated)
    assert _kept(jax.checkpoint, gated) == []
    monkeypatch.setattr(moe, "checkpoint_name", lambda x, name: x)
    assert _kept(tr.recomputed, gated) == []


def test_the_names_lower_to_nothing_outside_a_policy(monkeypatch):
    """With no wrapper around it and under a bare ``jax.checkpoint`` the
    gradient's program is what it is without the names."""
    params, x, idx, gates, counts = _share(True, DRAWS["three_rounds"])

    def lowered(wrap):
        grad = jax.grad(
            lambda *diff: _loss(wrap(_run))(*diff, idx=idx, counts=counts),
            (0, 1, 2),
        )
        text = jax.jit(grad).lower(params, x, gates).as_text()
        return re.sub(r"(@\w+?)_\d+\b", r"\1", text)

    named = [lowered(wrap) for wrap in (lambda f: f, jax.checkpoint)]
    assert not any(name in text for name in moe.KEPT for text in named)
    monkeypatch.setattr(moe, "checkpoint_name", lambda x, name: x)
    assert named == [lowered(wrap) for wrap in (lambda f: f, jax.checkpoint)]


_TOY = dict(
    vocab_size=64, model_dim=32, num_heads=2, mlp_dim=32, max_seq_len=64,
    dtype="float32", param_dtype="float32", rmsnorm=True,
    tie_embeddings=False, positions="none", num_experts=8, moe_top_k=2,
    router="sigmoid", shared_expert_dim=16, dense_mlp_dim=32,
)


@pytest.mark.parametrize("remat", [False, True], ids=["bare", "remat"])
@pytest.mark.parametrize(
    "pattern, held, sites",
    [("*E*E", 2, 2), ("*E-E*E", 4, 3), ("*E*E", 8, 0), ("*-", 2, 0)],
)
def test_a_traced_step_counts_its_share_layers(pattern, held, sites, remat):
    """``moe_share_kept_sites``: one a layer that holds a share of the
    experts, with and without ``remat``; none where every expert is held
    (the dropless layer) and in a model without experts."""
    cfg = TransformerConfig(
        **_TOY, num_layers=len(pattern), layer_pattern=pattern,
        experts_held=held, remat=remat,
    )
    params = jax.eval_shape(
        lambda: tr.init_params(jax.random.PRNGKey(0), cfg)
    )
    tokens = jax.ShapeDtypeStruct((1, 64), jnp.int32)
    before = trace_counts.snapshot()
    jax.eval_shape(
        jax.grad(lambda p, x, y: tr.loss_fn(p, x, y, cfg)),
        params, tokens, tokens,
    )
    assert trace_counts.since(before)["moe_share_kept_sites"] == sites
