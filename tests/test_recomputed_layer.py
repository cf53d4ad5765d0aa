"""What a recomputed layer keeps (``models/transformer.recomputed``,
``cfg.remat``): what its attention kernel read and returned, so the
gradient holds each forward attention kernel once where a bare
``jax.checkpoint`` holds it twice, over the four kinds of call the kernels'
``custom_vjp`` rule sees (the streaming triangle, the window's band, the
fused family, the latent attention's call of two widths); and what a
delta-rule mixer's serial pass read and returned, with the rule's ``o``, so
the ``gdn_*_wy_fwd`` / ``gdn_*_read_fwd`` kernels and the pass's forward
loop are there once too, for a scalar and for a vector decay. The gradients
are those of the layer without ``remat``, and nothing else is saved. Small
shapes, the kernels interpreted."""

import ast
import collections
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src.ad_checkpoint import saved_residuals

from dlrover_tpu.common import trace_counts
from dlrover_tpu.models import transformer as tr
from dlrover_tpu.models.config import TransformerConfig
from dlrover_tpu.parallel import pipeline

from dlrover_tpu.ops import gated_delta as gd
from lowering_fingerprint import inner_numbers_off

# `dlrover_tpu.ops.flash_attention` the attribute is the function
fa = importlib.import_module("dlrover_tpu.ops.flash_attention")

T = 64
CHUNK = 16  # of the delta rule: the pass is a loop of T / CHUNK steps
_SMALL = dict(
    vocab_size=64, model_dim=32, num_heads=2, mlp_dim=32, max_seq_len=T,
    dtype="float32", param_dtype="float32", rmsnorm=True,
    tie_embeddings=False,
)
_DELTA_RULE = dict(
    num_layers=2, layer_pattern="G-", gdn_key_heads=1, gdn_key_dim=128,
    gdn_value_dim=128, gdn_chunk=CHUNK, positions="none", dense_mlp_dim=32,
)
# a call kind: a toy model whose attention (delta-rule) layers make that
# call, the forward kernel it lowers to (the rule's two), and the width of
# a head there (q, k, v and ``o`` alike)
KINDS = {
    # grouped heads: the streaming kernels, on the triangle path
    "triangle": (
        dict(num_layers=3, layer_pattern="*-*", num_kv_heads=1,
             positions="none", dense_mlp_dim=32),
        "flash_attn_fwd", 16,
    ),
    # the same with a window: the band's kernels
    "window_band": (
        dict(num_layers=2, layer_pattern="W*", num_kv_heads=1,
             attn_window=16, positions="window", mixer_out_norm=True),
        "flash_attn_window_fwd", 16,
    ),
    # as many key heads as query heads at T <= 1024: the fused family
    "fused_family": (dict(num_layers=2), "flash_attn_fused_fwd", 16),
    # a latent attention: scores 24 wide, values 16, called 128 wide
    "two_widths": (
        dict(num_layers=2, layer_pattern="*-", attn_kind="latent",
             kv_latent_dim=16, qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16,
             rope=True, qk_norm=True, qk_norm_span="head", dense_mlp_dim=32),
        "flash_attn_fused_fwd", 128,
    ),
    # a delta-rule layer, one scalar decay a head: two value heads read
    # the one key head
    "scalar_decay": (
        dict(_DELTA_RULE, gdn_value_heads=2),
        ("gdn_chunk_wy_fwd", "gdn_chunk_read_fwd"), 128,
    ),
    # the decay a vector over the key's channels
    "vector_decay": (
        dict(_DELTA_RULE, gdn_value_heads=1, gdn_decay="channel",
             gdn_decay_bound=-5.0, gdn_gate="head_sigmoid"),
        ("gdn_channel_wy_fwd", "gdn_channel_read_fwd"), 128,
    ),
}
DELTA_RULE = ("scalar_decay", "vector_decay")


@pytest.fixture
def kernels(monkeypatch):
    """The attention kernels' own path, interpreted."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(fa, "_interpret_default", lambda: True)


def _cfg(kind, remat):
    return TransformerConfig(**dict(_SMALL, **KINDS[kind][0]), remat=remat)


def _inputs(cfg):
    params = tr.init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, T), 0, 64)
    return params, tokens, jnp.roll(tokens, -1, axis=1)


def _grad(cfg):
    return jax.grad(lambda p, x, y: tr.loss_fn(p, x, y, cfg))


def _kernels_in(jaxpr, found=None):
    """Every ``pallas_call`` of ``jaxpr`` and of the jaxprs inside it, by
    the kernel's name (the delta rule's serial pass is two of them,
    ``delta_state_pass`` and ``delta_state_pass_rev``); a kernel's own
    body is not looked into."""
    found = collections.Counter() if found is None else found
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found[eqn.params["name"]] += 1
            continue
        for inner in jax.core.jaxprs_in_params(eqn.params):
            _kernels_in(inner, found)
    return found


def _mixing_layers(cfg):
    """The layers whose call the kind is about."""
    pattern = cfg.layer_pattern or "*" * cfg.num_layers
    return sum(kind in "*WG" for kind in pattern)


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_the_gradient_holds_each_forward_attention_kernel_once(
    kind, kernels, monkeypatch
):
    cfg = _cfg(kind, remat=True)
    args = _inputs(cfg)
    delta_rule = kind in DELTA_RULE
    sites = "gdn_kept_sites" if delta_rule else "attn_kept_sites"
    before = trace_counts.snapshot()
    kept = _kernels_in(jax.make_jaxpr(_grad(cfg))(*args).jaxpr)
    counted = trace_counts.since(before)
    monkeypatch.setattr(tr, "recomputed", jax.checkpoint)
    before = trace_counts.snapshot()
    bare = _kernels_in(jax.make_jaxpr(_grad(cfg))(*args).jaxpr)
    forward = [n for n in bare if n.endswith(("_fwd", "_pass"))]
    backward = [n for n in bare if n.endswith(("_bwd", "_rev"))]
    layers = _mixing_layers(cfg)
    if delta_rule:
        # each of the rule's two kernels and the pass's forward kernel; the
        # convolution before them is made again (the mixer names what it
        # read, not what it returned), and so is the gated norm after them
        # (its result is not kept: 64 MiB a layer of the Ling cell)
        assert set(KINDS[kind][1]) | {"delta_state_pass"} <= set(forward)
        assert "delta_state_pass_rev" in backward
        again = [
            n for n in forward if n.startswith(("conv_", "gated_norm_"))
        ]
        assert "gated_norm_fwd" in again
        assert all(kept[n] == 2 * layers for n in again)
        assert all(kept[n] == layers for n in forward if n not in again)
        assert all(bare[n] == 2 * layers for n in forward)
        # a forward pass that does not run is not among the steps counted
        steps = layers * T // CHUNK
        assert counted["gdn_chunk_steps"] == 2 * steps
        assert trace_counts.since(before)["gdn_chunk_steps"] == 3 * steps
    else:
        assert KINDS[kind][1] in forward and backward
        assert sum(kept[n] for n in forward) == layers
        assert sum(bare[n] for n in forward) == 2 * layers
    # the backward kernels are there as often either way
    assert {n: kept[n] for n in backward} == {n: bare[n] for n in backward}
    # a site is counted where the wrapper keeps its outputs, and only
    # there: each layer of a pattern, and ONE for a block that a loop calls
    # (``jax.checkpoint`` traces it once)
    assert counted[sites] == (layers if cfg.layer_pattern else 1)
    assert trace_counts.since(before)[sites] == 0


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_gradients_are_those_of_the_layer_without_remat(kind, kernels):
    plain, again = _cfg(kind, remat=False), _cfg(kind, remat=True)
    args = _inputs(plain)
    want = jax.jit(_grad(plain))(*args)
    got = jax.jit(_grad(again))(*args)
    for (path, a), b in zip(
        jax.tree_util.tree_leaves_with_path(got),
        jax.tree_util.tree_leaves(want),
    ):
        a, b = np.asarray(a), np.asarray(b)
        assert np.max(np.abs(a - b)) <= 1e-6 * max(np.max(np.abs(b)), 1.0), (
            jax.tree_util.keystr(path)
        )


def _named_arrays(kind, cfg):
    """What a recomputed layer of the kind saves beside its arguments."""
    f32 = "float32"
    if kind not in DELTA_RULE:
        # what the kernel read (q, k, v as it was called) and returned
        # (``o`` and the logsumexp); the latent call hands every head its
        # own keys, padded like the rest
        heads, width = cfg.num_heads, KINDS[kind][2]
        kv_heads = cfg.num_kv_heads or heads
        q = o = ((1, heads, T, width), f32)
        k = v = ((1, kv_heads, T, width), f32)
        return [q, k, v, o, ((1, heads, T), f32)]
    # what the serial pass read (``W``, the keys, its decays) and returned
    # (``V'``, the entered states), chunk axis first, and the rule's ``o``;
    # of the stretch before it what the mixer named: the projection's
    # ``[q | k | v]``
    n, Hk, Hv = T // CHUNK, cfg.gdn_key_heads, cfg.gdn_value_heads
    r, dk, dv = Hv // Hk, cfg.gdn_key_dim, cfg.gdn_value_dim
    lead = (n, 1, Hk)
    qkv = ((1, T, 2 * Hk * dk + Hv * dv), f32)
    named = [
        (lead + (r, CHUNK, dk), f32), (lead + (CHUNK, dk), f32),  # W, K
        (lead + (r, CHUNK, dv), f32), (lead + (r, dk, dv), f32),  # V', S
        ((1, T, Hv, dv), f32), qkv,
    ]
    if cfg.gdn_decay == "channel":  # the keys carry the decay: no delta
        return named + [(lead + (r, dk), f32)]
    return named + [(lead + (r, CHUNK), f32), (lead + (r,), f32)]


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_the_wrapper_saves_the_named_outputs_and_nothing_else(kind, kernels):
    """Of one recomputed attention (delta-rule) layer: beside its
    arguments, one copy of each array the modules named."""
    cfg = _cfg(kind, remat=True)
    layer = _inputs(cfg)[0]["layers"][0]
    letter = (cfg.layer_pattern or "")[:1]
    positions = jnp.broadcast_to(jnp.arange(T), (1, T))
    x = jnp.ones((1, T, cfg.model_dim), jnp.float32)

    def one_layer(x, layer):
        if letter == "G":
            return tr._gdn_block(x, layer, cfg, None)
        if letter:
            return tr._attention_block(
                x, layer, cfg, None, positions, "norm", letter
            )
        return tr._attention_block(x, layer, cfg, None, positions)

    def computed(wrap):
        """What the layer computes and ``wrap`` saves (beside arguments
        and constants: the positions, the kernels' offsets)."""
        return sorted(
            (aval.shape, str(aval.dtype)) for aval, where in saved_residuals(
                lambda x, layer: jnp.sum(wrap(one_layer)(x, layer)), x, layer
            )
            if "from the argument" not in where
            and "from a constant" not in where
        )

    assert computed(tr.recomputed) == sorted(_named_arrays(kind, cfg))
    assert computed(jax.checkpoint) == []


# one helper for every layer wrapped for ``cfg.remat``
MODELS = {
    "layer_pattern": dict(KINDS["triangle"][0]),
    "scan_layers": dict(num_layers=2, scan_layers=True),
    "plain_blocks": dict(num_layers=2),
}


@pytest.mark.parametrize("model", sorted(MODELS))
@pytest.mark.parametrize("remat", [True, False])
def test_every_model_wraps_its_layers_with_the_one_helper(
    model, remat, monkeypatch
):
    wrapped = []

    def spy(layer_fn):
        wrapped.append(layer_fn)
        return recomputed(layer_fn)

    recomputed = tr.recomputed
    monkeypatch.setattr(tr, "recomputed", spy)
    cfg = TransformerConfig(**dict(_SMALL, **MODELS[model]), remat=remat)
    params, tokens, targets = jax.eval_shape(lambda: _inputs(cfg))
    jax.eval_shape(_grad(cfg), params, tokens, targets)
    # a wrapper a layer of a pattern (the trace counts want each traced),
    # one for the block that a scan or a loop calls
    want = len(cfg.layer_pattern) if cfg.layer_pattern else 1
    assert len(wrapped) == (want if remat else 0)


def test_no_layer_is_wrapped_for_remat_outside_the_helper():
    """``jax.checkpoint`` is called in ``recomputed`` and nowhere else in
    the two modules that wrap layers; the pipeline's three sites call the
    helper."""
    calls = {}
    for module in (tr, pipeline):
        with open(module.__file__) as f:
            tree = ast.parse(f.read())
        calls[module] = collections.Counter(
            ast.unparse(node.func) for node in ast.walk(tree)
            if isinstance(node, ast.Call)
        )
    assert calls[tr]["jax.checkpoint"] == 1
    assert calls[tr]["recomputed"] == 2
    assert calls[pipeline]["jax.checkpoint"] == 0
    assert calls[pipeline]["recomputed"] == 3
    assert pipeline.recomputed is tr.recomputed


def _attend(q):
    return jnp.sum(fa.flash_attention(q, q, q, layout="bhtd") ** 2)


def _delta_rule(vector):
    def rule(q):
        """``q`` [1, T, 1, 128] as keys, values and queries of one head."""
        beta = jnp.full(q.shape[:3], 0.5, jnp.float32)
        g = jnp.full(q.shape if vector else q.shape[:3], -0.1, jnp.float32)
        return jnp.sum(gd._delta_rule(q, q, q, beta, g, CHUNK, None) ** 2)

    return rule


# a call that holds names, its argument's shape, the module that names and
# the names
NAMED_CALLS = {
    "attention": (_attend, (1, 2, T, 16), fa, fa.KEPT),
    "scalar_decay": (_delta_rule(False), (1, T, 1, 128), gd, gd.KEPT),
    "vector_decay": (_delta_rule(True), (1, T, 1, 128), gd, gd.KEPT),
}


@pytest.mark.parametrize("call", sorted(NAMED_CALLS))
def test_a_name_lowers_to_nothing_outside_a_policy(
    call, kernels, monkeypatch
):
    """The text a gradient through the kernels lowers to holds no trace of
    the names: with no wrapper around it and under a bare
    ``jax.checkpoint`` the program is what it was without them."""
    fn, shape, module, names = NAMED_CALLS[call]
    q = jnp.full(shape, 0.1, jnp.float32)

    def lowered(wrap):
        # the numbers that tell one inner function's copies apart aside
        text = jax.jit(jax.grad(wrap(fn))).lower(q).as_text()
        return inner_numbers_off(text)

    named = [lowered(wrap) for wrap in (lambda f: f, jax.checkpoint)]
    assert not any(name in text for name in names for text in named)
    monkeypatch.setattr(module, "checkpoint_name", lambda x, name: x)
    assert named == [lowered(wrap) for wrap in (lambda f: f, jax.checkpoint)]
