"""The Pallas kernels, compiled by the installed Mosaic for a v5e chip that
is described and not attached (on-chip-measurement guide, section 2,
rehearsal 3). Nothing runs: a pass says the chip's compiler accepts the
kernel at this shape, never that it is right or fast.

Every kernel picks ``interpret`` from the backend, which is the CPU
here, so an unsteered compile would compile the *interpreted* kernel
and prove nothing: each test steers the kernel to its compiled form
itself and asserts ``tpu_custom_call`` in the lowered text.

One file, topology in a module-scoped fixture: only the xdist worker
that runs this file loads the TPU's library (see the guide for why it
must never happen at import, in conftest.py or in a second file).
"""

import importlib
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from dlrover_tpu.common import trace_counts
from trace_counted import (
    CONV, DIFF, EDGE, FUSED, GATE, GDN, LANES, PASS, SSCAN, SSD, STREAM,
    WINDOW, added,
)

# `dlrover_tpu.ops.flash_attention` the attribute is the function
# (ops/__init__ re-exports it); the module has to be asked for by name
fa = importlib.import_module("dlrover_tpu.ops.flash_attention")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    """A compile for a described chip is written to the persistent
    cache but cannot be read back without the chip; keep it out."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _compile_for_chip(fn, *shapes):
    lowered = jax.jit(fn).lower(*shapes)
    assert "tpu_custom_call" in lowered.as_text(), (
        "the interpreted kernel was lowered: the test did not steer "
        "the kernel to its compiled form"
    )
    compiled = lowered.compile()
    assert compiled.memory_analysis() is not None
    return compiled


# [B, H, T, D] in the kernel-native layout
ATTENTION_SHAPES = {
    # fused path: T <= _FUSED_MAX_T
    "gpt2_124m": (8, 12, 1024, 64),
    # 25 heads against _head_chunk
    "gpt2_xl": (4, 25, 1024, 64),
    # the benchmark's XL cell: the triangle walk, 5 heads a program
    # forward and 1 backward (_walk_head_chunk)
    "gpt2_xl_d12_cell": (8, 25, 1024, 64),
    # streaming path: the triangle in blocks of 1024, one-pass backward
    "llama2_7b": (1, 32, 4096, 128),
    # the benchmark's OLMoE cell (two such layers) ...
    "olmoe_cell": (2, 16, 4096, 128),
    # ... and its Nemotron cell: 32 query heads on 2 key-value heads
    "nemotron_cell": (1, 32, 8192, 128),
    # a head's float32 dq no longer fits VMEM: the split backward
    "long_context": (1, 4, 65536, 128),
}
KV_HEADS = {"nemotron_cell": 2}


@pytest.mark.parametrize("name", list(ATTENTION_SHAPES))
@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_flash_attention_compiles(name, direction, one_chip, monkeypatch):
    monkeypatch.setattr(fa, "_interpret_default", lambda: False)
    shape = ATTENTION_SHAPES[name]
    B, H, T, D = shape
    fused = T <= fa._FUSED_MAX_T
    assert fused == name.startswith("gpt2")
    kv_shape = (B, KV_HEADS.get(name, H), T, D)
    qkv = [
        jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=one_chip)
        for s in (shape, kv_shape, kv_shape)
    ]

    def attend(q, k, v):
        return fa.flash_attention(
            q, k, v, causal=True, force="pallas", layout="bhtd"
        )

    before = trace_counts.snapshot()
    if direction == "fwd":
        compiled = _compile_for_chip(attend, *qkv)
    else:
        compiled = _compile_for_chip(
            jax.grad(
                lambda q, k, v: attend(q, k, v)
                .astype(jnp.float32)
                .sum(),
                argnums=(0, 1, 2),
            ),
            *qkv,
        )
    # the kernel's name= reaches the HLO instruction's name (and so a
    # device trace's event): the benchmark's reducer finds the kernels
    # by it, whatever the compiler numbers them
    want = {
        ("fwd", True): ["flash_attn_fused_fwd"],
        ("bwd", True): ["flash_attn_fused_fwd", "flash_attn_fused_bwd"],
        ("fwd", False): ["flash_attn_fwd"],
        # the backward in one pass (no dq and no dk / dv kernel) while
        # a head's dq fits, split in those two beyond
        ("bwd", False): (
            ["flash_attn_fwd", "flash_attn_bwd"]
            if fa._one_pass_fits(T, D, 2)
            else ["flash_attn_fwd", "flash_attn_bwd_dq", "flash_attn_bwd_dkv"]
        ),
    }[direction, fused]
    assert fa._one_pass_fits(T, D, 2) == (name != "long_context")
    text = compiled.as_text()
    for kernel in want:
        assert kernel in text, kernel
    assert ("flash_attn_bwd_d" in text) == (len(want) == 3)
    # causal, in sequence, T = 1024: every fused site is lowered as the
    # triangle walk (4 row tiles: 10 of 16 score tiles a site), under
    # _FUSED_VMEM_LIMIT since it compiled; a streaming shape has none
    # and is lowered on the triangle path in blocks of 1024 (10 of 16
    # blocks a kernel at T = 4096, 36 of 64 at T = 8192), its one-pass
    # backward with a head's float32 dq resident under the same limit
    sites = len(want)
    n = T // fa._TRI_BLOCK
    walked = (sites, 0, n * (n + 1) // 2 * sites, n * n * sites)
    assert (walked[2], walked[3]) == {
        1024: (sites, sites), 4096: (10 * sites, 16 * sites),
        8192: (36 * sites, 64 * sites), 65536: (2080 * sites, 4096 * sites),
    }[T]
    assert added(before, FUSED) == (
        (sites, 0, 10 * sites, 16 * sites) if fused else (0, 0, 0, 0)
    )
    assert added(before, STREAM) == (
        (0, 0, 0, 0) if fused else walked
    )
    if fused:
        assert (
            fa._walk_head_chunk(H, T, D, 2, wide=4, narrow=1),
            fa._walk_head_chunk(H, T, D, 2, wide=7, narrow=2),
        ) == {12: (4, 2), 25: (5, 1)}[H]


# the Trinity-Mini cell's attention layers, [B, H, T, D] on 4 key/value
# heads: (window, block stated or None for the call's own, blocks a head
# walks, blocks under its diagonal, score tiles the backward multiplies of
# its edge blocks in four row strips, tiles those blocks hold)
WINDOW_SITES = {
    # the global layer: the triangle at T = 16384, one-pass backward;
    # 16 blocks on the diagonal, 10 of 16 tiles each
    "global": (None, None, 136, 136, 160, 256),
    # a window layer as the model calls it: the band in blocks of 1024,
    # and the 14 blocks its far edge crosses, 10 of 16 tiles each too
    "window": (2048, None, 45, 136, 300, 480),
    "window_512": (2048, 512, 150, 528, 600, 960),
    # a window off the block: two far blocks crossed (row - col < 476
    # fifteen times, 15 of 16 tiles; < -548 fourteen times, 3 of 16)
    "window_off_block": (1500, None, 45, 136, 427, 720),
    # the Phi-4-mini-flash cell's window layer, 40 heads on 20: a window
    # of 512 in blocks of 1024, 9 of 16 tiles on the diagonal and 3 of
    # 16 in the block before
    "window_phi": (512, None, 31, 136, 189, 496),
}
WINDOW_HEADS = {"window_phi": (40, 20)}


@pytest.mark.parametrize("site", list(WINDOW_SITES))
@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_window_attention_compiles_at_the_cell(
    site, direction, one_chip, monkeypatch
):
    monkeypatch.setattr(fa, "_interpret_default", lambda: False)
    window, block, walked, under, multiplied, held = WINDOW_SITES[site]
    H, Hkv = WINDOW_HEADS.get(site, (32, 4))
    B, T, D = 1, 16384, 128
    qkv = [
        jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=one_chip)
        for s in ((B, H, T, D), (B, Hkv, T, D), (B, Hkv, T, D))
    ]

    def attend(q, k, v):
        return fa.flash_attention(
            q, k, v, causal=True, force="pallas", layout="bhtd",
            window=window, block_q=block, block_k=block,
        )

    before = trace_counts.snapshot()
    if direction == "fwd":
        compiled = _compile_for_chip(attend, *qkv)
    else:
        compiled = _compile_for_chip(
            jax.grad(
                lambda q, k, v: attend(q, k, v).astype(jnp.float32).sum(),
                argnums=(0, 1, 2),
            ),
            *qkv,
        )
    assert fa._one_pass_fits(T, D, 2)
    stem = "flash_attn_window" if window else "flash_attn"
    want = [f"{stem}_fwd"] + ([f"{stem}_bwd"] if direction == "bwd" else [])
    text = compiled.as_text()
    for kernel in want:
        assert kernel in text, kernel
    assert ("flash_attn_window" in text) == bool(window)
    assert "flash_attn_bwd_d" not in text and "window_bwd_d" not in text
    sites = len(want)
    n = T // (block or fa._TRI_BLOCK)
    assert under == n * (n + 1) // 2
    assert added(before, STREAM) == (sites, 0, walked * sites, n * n * sites)
    assert added(before, WINDOW) == (
        (walked * sites, under * sites) if window else (0, 0)
    )
    # the forward multiplies every tile, the backward its strips' spans
    assert added(before, EDGE) == (
        held + multiplied * (sites - 1), held * sites
    )


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_block_diffusion_attention_compiles_at_the_cell(
    direction, one_chip
):
    """The SDAR cell's attention site: a doubled row of 2 x 8192 positions
    at 32 / 4 heads of 128 under the block-diffusion rule over blocks of
    4, the ``flash_attn_bd_*`` kernels in blocks of 1024 (80 of the
    grid's 256), the backward in one pass."""
    B, H, Hkv, T, D = 1, 32, 4, 16384, 128
    qkv = [
        jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=one_chip)
        for s in ((B, H, T, D), (B, Hkv, T, D), (B, Hkv, T, D))
    ]

    def attend(q, k, v):
        return fa.block_diffusion_attention(
            q, k, v, block_len=4, layout="bhtd", force="pallas",
            interpret=False,
        )

    before = trace_counts.snapshot()
    if direction == "fwd":
        compiled = _compile_for_chip(attend, *qkv)
    else:
        compiled = _compile_for_chip(
            jax.grad(
                lambda q, k, v: attend(q, k, v).astype(jnp.float32).sum(),
                argnums=(0, 1, 2),
            ),
            *qkv,
        )
    want = ["flash_attn_bd_fwd"] + (
        ["flash_attn_bd_bwd"] if direction == "bwd" else []
    )
    text = compiled.as_text()
    for kernel in want:
        assert kernel in text, kernel
    assert "flash_attn_fwd" not in text and "flash_attn_bwd" not in text
    sites = len(want)
    assert added(before, fa._BD) == (80 * sites, 256 * sites)
    # the forward multiplies its clean edges whole (16 x 16 tiles) and its
    # noised x noised blocks in 8 strips; the backward every edge in strips
    assert added(before, EDGE) == (
        (8 * 8 + 16 * 16) + (8 * 8 + 16 * 10) * (sites - 1),
        (8 * 64 + 16 * 16) * sites,
    )


def test_window_attention_compiles_split_beyond_one_pass(
    one_chip, monkeypatch
):
    """A head's float32 dq no longer fits VMEM: the band's dq and dk / dv
    kernels, at the source's whole context of 131,072 (the 128 blocks a
    side that the step tables may hold)."""
    monkeypatch.setattr(fa, "_interpret_default", lambda: False)
    B, H, Hkv, T, D = 1, 8, 1, 131072, 128
    assert not fa._one_pass_fits(T, D, 2)
    qkv = [
        jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=one_chip)
        for s in ((B, H, T, D), (B, Hkv, T, D), (B, Hkv, T, D))
    ]
    before = trace_counts.snapshot()
    text = _compile_for_chip(
        jax.grad(
            lambda q, k, v: fa.flash_attention(
                q, k, v, causal=True, force="pallas", layout="bhtd",
                window=2048,
            ).astype(jnp.float32).sum(),
            argnums=(0, 1, 2),
        ),
        *qkv,
    ).as_text()
    for kernel in ("window_fwd", "window_bwd_dq", "window_bwd_dkv"):
        assert f"flash_attn_{kernel}" in text, kernel
    # 128 blocks a side: 1 + 2 + 126 * 3 of 8,256 a kernel, three kernels
    assert added(before, WINDOW) == (3 * 381, 3 * 8256)


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_streaming_attention_compiles_at_heads_of_256(
    direction, one_chip, monkeypatch
):
    """The Qwen3-Next cell's attention layer, [1, 16 / 2, 8192, 256]: a
    head twice as wide as any the streaming kernels had run. A head wider
    than the lanes keeps blocks of 512 (``_validate_blocks``: the
    1024-blocks triangle is for heads no wider than measured), so the
    triangle path walks 136 of 256 blocks a kernel."""
    monkeypatch.setattr(fa, "_interpret_default", lambda: False)
    B, H, Hkv, T, D = 1, 16, 2, 8192, 256
    qkv = [
        jax.ShapeDtypeStruct((B, h, T, D), jnp.bfloat16, sharding=one_chip)
        for h in (H, Hkv, Hkv)
    ]

    def attend(q, k, v):
        return fa.flash_attention(
            q, k, v, causal=True, force="pallas", layout="bhtd"
        )

    before = trace_counts.snapshot()
    if direction == "fwd":
        text = _compile_for_chip(attend, *qkv).as_text()
        want = ["flash_attn_fwd"]
    else:
        text = _compile_for_chip(
            jax.grad(
                lambda q, k, v: attend(q, k, v).astype(jnp.float32).sum(),
                argnums=(0, 1, 2),
            ),
            *qkv,
        ).as_text()
        want = ["flash_attn_fwd"] + (
            ["flash_attn_bwd"] if fa._one_pass_fits(T, D, 2)
            else ["flash_attn_bwd_dq", "flash_attn_bwd_dkv"]
        )
    for kernel in want:
        assert kernel in text, kernel
    assert "flash_attn_fused" not in text
    sites = len(want)
    assert T // fa._BLOCK == 16
    assert added(before, STREAM) == (sites, 0, 136 * sites, 256 * sites)


# the serial pass between a kind's two stretches, forward and reversed
PASS_KERNELS = ["delta_state_pass", "delta_state_pass_rev"]

# T, key heads, value heads, key width, value width, inverse by halves
DELTA_RULE_CELLS = {
    # token-major lane blocks of a head, the product form
    "qwen3_next": (8192, 16, 32, 128, 128, False),
    # heads of no whole tiles, head-major; beta to 2, by halves
    "olmo_hybrid": (16384, 30, 30, 96, 192, True),
}


@pytest.mark.parametrize("cell", list(DELTA_RULE_CELLS))
@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_delta_rule_chunk_kernels_compile_at_the_cell(
    direction, cell, one_chip, monkeypatch
):
    """The Qwen3-Next cell's Gated DeltaNet layer, 1 x 8192 at 16 key / 32
    value heads of 128 in chunks of 64, bfloat16, and the Olmo-Hybrid
    cell's, 1 x 16384 at 30 heads of 96 / 192: the two kernels around
    the serial pass forward, all four under ``grad``; no [64, 64] square
    of a value head and chunk is left in the program around them."""
    from dlrover_tpu.ops import gated_delta

    monkeypatch.setattr(fa, "_interpret_default", lambda: False)
    T, Hk, Hv, dk, dv, halves = DELTA_RULE_CELLS[cell]
    B, C = 1, 64

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = [
        sds((B, T, Hk, dk)), sds((B, T, Hk, dk)), sds((B, T, Hv, dv)),
        sds((B, T, Hv), jnp.float32), sds((B, T, Hv), jnp.float32),
    ]

    def rule(*a):
        return gated_delta.gated_delta_chunked(*a, C, halves)

    before = trace_counts.snapshot()
    if direction == "fwd":
        text = _compile_for_chip(rule, *args).as_text()
        want, steps = ["gdn_chunk_wy_fwd", "gdn_chunk_read_fwd"], T // C
        want += PASS_KERNELS[:1]
    else:
        text = _compile_for_chip(
            jax.grad(lambda *a: jnp.sum(rule(*a) ** 2), argnums=range(5)),
            *args,
        ).as_text()
        want = [
            "gdn_chunk_wy_fwd", "gdn_chunk_read_fwd",
            "gdn_chunk_wy_bwd", "gdn_chunk_read_bwd", *PASS_KERNELS,
        ]
        steps = 2 * T // C
    for kernel in want:
        assert kernel in text, kernel
    assert f"f32[{T // C},{B},{Hk},{Hv // Hk},{C},{C}]" not in text
    assert "while(" not in text  # the pass is no loop of XLA's any more
    assert added(before, GDN + PASS) == (1, steps, 1, 1)


CONV_SHAPES = {
    # [B, T, C], with a bias or without: the convolution before the scan
    "nemotron3_nano_mamba2": ((1, 8192, 6144), True),
    "qwen3_next_deltanet": ((1, 8192, 8192), False),
    "ling3_kda": ((1, 8192, 12288), False),
}


@pytest.mark.parametrize("name", list(CONV_SHAPES))
@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_convolution_kernels_compile_at_the_cells(
    name, direction, one_chip, monkeypatch
):
    """``silu(causal_conv1d(...))`` at the three hybrid cells' widths,
    1 x 8192 in bfloat16 with float32 weights of four taps: the forward
    kernel, and under ``grad`` the backward kernel with the taps'
    weight-gradient sums inside it; no padded float32 copy of the tokens
    is left in the program around them."""
    from dlrover_tpu.ops import conv_kernels, mamba2

    monkeypatch.setattr(fa, "_interpret_default", lambda: False)
    shape, bias = CONV_SHAPES[name]
    C = shape[2]

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = [sds(shape, jnp.bfloat16), sds((4, C))] + [sds((C,))] * bias
    assert conv_kernels.fits(*args[:2])
    before = trace_counts.snapshot()
    if direction == "fwd":
        text = _compile_for_chip(mamba2.conv_silu, *args).as_text()
        want = ["conv_silu_fwd"]
    else:
        text = _compile_for_chip(
            jax.grad(
                lambda *a: jnp.sum(
                    mamba2.conv_silu(*a).astype(jnp.float32) ** 2
                ),
                argnums=range(len(args)),
            ),
            *args,
        ).as_text()
        want = ["conv_silu_fwd", "conv_silu_bwd"]
    for kernel in want:
        assert kernel in text, kernel
    if direction == "fwd":  # (the test's own loss is a float32 fusion)
        assert f"f32[{shape[0]},{shape[1]},{C}]" not in text
    assert f"f32[{shape[0]},{shape[1] + 3},{C}]" not in text
    assert added(before, CONV) == (1, 1)


# the gated norm after a scan at the hybrid cells: 1 x 8192 x 4096 in
# bfloat16 (the Olmo-Hybrid cell: 1 x 16384 x 5760, groups of a tile and a
# half); the group's width, the gate's width, the gate inside
GATED_NORM_SHAPES = {
    "ling_sigmoid_a_head": (128, 32, False),
    "qwen3_next_silu_outside": (128, 4096, False),
    "nemotron_silu_inside": (512, 4096, True),
    "olmo_hybrid_silu_outside_192": (192, 5760, False),
}


@pytest.mark.parametrize("name", list(GATED_NORM_SHAPES))
@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_gated_norm_kernels_compile_at_the_cells(
    name, direction, one_chip, monkeypatch
):
    """``weight * RMSNorm(o) * gate(z)`` at the three hybrid cells' shapes:
    the forward kernel, and under ``grad`` the backward kernel with the
    weight's gradient sums inside it; no float32 copy of the tokens is
    left in the program around them."""
    from dlrover_tpu.ops import gated_norm_kernels, mamba2

    monkeypatch.setattr(fa, "_interpret_default", lambda: False)
    width, gates, inside = GATED_NORM_SHAPES[name]
    B, T, C = (1, 16384, 5760) if width == 192 else (1, 8192, 4096)

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = [sds((B, T, C)), sds((B, T, gates)), sds((C,), jnp.float32)]
    assert gated_norm_kernels.fits(*args[:2], width)

    def site(o, z, w):
        return mamba2.gated_norm(None, o, z, w, width, 1e-6, inside)

    before = trace_counts.snapshot()
    if direction == "fwd":
        text = _compile_for_chip(site, *args).as_text()
        want = ["gated_norm_fwd"]
    else:
        text = _compile_for_chip(
            jax.grad(
                lambda *a: jnp.sum(site(*a).astype(jnp.float32) ** 2),
                argnums=(0, 1, 2),
            ),
            *args,
        ).as_text()
        want = ["gated_norm_fwd", "gated_norm_bwd"]
    for kernel in want:
        assert kernel in text, kernel
    if direction == "fwd":  # (the test's own loss is a float32 fusion)
        assert f"f32[{B},{T},{C}]" not in text
    assert f"f32[{B},{T},{C // width},{width}]" not in text
    assert added(before, GATE) == (1, 1)


# the Mamba-2 chunked scan at the Nemotron cell: 64 heads of 64 in 8
# groups, a state of 128, chunks of 128, 1 x 8192 tokens
SSD_SHAPES = {
    "nemotron3_nano_bf16": jnp.bfloat16,
    "nemotron3_nano_f32": jnp.float32,
}


@pytest.mark.parametrize("name", list(SSD_SHAPES))
@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_ssd_scan_kernels_compile_at_the_cell(
    name, direction, one_chip, monkeypatch
):
    """The scan as the Nemotron mixer makes it, the kernel way: the
    forward kernel, and under ``grad`` the reversed kernel beside it; no
    ``[Q, Q]`` decay square and no float32 copy of the tokens is an array
    of the program around them."""
    from dlrover_tpu.models.config import TransformerConfig
    from dlrover_tpu.ops import mamba2, ssd_kernels

    monkeypatch.setattr(fa, "_interpret_default", lambda: False)
    act = SSD_SHAPES[name]
    B, T, H, P, G, N, Q = 1, 8192, 64, 64, 8, 128, 128
    cfg = TransformerConfig(
        vocab_size=64, num_layers=1, layer_pattern="M", model_dim=256,
        num_heads=2, mlp_dim=64, max_seq_len=T, ssm_heads=H,
        ssm_head_dim=P, ssm_state=N, ssm_groups=G, ssm_chunk=Q,
    )

    def sds(shape, dtype=act):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    p = jax.eval_shape(
        lambda: mamba2.init_mamba2_params(jax.random.PRNGKey(0), cfg, act)
    )
    p = jax.tree_util.tree_map(lambda x: sds(x.shape, x.dtype), p)
    u = sds((B, T, cfg.model_dim))
    assert ssd_kernels.fits(
        sds((B, T, H, P)), sds((B, T, H), jnp.float32), sds((B, T, G, N)),
        sds((B, T, G, N)), Q,
    )

    def site(p, u):
        return mamba2.mamba2_mixer(u, p, cfg, 1e-5)

    before = trace_counts.snapshot()
    if direction == "fwd":
        text = _compile_for_chip(site, p, u).as_text()
        want = ["ssd_scan_fwd"]
    else:
        text = _compile_for_chip(
            jax.grad(
                lambda *a: jnp.sum(site(*a).astype(jnp.float32) ** 2),
                argnums=(0, 1),
            ),
            p, u,
        ).as_text()
        want = ["ssd_scan_fwd", "ssd_scan_bwd"]
    for kernel in want:
        assert kernel in text, kernel
    # no head's decay square outside the kernels
    assert f"{G},{H // G},{Q},{Q}]" not in text
    if act == jnp.bfloat16 and direction == "fwd":
        # (the test's own loss is a float32 fusion)
        assert f"f32[{B},{T},{H * P}]" not in text
        assert f"f32[{B},{T},{H},{P}]" not in text
    assert added(before, SSD) == (1, 1)


CHANNEL_KERNELS = [
    "gdn_channel_wy_fwd", "gdn_channel_read_fwd",
    "gdn_channel_wy_bwd", "gdn_channel_read_bwd",
]


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_vector_decay_chunk_kernels_compile_at_the_cell(
    direction, one_chip, monkeypatch
):
    """The Ling cell's Kimi-Delta-Attention layer, 1 x 8192 at 32 heads of
    128 / 128 in chunks of 64, bfloat16, the decay a vector over the key's
    channels: the two kernels around the serial pass forward, all four
    under ``grad``; no [64, 64] square of a head and chunk and no decayed
    copy of a chunk's keys a row block is left in the program around
    them."""
    from dlrover_tpu.ops import gated_delta

    monkeypatch.setattr(fa, "_interpret_default", lambda: False)
    B, T, H, d, C = 1, 8192, 32, 128, 64

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = [
        sds((B, T, H, d)), sds((B, T, H, d)), sds((B, T, H, d)),
        sds((B, T, H), jnp.float32), sds((B, T, H, d), jnp.float32),
    ]

    def rule(*a):
        return gated_delta.gated_delta_chunked(*a, C)

    before = trace_counts.snapshot()
    if direction == "fwd":
        text = _compile_for_chip(rule, *args).as_text()
        want, steps = CHANNEL_KERNELS[:2] + PASS_KERNELS[:1], T // C
    else:
        text = _compile_for_chip(
            jax.grad(lambda *a: jnp.sum(rule(*a) ** 2), argnums=range(5)),
            *args,
        ).as_text()
        want, steps = CHANNEL_KERNELS + PASS_KERNELS, 2 * T // C
    for kernel in want:
        assert kernel in text, kernel
    assert "gdn_chunk_" not in text  # the scalar kind's are not this site's
    assert "while(" not in text  # the pass is no loop of XLA's any more
    n = T // C
    assert f"f32[{n},{B},{H},{C},{C}]" not in text
    assert f"bf16[{n},{B},{H},{C // 16},{C},{d}]" not in text
    assert added(before, GDN + PASS) == (1, steps, 1, 1)


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_attention_of_192_and_128_compiles_as_the_program_calls_it(
    direction, one_chip, monkeypatch
):
    """The Ling-3.0-flash cell's latent attention, [1, 32, 8192, 192 |
    128]: the program pads q, k and v to the kernels' one width of 256,
    scales by 1 / sqrt(192) and slices the output
    (``models/transformer._attention_of_two_widths``). 32 key/value heads
    of 256 in blocks of 512: the triangle walks 136 of 256 blocks a
    kernel, and the call is counted as 256 lanes for 192 stated."""
    from dlrover_tpu.models import transformer

    monkeypatch.setattr(fa, "_interpret_default", lambda: False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    B, H, T = 1, 32, 8192
    qkv = [
        jax.ShapeDtypeStruct((B, H, T, D), jnp.bfloat16, sharding=one_chip)
        for D in (192, 192, 128)
    ]

    def attend(q, k, v):
        return transformer._attention_of_two_widths(q, k, v, None)

    before = trace_counts.snapshot()
    if direction == "fwd":
        compiled = _compile_for_chip(attend, *qkv)
        want = ["flash_attn_fwd"]
        assert compiled.output_shardings is not None
        (out,) = jax.tree_util.tree_leaves(
            jax.eval_shape(attend, *qkv)
        )
        assert out.shape == (B, H, T, 128)
    else:
        compiled = _compile_for_chip(
            jax.grad(
                lambda q, k, v: attend(q, k, v).astype(jnp.float32).sum(),
                argnums=(0, 1, 2),
            ),
            *qkv,
        )
        want = ["flash_attn_fwd"] + (
            ["flash_attn_bwd"] if fa._one_pass_fits(T, 256, 2)
            else ["flash_attn_bwd_dq", "flash_attn_bwd_dkv"]
        )
    text = compiled.as_text()
    for kernel in want:
        assert kernel in text, kernel
    assert "flash_attn_fused" not in text
    sites = len(want)
    assert added(before, STREAM) == (sites, 0, 136 * sites, 256 * sites)
    assert added(before, LANES) in ((256, 192), (512, 384))


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_selective_scan_kernels_compile_at_the_cell(
    direction, one_chip, monkeypatch
):
    """The Phi-4-mini-flash cell's Mamba-1 recurrence, 1 x 16384 steps of
    5120 channels with 16 states each: the forward kernel, and under
    ``grad`` the backward kernel with a block's states made again in
    VMEM. Nothing of [T, channels, states] is in the program around them:
    the largest arrays beside the tokens are the state that enters each
    block of 128 steps and the channel sums still spread over 128 lanes."""
    from dlrover_tpu.ops import selective_scan as ss

    monkeypatch.setattr(fa, "_interpret_default", lambda: False)
    B, T, C, N = 1, 16384, 5120, 16

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = [
        sds((B, T, C), jnp.bfloat16), sds((B, T, C)), sds((C, N)),
        sds((B, T, N), jnp.bfloat16), sds((B, T, N), jnp.bfloat16),
        sds((C,)),
    ]
    assert ss.fits(args[0], args[2])
    before = trace_counts.snapshot()
    if direction == "fwd":
        text = _compile_for_chip(ss.selective_scan, *args).as_text()
        want, steps = ["sscan_fwd"], T
    else:
        text = _compile_for_chip(
            jax.grad(
                lambda *a: jnp.sum(
                    ss.selective_scan(*a).astype(jnp.float32) ** 2
                ),
                argnums=range(len(args)),
            ),
            *args,
        ).as_text()
        want, steps = ["sscan_fwd", "sscan_bwd"], 3 * T
    for kernel in want:
        assert kernel in text, kernel
    for states in (f"{T},{C},{N}]", f"{T},{N},{C}]", f"{C},{N},{T}]"):
        assert states not in text, states
    assert f"f32[{B},{T // 128},{N},{C}]" in text  # a state a block
    assert added(before, SSCAN) == (1, 1, steps)


@pytest.mark.parametrize("kind", ["W", "*"])
@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_differential_attention_compiles_as_the_program_calls_it(
    kind, direction, one_chip, monkeypatch
):
    """The Phi-4-mini-flash cell's differential attention, 40 query heads
    on 20 key heads of 64 with value pairs of 128 at T = 16384: ONE call a
    layer, q and k padded to the pairs' width
    (``models/transformer._diff_attention``), the window layer on the
    band of a 512-key window in blocks of 1024 (31 of 136 blocks), the
    full layer on the triangle; 20 pairs, each score map once."""
    from dlrover_tpu.models import transformer
    from dlrover_tpu.models.config import TransformerConfig

    monkeypatch.setattr(fa, "_interpret_default", lambda: False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = TransformerConfig(
        vocab_size=128, num_layers=2, layer_pattern=kind + "-",
        attn_window=512 if kind == "W" else 0, attn_kind="diff",
        attn_bias=True, positions="none", model_dim=2560, num_heads=40,
        num_kv_heads=20, attn_head_dim=64, dense_mlp_dim=128,
        max_seq_len=16384, first_layer=15, swiglu=True,
    )
    shapes = jax.eval_shape(
        lambda: transformer.init_params(jax.random.PRNGKey(0), cfg)
    )["layers"][0]
    layer = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        shapes,
    )
    x = jax.ShapeDtypeStruct((1, 16384, 2560), jnp.bfloat16,
                             sharding=one_chip)

    def attend(x, layer):
        return transformer._diff_attention(x, layer, cfg, None, kind, 15)[0]

    before = trace_counts.snapshot()
    if direction == "fwd":
        compiled = _compile_for_chip(attend, x, layer)
    else:
        compiled = _compile_for_chip(
            jax.grad(
                lambda x, layer: attend(x, layer).astype(jnp.float32).sum(),
                argnums=(0, 1),
            ),
            x, layer,
        )
    stem = "flash_attn_window" if kind == "W" else "flash_attn"
    want = [f"{stem}_fwd"] + ([f"{stem}_bwd"] if direction == "bwd" else [])
    text = compiled.as_text()
    for kernel in want:
        assert kernel in text, kernel
    assert ("flash_attn_window" in text) == (kind == "W")
    sites = len(want)
    walked = 31 if kind == "W" else 136
    assert added(before, STREAM) == (sites, 0, walked * sites, 256 * sites)
    assert added(before, WINDOW) == (
        (31 * sites, 136 * sites) if kind == "W" else (0, 0)
    )
    assert added(before, LANES) == (128, 64)
    assert added(before, DIFF) == (20, 20)


# the bf16 [50257, 768] leaf compiles too, but takes ~19 s: f32 here
ADAM_LEAVES = {
    "gpt2_wte": (50257, 768),
    "gpt2_xl_mlp": (1600, 6400),
}


@pytest.mark.parametrize("leaf", list(ADAM_LEAVES))
def test_adam8_update_compiles(leaf, one_chip):
    """``adamw_8bit``'s ``update``, the statement, for the chip: over a
    ``BLOCKS`` leaf (50257 rows are no whole tiles) and a ``TILES`` one,
    neither with a Pallas call."""
    from dlrover_tpu.ops.quantized_optim import BLOCKS, TILES, adamw_8bit

    tx = adamw_8bit(1e-3)
    params = {
        "w": jax.ShapeDtypeStruct(
            ADAM_LEAVES[leaf], jnp.float32, sharding=one_chip
        )
    }
    state = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        jax.eval_shape(tx.init, params),
    )
    want = {"gpt2_wte": BLOCKS, "gpt2_xl_mlp": TILES}[leaf]
    assert state.mu["w"].layout == state.nu["w"].layout == want
    lowered = jax.jit(tx.update).lower(params, state, params)
    assert "tpu_custom_call" not in lowered.as_text()
    assert lowered.compile().memory_analysis() is not None


def _entry_results(hlo_text):
    """(op, element counts of its result's arrays) for every instruction
    of the optimised HLO's entry computation."""
    import math
    import re

    entry = hlo_text[hlo_text.index("\nENTRY"):]
    out = []
    for line in entry.splitlines():
        m = re.match(
            r"\s*(?:ROOT )?%[\w.\-]+ = (\(.*?\)|\S+) ([\w\-]+)\(", line
        )
        if m:
            arrays = re.findall(r"\b([a-z]+\d+)\[([\d,]*)\]", m.group(1))
            out.append((m.group(2), [
                (dtype, math.prod(int(d) for d in dims.split(",") if d))
                for dtype, dims in arrays
            ]))
    return out


@pytest.mark.parametrize("shape", [(8, 2048, 1024), (2048, 2048)], ids=str)
def test_adam8_tile_view_stays_a_bitcast(shape, one_chip):
    """``adamw_8bit``'s ``update`` on a leaf of whole (8, 128)
    tiles: the moments' view of the gradient, and delta's way back, must
    cost nothing on the chip. No ``reshape`` / ``copy`` / ``transpose``
    of the leaf's size in the entry computation (the [nblocks, 128]
    layout had two), no Pallas kernel, and one leaf-sized f32 result,
    the new parameter: delta is fused into the apply."""
    import math

    import optax

    from dlrover_tpu.ops.quantized_optim import TILES, adamw_8bit

    tx = adamw_8bit(3e-4, weight_decay=0.1, min_quantized_size=4096)

    def step(p, g, st):
        u, st = tx.update(g, st, p)
        return optax.apply_updates(p, u), st

    params = {"w": jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)}
    state = jax.eval_shape(tx.init, params)
    assert state.mu["w"].layout == state.nu["w"].layout == TILES
    state = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        state,
    )
    lowered = jax.jit(step, donate_argnums=(0, 2)).lower(
        params, params, state
    )
    assert "tpu_custom_call" not in lowered.as_text()
    results = _entry_results(lowered.compile().as_text())
    n = math.prod(shape)
    moved = [
        (op, arrays) for op, arrays in results
        if op in ("reshape", "copy", "transpose")
        and any(count == n for _, count in arrays)
    ]
    assert not moved, moved
    leaf_f32 = [
        op for op, arrays in results
        if op == "fusion" and ("f32", n) in arrays
    ]
    assert len(leaf_f32) == 1, results


# leaves of the benchmark's int8 configurations: the experts (rows of
# tiles along the scales' lanes), a head 21 blocks wide of rows no whole
# lane row, the head whose blocks lie along the lanes, a leaf of many
# single tiles (the leading dimension along the lanes), and a wide one
ONE_PASS_LEAVES = [
    (64, 2048, 1024), (3712, 2688), (2688, 16384), (2048, 16, 128),
    (2048, 50304),
]


@pytest.mark.parametrize("shape", ONE_PASS_LEAVES, ids=str)
def test_adam8_one_pass_step_compiles_in_place(shape, one_chip, monkeypatch):
    """``adamw_8bit``'s ``update_and_apply`` on a whole-tile
    leaf for the chip: one ``q8_adam_step`` call, the parameter, codes and
    scales aliased onto its results (the program needs no temporary of
    the leaf's size), nothing of the leaf's size moved around it and the
    scales, which the kernel reads a tile a lane, not copied either: the
    view is the order the chip keeps them in (``_lane_dim``). The
    kernel's code is as long as one strip: no longer for a wider leaf."""
    import math

    from dlrover_tpu.ops import quantized_optim

    monkeypatch.setattr(quantized_optim, "_on_tpu", lambda: True)
    monkeypatch.setattr(quantized_optim, "_interpret", lambda: False)
    tx = quantized_optim.adamw_8bit(
        3e-4, weight_decay=0.1, min_quantized_size=4096
    )
    params = {"w": jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)}
    state = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        jax.eval_shape(tx.init, params),
    )

    def step(p, g, st):
        return tx.update_and_apply(g, st, p, scale=jnp.float32(0.5))

    lowered = jax.jit(step, donate_argnums=(0, 2)).lower(
        params, params, state
    )
    assert lowered.as_text().count("tpu_custom_call") == 1
    compiled = lowered.compile()
    text = compiled.as_text()
    assert "q8_adam_step" in text
    n = math.prod(shape)
    moved = [
        (op, arrays) for op, arrays in _entry_results(text)
        if op in ("reshape", "copy", "transpose", "fusion")
        and any(count >= n // 128 for _, count in arrays)
    ]
    assert not moved, moved
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < n // 128
    # parameter (4 B), two moments' codes (1 B each) and their scales
    assert memory.alias_size_in_bytes >= 6 * n
    assert memory.generated_code_size_in_bytes < 256 << 10


@pytest.mark.parametrize("dim", [64, 128])
@pytest.mark.parametrize("op", ["gather", "scatter"])
def test_device_tier_kernel_compiles(op, dim, one_chip, monkeypatch):
    from dlrover_tpu.ops.embedding.device_tier import _Kernels

    monkeypatch.setenv("DLROVER_TPU_PALLAS", "compile")
    n, capacity = 4096, 1 << 16
    kernels = _Kernels("pallas")

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    slots = sds((n,), jnp.int32)
    table = sds((capacity, dim), jnp.float32)
    if op == "gather":
        _compile_for_chip(
            kernels._build_gather(n, capacity, dim), slots, table
        )
    else:
        _compile_for_chip(
            kernels._build_scatter(n, capacity, dim),
            slots, sds((n, dim), jnp.float32), table,
        )


def test_sharded_attention_compiles_for_four_chips(topo, monkeypatch):
    """On a mesh of several chips the model's attention runs the kernel
    under shard_map: GSPMD refuses to partition a Mosaic kernel by
    itself, which is what every dp/fsdp/tp step hit on the TPU before."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from dlrover_tpu.models.transformer import _causal_attention
    from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh

    monkeypatch.setattr(fa, "_interpret_default", lambda: False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = build_mesh(MeshConfig(fsdp=2, tp=2), devices=topo.devices[:4])
    sharding = NamedSharding(mesh, P(("dp", "fsdp"), "tp", None, None))
    qkv = [
        jax.ShapeDtypeStruct(
            ATTENTION_SHAPES["gpt2_124m"], jnp.bfloat16, sharding=sharding
        )
    ] * 3
    with pytest.raises(NotImplementedError, match="automatically partitioned"):
        jax.jit(
            lambda q, k, v: fa.flash_attention(
                q, k, v, causal=True, layout="bhtd"
            )
        ).lower(*qkv)
    _compile_for_chip(
        lambda q, k, v: _causal_attention(q, k, v, mesh, layout="bhtd"),
        *qkv,
    )


def test_sharded_delta_rule_compiles_for_four_chips(topo, monkeypatch):
    """The Gated DeltaNet mixer at the Qwen3-Next widths on a mesh of four
    chips: alone its kernels cannot be partitioned; the mixer runs them
    under shard_map, batch over fsdp and heads over tp."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from dlrover_tpu.ops import gated_delta
    from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh

    monkeypatch.setattr(fa, "_interpret_default", lambda: False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = build_mesh(MeshConfig(fsdp=2, tp=2), devices=topo.devices[:4])
    B, T, Hk, Hv, d, C = 2, 2048, 16, 32, 128, 64

    def sds(shape, dtype=jnp.bfloat16):
        spec = P(("dp", "fsdp"), None, "tp", None)[:len(shape)]
        return jax.ShapeDtypeStruct(
            shape, dtype, sharding=NamedSharding(mesh, P(*spec))
        )

    args = [
        sds((B, T, Hk, d)), sds((B, T, Hk, d)), sds((B, T, Hv, d)),
        sds((B, T, Hv), jnp.float32), sds((B, T, Hv), jnp.float32),
    ]
    with pytest.raises(NotImplementedError, match="automatically partitioned"):
        jax.jit(
            lambda *a: gated_delta.gated_delta_chunked(*a, C)
        ).lower(*args)
    text = _compile_for_chip(
        jax.grad(
            lambda *a: jnp.sum(gated_delta._delta_rule(*a, C, mesh) ** 2),
            argnums=range(5),
        ),
        *args,
    ).as_text()
    for kernel in ("gdn_chunk_wy_bwd", "gdn_chunk_read_bwd", *PASS_KERNELS):
        assert kernel in text, kernel
    with pytest.raises(ValueError, match="do not divide dp\\*fsdp=2"):
        gated_delta._delta_rule(
            *(jnp.zeros(a.shape[:0] + (3,) + a.shape[1:], a.dtype)
              for a in args), C, mesh,
        )


def test_sharded_vector_decay_rule_compiles_for_four_chips(topo, monkeypatch):
    """The same for a decay that is a vector over the key's channels, at
    the Ling widths: ``g`` has four axes and is sharded as ``q`` is, batch
    over fsdp and heads over tp; all four of the kind's kernels are in the
    step."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from dlrover_tpu.ops import gated_delta
    from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh

    monkeypatch.setattr(fa, "_interpret_default", lambda: False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = build_mesh(MeshConfig(fsdp=2, tp=2), devices=topo.devices[:4])
    B, T, H, d, C = 2, 2048, 32, 128, 64

    def sds(shape, dtype=jnp.bfloat16):
        spec = P(("dp", "fsdp"), None, "tp", None)[:len(shape)]
        return jax.ShapeDtypeStruct(
            shape, dtype, sharding=NamedSharding(mesh, P(*spec))
        )

    args = [
        sds((B, T, H, d)), sds((B, T, H, d)), sds((B, T, H, d)),
        sds((B, T, H), jnp.float32), sds((B, T, H, d), jnp.float32),
    ]
    with pytest.raises(NotImplementedError, match="automatically partitioned"):
        jax.jit(
            lambda *a: gated_delta.gated_delta_chunked(*a, C)
        ).lower(*args)
    text = _compile_for_chip(
        jax.grad(
            lambda *a: jnp.sum(gated_delta._delta_rule(*a, C, mesh) ** 2),
            argnums=range(5),
        ),
        *args,
    ).as_text()
    for kernel in CHANNEL_KERNELS + PASS_KERNELS:
        assert kernel in text, kernel
    # a device's share: one batch element, 16 heads
    assert f"f32[1,{T},{16 * d}]" in text


def test_explicit_sync_step_over_dp_x_tp_compiles_for_four_chips(
    topo, monkeypatch
):
    """The explicit gradient sync (``comm_overlap``) on a dp x tp mesh
    calls the model inside a region that is manual over dp only; the
    kernel then sits under a nested ``shard_map`` over the axes that
    region left to GSPMD. Before, the whole step was refused."""
    import dataclasses

    from dlrover_tpu.accel.dry_runner import _build
    from dlrover_tpu.accel.strategy import Strategy
    from dlrover_tpu.models.config import gpt2_small
    from dlrover_tpu.models.train import batch_sharding
    from dlrover_tpu.parallel.mesh import MeshConfig
    from dlrover_tpu.trainer.elastic.trainer import build_optimizer

    monkeypatch.setattr(fa, "_interpret_default", lambda: False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    # published width and heads; one layer and a small vocabulary (one
    # that tp divides) keep the compile to a few seconds
    cfg = dataclasses.replace(gpt2_small(), num_layers=1, vocab_size=1024)
    strategy = Strategy(mesh=MeshConfig(dp=2, tp=2), comm_overlap=True)
    _, mesh, step_fn, _, _, abstract_state = _build(
        strategy, cfg, build_optimizer("adamw", lr=3e-4),
        topo.devices[:4], donate=False, donate_inputs=False,
    )
    x = jax.ShapeDtypeStruct(
        (8, 1024), jnp.int32, sharding=batch_sharding(mesh)
    )
    lowered = step_fn.lower(abstract_state(), x, x)
    text = lowered.as_text()
    assert "tpu_custom_call" in text
    # collectives written by the program itself, before GSPMD has
    # partitioned anything: the explicit sync is what was lowered
    assert "stablehlo.all_reduce" in text
    lowered.compile()


def test_uneven_attention_on_a_tpu_mesh_names_what_does_not_divide(
    topo, monkeypatch
):
    """A batch the data axes do not divide (a small accumulation
    microbatch) cannot go under ``shard_map``; on the TPU the kernel
    would be refused by the compiler, so the model says which sizes to
    change instead."""
    from dlrover_tpu.models.transformer import _causal_attention
    from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = build_mesh(MeshConfig(fsdp=4), devices=topo.devices[:4])
    qkv = [jax.ShapeDtypeStruct((2, 12, 1024, 64), jnp.bfloat16)] * 3
    with pytest.raises(ValueError, match=r"batch 2 .* dp\*fsdp=4"):
        jax.eval_shape(
            lambda q, k, v: _causal_attention(q, k, v, mesh, layout="bhtd"),
            *qkv,
        )
