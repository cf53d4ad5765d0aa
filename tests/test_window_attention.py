"""``flash_attention(..., window=W)`` at a small size on the CPU: against
the written-out masked softmax in every family the call can reach, the
band kernels in interpret mode forward and backward with the blocks they
walk and the score tiles their edge blocks multiply counted, the plan of
an edge block's row strips, the step tables at the Trinity-Mini cell's
shape, and what refuses a window. (The model that uses it:
``test_afmoe.py``.)"""

import importlib
import importlib.util
import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.common import trace_counts
from trace_counted import EDGE, STREAM, WINDOW, added

# `dlrover_tpu.ops.flash_attention` the attribute is the function
fa = importlib.import_module("dlrover_tpu.ops.flash_attention")

RTOL = 2e-5


def _rel(got, want):
    got, want = np.asarray(got), np.asarray(want)
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


def _written_out(q, k, v, window):
    """[B, H, T, D]: the masked softmax, written out."""
    H, Hkv = q.shape[1], k.shape[1]
    k, v = (jnp.repeat(t, H // Hkv, axis=1) for t in (k, v))
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * q.shape[-1] ** -0.5
    T = q.shape[2]
    ahead = jnp.arange(T)[:, None] - jnp.arange(T)[None]
    seen = (ahead >= 0) & (ahead < window)
    return jnp.einsum(
        "bhqk,bhkd->bhqd", jax.nn.softmax(jnp.where(seen, s, -jnp.inf)), v
    )


def _qkv(T, H=4, Hkv=2, D=16, B=1, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed + T), 4)
    shapes = [(B, H, T, D), (B, Hkv, T, D), (B, Hkv, T, D), (B, H, T, D)]
    return [jax.random.normal(k, s) for k, s in zip(ks, shapes)]


def _value_and_cotangents(attend, q, k, v, do):
    def probe(q, k, v):
        o = attend(q, k, v)
        return jnp.sum(o * do), o

    (_, o), grads = jax.value_and_grad(probe, (0, 1, 2), has_aux=True)(
        q, k, v
    )
    return (o, *grads)


def _agree(got, want, tol=2e-5):
    for a, b in zip(got, want):
        a, b = np.asarray(a), np.asarray(b)
        assert np.max(np.abs(a - b)) <= tol * max(np.max(np.abs(b)), 1.0)


# T = 64 in blocks of 16: a window below the block, at it, a key and two
# past it (18: two far blocks crossed), at two blocks and past them, one
# key short of T (the whole triangle, masked at one corner), and one key
# (the diagonal alone, where dq and dk are 0)
BANDS = {
    # window: blocks a head walks, of 10 at or under the diagonal
    1: 4, 5: 7, 16: 7, 17: 7, 18: 9, 24: 9, 32: 9, 33: 9, 34: 10, 63: 10,
    # no window: the whole triangle, its diagonal blocks in strips
    None: 10,
}


def _edge_tiles(window, T=64, block=16, strips=4):
    """(score tiles with a pair some row sees, tiles held) over the blocks
    of a head that its rows see part of and not all, a tile ``block //
    strips`` a side: counted from the mask, pair by pair."""
    ahead = np.arange(T)[:, None] - np.arange(T)[None]
    seen = (ahead >= 0) & (ahead < (window or T))
    n, tile = T // block, block // strips
    tiles = seen.reshape(n, strips, tile, n, strips, tile).any((2, 5))
    tiles = tiles.transpose(0, 2, 1, 3)  # [i, j, strip, tile of keys]
    blocks = seen.reshape(n, block, n, block).transpose(0, 2, 1, 3)
    edge = blocks.any((2, 3)) & ~blocks.all((2, 3))
    return int(tiles[edge].sum()), int(edge.sum()) * strips * strips


def _by_length(window):
    return (window is None, window)


@pytest.mark.parametrize("one_pass", [True, False], ids=["one_pass", "split"])
@pytest.mark.parametrize("window", sorted(BANDS, key=_by_length))
def test_band_kernels_are_the_masked_softmax_and_walk_the_band_alone(
    window, one_pass, monkeypatch
):
    if not one_pass:
        monkeypatch.setattr(fa, "_ONE_PASS_MAX_BYTES", 0)
    # without a window, as many key/value heads as query heads (the band
    # cases are on two of four: a head group's dk and dv summed outside)
    q, k, v, do = _qkv(64, Hkv=4 if window is None else 2)
    before = trace_counts.snapshot()
    got = _value_and_cotangents(
        lambda q, k, v: fa.flash_attention(
            q, k, v, window=window, layout="bhtd", force="pallas",
            block_q=16, block_k=16, allow_fused=False,
        ), q, k, v, do,
    )
    kernels = 2 if one_pass else 3  # forward; backward in one pass or two
    walked = kernels * BANDS[window]
    assert added(before, WINDOW) == (
        (walked, kernels * 10) if window else (0, 0)
    )
    assert added(before, STREAM) == (kernels, 0, walked, kernels * 16)
    # the forward multiplies every tile its edge blocks hold, a backward
    # kernel those its strips see any of
    seen, held = _edge_tiles(window)
    assert added(before, EDGE) == (
        held + (kernels - 1) * seen, kernels * held
    )
    _agree(got, _value_and_cotangents(
        lambda q, k, v: _written_out(q, k, v, window or 64), q, k, v, do
    ))


# (block, lo, hi) of an edge block whose visible pairs are lo <= row - col
# < hi, and the score tiles its plan multiplies in four strips and in two
EDGE_BLOCKS = {
    "diagonal": ((1024, 0, None), 10, 3),
    # Trinity-Mini: 2048 in blocks of 1024, the block two before
    "trinity_far": ((1024, None, 0), 10, 3),
    # Phi-4-mini-flash: 512 in blocks of 1024, both blocks of the band
    "phi_diagonal": ((1024, 0, 512), 9, 3),
    "phi_far": ((1024, None, -512), 3, 1),
    # 1536: the far edge crosses the block before and the one before that
    "1536_far_1": ((1024, None, 512), 15, 4),
    "1536_far_2": ((1024, None, -512), 3, 1),
    # the tests' blocks of 16 under a window of 18
    "18_far_1": ((16, None, 2), 13, 4),
    "18_far_2": ((16, None, -14), 1, 1),
}


@pytest.mark.parametrize("strips", [4, 2, 1])
@pytest.mark.parametrize("edge", list(EDGE_BLOCKS))
def test_an_edge_blocks_strips_cover_what_its_rows_see_once(edge, strips):
    (block, lo, hi), of_16, of_4 = EDGE_BLOCKS[edge]
    plan = fa._edge_strips(block, lo, hi, strips)
    rows = block // strips
    covered = np.zeros((block, block), int)
    for r0, r1, c0, c1 in plan:
        assert r1 - r0 == rows and not (r0 % rows or c0 % rows or c1 % rows)
        assert 0 <= c0 < c1 <= block
        covered[r0:r1, c0:c1] += 1
    ahead = np.arange(block)[:, None] - np.arange(block)[None]
    seen = np.ones_like(covered, bool)
    if lo is not None:
        seen &= ahead >= lo
    if hi is not None:
        seen &= ahead < hi
    # every visible pair in exactly one strip's span, no strip over a span
    # it sees nothing of, and a strip's span no wider than its rows see
    assert (covered[seen] == 1).all() and covered.max() == 1
    for r0, r1, c0, c1 in plan:
        cols = np.flatnonzero(seen[r0:r1].any(0))
        assert c0 <= cols[0] < c0 + rows and c1 - rows <= cols[-1] < c1
    for r0 in range(0, block, rows):  # a strip left out sees nothing
        if not any(r0 == strip[0] for strip in plan):
            assert not seen[r0:r0 + rows].any()
    tiles = sum((c1 - c0) // rows for _, _, c0, c1 in plan)
    assert tiles == {4: of_16, 2: of_4, 1: 1}[strips]


def test_the_strips_of_a_block_are_whole_tiles_where_it_is_compiled():
    """Four strips where a strip's rows are whole lane tiles (the cells'
    blocks of 1024, and of 512 at heads of 256), fewer or one below;
    interpreted, the tests' blocks of 16 walk four strips of four rows."""
    compiled = {1024: 4, 512: 4, 256: 2, 128: 1, 16: 1, 8: 1}
    for block, strips in compiled.items():
        assert fa._edge_strip_count(block, interpret=False) == strips
    for block, strips in {1024: 4, 16: 4, 8: 4}.items():
        assert fa._edge_strip_count(block, interpret=True) == strips
    assert list(fa._far_edges(1024, 2048)) == [2]
    assert list(fa._far_edges(1024, 512)) == [1]
    assert list(fa._far_edges(1024, 1536)) == [1, 2]
    assert list(fa._far_edges(16, 16)) == [1]


def test_a_window_shorter_than_the_block_on_grouped_heads():
    """Phi-4-mini-flash's window layer in small: a window of a quarter and
    of half a block, eight query heads on two key/value heads."""
    for window in (4, 8):
        q, k, v, do = _qkv(64, H=8, Hkv=2)
        got = _value_and_cotangents(
            lambda q, k, v: fa.flash_attention(
                q, k, v, window=window, layout="bhtd", force="pallas",
                block_q=16, block_k=16, allow_fused=False,
            ), q, k, v, do,
        )
        _agree(got, _value_and_cotangents(
            lambda q, k, v: _written_out(q, k, v, window), q, k, v, do
        ))


@pytest.mark.parametrize("T,block", [(16384, 1024), (16384, 512), (64, 16)])
def test_the_step_tables_list_the_band(T, block):
    """Trinity-Mini's window at the cell's row: 45 of the 136 blocks under
    the diagonal in blocks of 1024, 150 of 528 in blocks of 512."""
    window = {16384: 2048, 64: 24}[T]
    n = T // block
    wb = fa._band_blocks(window, block)
    assert wb == {1024: 2, 512: 4, 16: 2}[block]
    by_query = np.stack(fa._triangle_steps(n, by_key=False, wb=wb), 1)
    by_key = np.stack(fa._triangle_steps(n, by_key=True, wb=wb), 1)
    band = {(i, j) for i in range(n) for j in range(max(0, i - wb), i + 1)}
    assert {tuple(s) for s in by_query.tolist()} == band
    assert {tuple(s) for s in by_key.tolist()} == band
    assert len(by_query) == len(by_key) == len(band)
    assert len(band) == fa._band_steps(n, block, window)
    assert len(band) == {1024: 45, 512: 150, 16: 9}[block]
    # a query block starts on its diagonal block and ends on its first
    # visible one; a key block ends where the window leaves it
    for i in range(n):
        mine = by_query[by_query[:, 0] == i][:, 1]
        assert mine.tolist() == list(range(i, max(0, i - wb) - 1, -1))
    for j in range(n):
        mine = by_key[by_key[:, 1] == j][:, 0]
        assert mine.tolist() == list(range(j, min(n - 1, j + wb) + 1))
    # every block the window can see any of is in the band, and no other
    for i in range(n):
        for j in range(i + 1):
            seen = i * block - (j * block + block - 1) < window
            assert seen == ((i, j) in band)


def test_without_a_window_the_tables_are_the_triangles():
    for by_key in (False, True):
        qi, kj = fa._triangle_steps(5, by_key)
        assert len(qi) == 15
        assert fa._band_steps(5, 16, None) is None


@pytest.mark.parametrize("family", ["reference", "fused", "rect", "odd"])
@pytest.mark.parametrize("window", [1, 7, 16, 40])
def test_a_window_is_exact_where_no_band_is_walked(family, window):
    """The jnp path, the fused square (as many key/value heads as query
    heads, T <= 1024), the rectangular grid (blocks not square) and a T no
    block divides: the window is a mask there, and the site says so."""
    T = 40 if family == "odd" else 64
    q, k, v, do = _qkv(T, Hkv=4 if family == "fused" else 2)
    kw = {
        "reference": dict(force="reference"),
        "fused": dict(force="pallas"),
        "rect": dict(force="pallas", block_q=16, block_k=32),
        "odd": dict(block_q=16, block_k=16),  # falls back to the jnp path
    }[family]
    before = trace_counts.snapshot()
    got = _value_and_cotangents(
        lambda q, k, v: fa.flash_attention(
            q, k, v, window=window, layout="bhtd", **kw
        ), q, k, v, do,
    )
    walked, under = added(before, WINDOW)
    assert walked == under and bool(under) == (
        family in ("fused", "rect") and window < T
    )
    _agree(got, _value_and_cotangents(
        lambda q, k, v: _written_out(q, k, v, window), q, k, v, do
    ))


def test_a_window_no_query_sees_past_is_the_plain_causal_call():
    q, k, v, _ = _qkv(64)
    call = lambda **kw: jax.make_jaxpr(lambda q, k, v: fa.flash_attention(  # noqa: E731
        q, k, v, layout="bhtd", force="pallas", block_q=16, block_k=16,
        allow_fused=False, **kw
    ))(q, k, v)
    before = trace_counts.snapshot()
    assert str(call(window=64)) == str(call(window=1000)) == str(call())
    assert added(before, WINDOW) == (0, 0)
    assert "flash_attn_window_fwd" in str(call(window=63))
    assert "flash_attn_window" not in str(call())


def test_residuals_of_a_window_are_its_logsumexp():
    q, k, v, _ = _qkv(64)
    o, lse = fa.flash_attention(
        q, k, v, window=24, layout="bhtd", force="pallas", block_q=16,
        block_k=16, allow_fused=False, return_residuals=True,
    )
    o_ref, lse_ref = fa.flash_attention(
        q, k, v, window=24, layout="bhtd", force="reference",
        return_residuals=True,
    )
    assert _rel(o, o_ref) <= RTOL and _rel(lse, lse_ref) <= RTOL


@pytest.mark.parametrize("bad", [
    dict(window=0), dict(window=-3), dict(window=2.0), dict(window=True),
    dict(window=8, causal=False),
    dict(window=8, mask_fn=lambda q, k: q >= k),
])
def test_a_window_that_is_none_is_refused(bad):
    q, k, v, _ = _qkv(64)
    with pytest.raises(ValueError, match="window"):
        fa.flash_attention(q, k, v, layout="bhtd", **bad)


# -- the reader of the two edge-tile counts -----------------------------------

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
EDGE_METRIC = "attn.edge_tiles_multiplied_pct"


def _edge_reader():
    path = os.path.join(REPO, "benchmark", "layer_metrics", EDGE_METRIC + ".py")
    spec = importlib.util.spec_from_file_location("edge_reader", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("pipeline,reads", [
    # a program that keeps no such counter (the parent of PR 54), one whose
    # attention never takes the streaming triangle, and the Trinity-Mini
    # cell's five layers: four bands of 2048 and the triangle, forward
    # whole and backward in four strips
    (None, None),
    ({}, None),
    ({"attn_edge_tiles": 0, "attn_edge_tiles_multiplied": 0}, None),
    ({"attn_edge_tiles": 4352}, None),
    ({"attn_edge_tiles": 4352, "attn_edge_tiles_multiplied": 3536}, 81.25),
    ({"attn_edge_tiles": 4352, "attn_edge_tiles_multiplied": 4352}, 100.0),
], ids=["no_stats", "no_counter", "no_site", "half", "trinity", "whole"])
def test_the_edge_tile_reader_reads_the_two_counts(pipeline, reads):
    run = types.SimpleNamespace(window={"pipeline": pipeline})
    assert _edge_reader().read(run) == reads


def test_the_edge_tile_readers_cells_are_the_streaming_ones():
    """Its rule on a cell's fields names the cells ``BENCHMARK.json`` lists
    for it: the ten whose rows are longer than the fused family takes
    (six at PR 54, the looped configuration's cell since PR 57, the Mistral
    cell since PR 59, the Olmo-Hybrid cell since PR 64, the SDAR cell since
    PR 67)."""
    reader = _edge_reader()
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry, = (m for m in bench["per_layer"] if m["name"] == EDGE_METRIC)
    assert (entry["layer"], entry["unit"], entry["moves"]) == (
        reader.LAYER, reader.UNIT, reader.MOVES
    )
    assert (entry["better"], entry["source"]) == ("lower", "program_counter")
    taken = []
    for cell in bench["workloads"]:
        path = os.path.join(
            REPO, "benchmark", "cells", cell["name"] + ".json"
        )
        with open(path) as f:
            if reader.CELLS(json.load(f)):
                taken.append(cell["name"])
    assert taken == entry["workloads"] and len(taken) == 10
    assert reader.FUSED_MAX_T == fa._FUSED_MAX_T
    assert not reader.CELLS({}) and reader.CELLS({"seq": 4096})
