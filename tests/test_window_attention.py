"""``flash_attention(..., window=W)`` at a small size on the CPU: against
the written-out masked softmax in every family the call can reach, the
band kernels in interpret mode forward and backward with the blocks they
walk counted, the step tables at the Trinity-Mini cell's shape, and what
refuses a window. (The model that uses it: ``test_afmoe.py``.)"""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.common import trace_counts
from trace_counted import STREAM, WINDOW, added

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
}


@pytest.mark.parametrize("one_pass", [True, False], ids=["one_pass", "split"])
@pytest.mark.parametrize("window", sorted(BANDS))
def test_band_kernels_are_the_masked_softmax_and_walk_the_band_alone(
    window, one_pass, monkeypatch
):
    if not one_pass:
        monkeypatch.setattr(fa, "_ONE_PASS_MAX_BYTES", 0)
    q, k, v, do = _qkv(64)
    before = trace_counts.snapshot()
    got = _value_and_cotangents(
        lambda q, k, v: fa.flash_attention(
            q, k, v, window=window, layout="bhtd", force="pallas",
            block_q=16, block_k=16, allow_fused=False,
        ), q, k, v, do,
    )
    kernels = 2 if one_pass else 3  # forward; backward in one pass or two
    walked = kernels * BANDS[window]
    assert added(before, WINDOW) == (walked, kernels * 10)
    assert added(before, STREAM) == (kernels, 0, walked, kernels * 16)
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
