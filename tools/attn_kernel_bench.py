"""Time the causal attention kernels alone on the chip.

``LAYERS`` chained calls (a layer's output is the next one's query) in
one jitted program, forward and forward + backward, host clock around
``block_until_ready``: milliseconds a call. Variants of one shape:

- ``fused``: what ``flash_attention`` picks (T <= 1024, H = H_kv: the
  fused family; causal with equal static offsets: its triangle walk);
- ``square``: the fused family's whole-square body (the walk switched
  off), which is what every fused call ran before the walk;
- ``streaming`` = ``stream-tri``: ``allow_fused=False``, the block-tiled
  kernels as ``flash_attention`` lowers them (causal with equal static
  offsets and square blocks: their triangle path);
- ``stream-rect[:<n>]``: the same with the triangle path switched off,
  the rectangular grid every streaming call ran before it (at blocks of
  512, or of ``n``);
- ``stream-split``: the triangle path with the one-pass backward
  switched off (its dq and dk / dv kernels);
- ``block:<n>``: ``stream-tri`` at blocks of ``n`` x ``n``;
- ``stream-floor``: the triangle grid with no compute in its steps
  (fetches, steps and write-backs only);
- ``tri:<bq>[:<heads fwd>:<heads bwd>]``: the fused walk at another row
  tile and other heads a program;
- ``window:<W>[:<n>]``: ``stream-tri`` through a window of ``W`` keys,
  the band its kernels walk (at blocks of ``n``);
  ``window-mask:<W>[:<n>]``: the same window as a mask over the whole
  triangle (every block at or under the diagonal walked, those past the
  window's far edge wholly masked): what the band saves;
- ``bd:<B>[:<n>[:<s>]]``: the shape's T is a DOUBLED row (a noised copy of
  T / 2 positions before the clean one) under the block-diffusion rule
  over blocks of ``B``, the blocks its ``flash_attn_bd_*`` kernels walk
  (at blocks of ``n``; the noised x noised blocks on the diagonal in
  ``s`` row strips and not the module's ``_BD_EQ_STRIPS``);
  ``bd-mask:<B>``: the same rule as a ``mask_fn`` over the rectangular
  grid (every block of the square walked): what the walk saves.

A streaming variant that ends in ``@<s>`` walks the edge blocks of its
backward kernels (the diagonal's, and those a window's far edge crosses)
in ``s`` row strips and not in the module's ``_EDGE_STRIPS``; ``@1`` is
the whole block, masked, which every edge block was before the strips
and the forward kernel's still is. A line says the strips its blocks were
walked in (``edge_strips``), and its ``counts`` the score tiles the
kernels multiplied of those the edge blocks hold.

A shape is ``BxHxTxD`` or, with its own key-value head count,
``BxHxHkvxTxD``.

    python tools/attn_kernel_bench.py 16x12x1024x64 fused square streaming tri:128
    python tools/attn_kernel_bench.py 1x32x2x8192x128 stream-tri stream-rect block:256
    python tools/attn_kernel_bench.py 1x32x4x16384x128 stream-tri window:2048 window:2048:512 window-mask:2048
    python tools/attn_kernel_bench.py 1x32x4x16384x128 stream-tri stream-tri@2 stream-tri@1 window:2048 window:2048@2 window:2048@1
    python tools/attn_kernel_bench.py 1x40x20x16384x128 stream-tri window:512 window:512@2 window:512@1
    python tools/attn_kernel_bench.py 1x32x4x16384x128 stream-tri bd:4 bd:4:512 bd:4:1024:4 bd-mask:4
"""
import importlib
import json
import sys
import time

import jax
import jax.numpy as jnp

from dlrover_tpu.common import trace_counts

# the package re-exports the function under the module's name
fa = importlib.import_module("dlrover_tpu.ops.flash_attention")

# what the module picks, put back before each variant
ROW_TILE = fa._TRI_ROW_TILE
HEAD_CHUNK = fa._walk_head_chunk
STREAM = {
    name: getattr(fa, name)
    for name in (
        "_stream_plan", "_ONE_PASS_MAX_BYTES", "_tri_fwd_kernel",
        "_tri_bwd_kernel", "_band_blocks", "_EDGE_STRIPS", "_BD_EQ_STRIPS",
    )
}
LAYERS = 12
REPEATS = 10
ROUNDS = 5


def _no_compute(n_in: int, n_out: int):
    """A triangle kernel's refs (two tables, ``n_in`` inputs, ``n_out``
    outputs, scratch) with every step writing zeros and no more."""

    def kernel(*refs, **_):
        for out in refs[2 + n_in:2 + n_in + n_out]:
            out[...] = jnp.zeros_like(out)

    return kernel


def _steer(variant):
    """Module constants of the variant; returns ``allow_fused`` and the
    entry's block (and window) arguments."""
    fa._TRI_ROW_TILE = ROW_TILE
    fa._walk_head_chunk = HEAD_CHUNK
    for name, was in STREAM.items():
        setattr(fa, name, was)
    variant, _, strips = variant.partition("@")
    if strips:
        fa._EDGE_STRIPS = int(strips)
    kind, *rest = variant.split(":")
    if kind in ("bd", "bd-mask"):
        if len(rest) > 2:
            fa._BD_EQ_STRIPS = int(rest[2])
        block = int(rest[1]) if len(rest) > 1 else None
        return False, {
            "diffusion": int(rest[0]), "mask": kind == "bd-mask",
            "block": block,
        }
    if kind in ("window", "window-mask"):
        if kind == "window-mask":  # a band as wide as any triangle
            fa._band_blocks = lambda window, block: 1 << 20
        n = int(rest[1]) if len(rest) > 1 else None
        blocks = {"block_q": n, "block_k": n} if n else {}
        return False, dict(blocks, window=int(rest[0]))
    if kind in ("streaming", "block") or kind.startswith("stream-"):
        if kind == "stream-rect":
            fa._stream_plan = lambda *a, **kw: None
        elif kind == "stream-split":
            fa._ONE_PASS_MAX_BYTES = 0
        elif kind == "stream-floor":
            fa._tri_fwd_kernel = _no_compute(3, 2)
            fa._tri_bwd_kernel = _no_compute(6, 3)
        elif kind not in ("streaming", "stream-tri", "block"):
            raise SystemExit(f"unknown variant {variant!r}")
        n = int(rest[0]) if rest else None
        if kind == "stream-rect":
            n = n or fa._BLOCK  # what the rectangular grid is called at
        return False, {"block_q": n, "block_k": n} if n else {}
    if kind == "square":
        fa._TRI_ROW_TILE = 1 << 30  # divides no T: the square body
    elif kind == "tri":
        fa._TRI_ROW_TILE = int(rest[0])
        if len(rest) == 3:  # by the wide blocks a head: 4 forward, 7 back
            heads = {4: int(rest[1]), 7: int(rest[2])}
            fa._walk_head_chunk = (
                lambda H, T, D, itemsize, wide, narrow: heads[wide]
            )
    elif kind != "fused":
        raise SystemExit(f"unknown variant {variant!r}")
    return True, {}


def _time(fn, *args):
    """The least of ``ROUNDS`` rounds of ``REPEATS`` calls, a call."""
    jax.block_until_ready(fn(*args))  # compiles
    rounds = []
    for _ in range(ROUNDS):
        t0 = time.perf_counter()
        for _ in range(REPEATS):
            out = fn(*args)
        jax.block_until_ready(out)
        rounds.append((time.perf_counter() - t0) / REPEATS / LAYERS * 1e3)
    return min(rounds)


def bench(shape, variant):
    allow_fused, blocks = _steer(variant)
    B, H, Hkv, T, D = shape
    diffusion = blocks.pop("diffusion", None)
    if diffusion:
        by_mask, stated = blocks.pop("mask"), blocks.pop("block")
        if stated:
            blocks = {"block_q": stated, "block_k": stated}

    def attend(q, k, v):
        if by_mask:
            return fa.flash_attention(
                q, k, v, causal=False, layout="bhtd", force="pallas",
                mask_fn=fa.block_diffusion_mask(T // 2, diffusion),
            )
        return fa.block_diffusion_attention(
            q, k, v, block_len=diffusion, layout="bhtd", block=stated,
        )

    def chain(q, k, v):
        for _ in range(LAYERS):
            if diffusion:
                q = attend(q, k, v)
                continue
            q = fa.flash_attention(
                q, k, v, causal=True, layout="bhtd",
                allow_fused=allow_fused, **blocks,
            )
        return q

    def loss(q, k, v):
        return chain(q, k, v).astype(jnp.float32).sum()

    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = (
        jax.random.normal(key, (B, heads, T, D), jnp.bfloat16)
        for key, heads in zip(keys, (H, Hkv, Hkv))
    )
    before = trace_counts.snapshot()
    t0 = time.perf_counter()
    fwd = _time(jax.jit(chain), q, k, v)
    both = _time(jax.jit(jax.grad(loss, argnums=(0, 1, 2))), q, k, v)
    block, _ = fa._validate_blocks(
        q, k, blocks.get("block_q"), blocks.get("block_k"), "bhtd",
        triangle=True,
    )
    return {
        "shape": list(shape), "variant": variant,
        "edge_strips": fa._edge_strip_count(block, False),
        "fwd_ms": round(fwd, 4), "fwd_bwd_ms": round(both, 4),
        "counts": dict(+trace_counts.since(before)),
        "wall_s": round(time.perf_counter() - t0, 1),
    }


def main(argv):
    assert jax.default_backend() == "tpu", "this timing needs the chip"
    shape = tuple(int(n) for n in argv[0].split("x"))
    if len(shape) == 4:  # no key-value head count of its own
        shape = shape[:2] + shape[1:]
    for variant in argv[1:] or ["fused", "square", "streaming"]:
        try:
            print(json.dumps(bench(shape, variant)), flush=True)
        except Exception as e:  # a variant the compiler refuses
            print(json.dumps({
                "shape": list(shape), "variant": variant,
                "error": str(e)[:400],
            }), flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
