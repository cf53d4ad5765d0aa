"""Ops layer numerics: Pallas flash attention (interpret mode), kernel
ring attention, AGD/WSAM, 8-bit AdamW."""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from dlrover_tpu.common import trace_counts
from dlrover_tpu.ops import (
    adamw_8bit,
    agd,
    dequantize_8bit,
    flash_attention,
    make_wsam_grad_fn,
    quantize_8bit,
)
from dlrover_tpu.ops.flash_attention import (
    flash_attention_bwd,
    flash_attention_fwd,
    flash_attention_reference,
)
# `dlrover_tpu.ops.flash_attention` the attribute is the function
fa = importlib.import_module("dlrover_tpu.ops.flash_attention")
from dlrover_tpu.ops.optimizers import apply_wsam_sharpness
from dlrover_tpu.ops.quantized_optim import (
    _adam8_update_jnp,
    _to_blocks,
)
from trace_counted import FUSED, STREAM, added


def _qkv(B=2, T=128, H=4, Hkv=4, D=32, seed=0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(B, T, H, D)), dtype)
    k = jnp.asarray(rng.normal(size=(B, T, Hkv, D)), dtype)
    v = jnp.asarray(rng.normal(size=(B, T, Hkv, D)), dtype)
    return q, k, v


class TestFlashAttention:
    # fused=True exercises the short-seq fused kernels (these shapes are
    # eligible); fused=False pins the streaming block-tiled kernels so
    # they keep coverage at non-GQA shapes too
    @pytest.mark.parametrize("fused", [True, False])
    @pytest.mark.parametrize("causal", [True, False])
    def test_forward_matches_reference(self, causal, fused):
        q, k, v = _qkv()
        ref = flash_attention_reference(q, k, v, causal=causal)
        out = flash_attention(
            q, k, v, causal=causal, force="pallas", block_q=64,
            block_k=64, allow_fused=fused,
        )
        np.testing.assert_allclose(out, ref, atol=2e-5)

    def test_gqa(self):
        q, k, v = _qkv(H=8, Hkv=2)
        ref = flash_attention_reference(q, k, v)
        out = flash_attention(q, k, v, force="pallas", block_q=64)
        np.testing.assert_allclose(out, ref, atol=2e-5)

    @pytest.mark.parametrize("fused", [True, False])
    def test_custom_mask(self, fused):
        # sliding-window mask (positions within 32 of the query)
        win = lambda qp, kp: (qp >= kp) & (qp - kp < 32)  # noqa: E731
        q, k, v = _qkv()
        ref = flash_attention_reference(q, k, v, causal=True, mask_fn=win)
        out = flash_attention(
            q, k, v, causal=True, mask_fn=win, force="pallas",
            block_q=64, allow_fused=fused,
        )
        np.testing.assert_allclose(out, ref, atol=2e-5)

    def test_grads_match_reference(self):
        q, k, v = _qkv(T=128, H=4, Hkv=2)

        def lp(q, k, v):
            return (
                flash_attention(q, k, v, force="pallas", block_q=64) ** 2
            ).sum()

        def lr(q, k, v):
            return (flash_attention_reference(q, k, v) ** 2).sum()

        gp = jax.grad(lp, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(lr, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gp, gr):
            np.testing.assert_allclose(a, b, atol=5e-4)

    @pytest.mark.parametrize("fused", [True, False])
    def test_offsets_shift_causal_mask(self, fused):
        # kernel with k_offset sees keys as "earlier" -> full visibility
        q, k, v = _qkv(T=64)
        o1, lse1 = flash_attention_fwd(
            q, k, v, causal=True, q_offset=64, k_offset=0, block_q=64,
            allow_fused=fused,
        )
        ref = flash_attention_reference(
            q, k, v, causal=True, q_offset=64, k_offset=0
        )
        np.testing.assert_allclose(o1, ref, atol=2e-5)
        # and bwd runs with the same offsets
        do = jnp.ones_like(o1)
        dq, dk, dv = flash_attention_bwd(
            q, k, v, o1, lse1, do, causal=True, q_offset=64, k_offset=0,
            allow_fused=fused,
        )
        assert dq.shape == q.shape and dk.shape == k.shape

    def test_fully_masked_rows_zero_grads(self):
        # rows whose every key is masked must get zero output AND zero
        # gradient through the pallas backward (regression: p=exp(s-lse)
        # was 1, not 0, when lse==NEG_INF)
        blind = lambda qp, kp: (qp >= kp) & (qp >= 64)  # noqa: E731
        q, k, v = _qkv(T=128)

        def lp(q, k, v):
            return (
                flash_attention(
                    q, k, v, mask_fn=blind, force="pallas", block_q=64
                )
                ** 2
            ).sum()

        def lr(q, k, v):
            return (
                flash_attention_reference(q, k, v, mask_fn=blind) ** 2
            ).sum()

        out = flash_attention(
            q, k, v, mask_fn=blind, force="pallas", block_q=64
        )
        assert float(jnp.abs(out[:, :64]).max()) == 0.0
        gp = jax.grad(lp, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(lr, argnums=(0, 1, 2))(q, k, v)
        assert float(jnp.abs(gp[0][:, :64]).max()) == 0.0
        for a, b in zip(gp, gr):
            np.testing.assert_allclose(a, b, atol=5e-4)

    def test_odd_length_falls_back(self):
        q, k, v = _qkv(T=100)  # 100 doesn't tile into 64/128 blocks
        out = flash_attention(q, k, v)  # auto mode: should not raise
        ref = flash_attention_reference(q, k, v)
        np.testing.assert_allclose(out, ref, atol=2e-5)

    # -- the fused family's triangle walk (causal, equal static offsets)
    @pytest.mark.parametrize("row_tile", [8, 16, 32])  # 8, 4, 2 row tiles
    @pytest.mark.parametrize(
        "dtype,atol_o,atol_g",
        [(jnp.float32, 2e-5, 2e-4), (jnp.bfloat16, 2e-2, 6e-2)],
        ids=["f32", "bf16"],
    )
    def test_triangle_matches_reference(
        self, dtype, atol_o, atol_g, row_tile, monkeypatch
    ):
        # the internal calls, so a small T can have several row tiles;
        # 2 heads a program under H = 6 crosses the head chunking
        monkeypatch.setattr(fa, "_walk_head_chunk", lambda *a, **kw: 2)
        B, H, T, D = 2, 6, 64, 32
        rng = np.random.default_rng(row_tile)
        q, k, v, do = (
            jnp.asarray(rng.normal(size=(B, H, T, D)), dtype)
            for _ in range(4)
        )
        kw = dict(
            causal=True, mask_fn=None, sm_scale=D**-0.5, interpret=True,
            diagonal=True, row_tile=row_tile,
        )
        off = jnp.zeros(2, jnp.int32)
        before = trace_counts.snapshot()
        o, lse4 = fa._fused_fwd_call(q, k, v, off, **kw)
        f32 = lambda x: x.astype(jnp.float32)  # noqa: E731
        delta4 = (f32(do) * f32(o)).sum(-1, keepdims=True)
        grads = fa._fused_bwd_call(q, k, v, do, lse4, delta4, off, **kw)
        n = T // row_tile
        assert added(before, FUSED) == (2, 0, n * (n + 1), 2 * n * n)

        def ref(q, k, v):  # the reference speaks [B, T, H, D]
            o, lse = flash_attention_reference(
                *(x.transpose(0, 2, 1, 3) for x in (q, k, v)),
                return_residuals=True,
            )
            return o.transpose(0, 2, 1, 3), lse

        o_ref, lse_ref = ref(q, k, v)
        np.testing.assert_allclose(f32(o), f32(o_ref), atol=atol_o)
        np.testing.assert_allclose(lse4[..., 0], lse_ref, atol=2e-5)
        want = jax.grad(
            lambda q, k, v: (f32(ref(q, k, v)[0]) * f32(do)).sum(),
            argnums=(0, 1, 2),
        )(q, k, v)
        for got, g_ref, name in zip(grads, want, "qkv"):
            np.testing.assert_allclose(
                f32(got), f32(g_ref), atol=atol_g, err_msg=f"d{name}"
            )

    # which body a fused call site takes is decided by what is known
    # when the program is traced; either way it matches the reference
    @pytest.mark.parametrize(
        "case,kw,counts",
        [
            ("in_sequence", dict(), (2, 0, 20, 32)),
            ("equal_offsets", dict(q_offset=96, k_offset=96), (2, 0, 20, 32)),
            ("overlap", dict(q_offset=32, k_offset=0), (0, 2, 0, 0)),
            ("all_visible", dict(q_offset=128, k_offset=0), (0, 2, 0, 0)),
            ("all_future", dict(q_offset=0, k_offset=128), (0, 2, 0, 0)),
            ("mask_fn", dict(
                mask_fn=lambda qp, kp: (qp >= kp) & (qp - kp < 48)
            ), (0, 2, 0, 0)),
            ("not_causal", dict(causal=False), (0, 2, 0, 0)),
            ("streaming", dict(allow_fused=False), (0, 0, 0, 0)),
        ],
    )
    def test_body_taken_and_grads(self, case, kw, counts, monkeypatch):
        monkeypatch.setattr(fa, "_TRI_ROW_TILE", 32)  # T = 128: 4 tiles
        q, k, v = _qkv(T=128)

        def lp(q, k, v):
            return (
                flash_attention(q, k, v, force="pallas", **kw) ** 2
            ).sum()

        kw_ref = {k_: v_ for k_, v_ in kw.items() if k_ != "allow_fused"}

        def lr(q, k, v):
            return (flash_attention_reference(q, k, v, **kw_ref) ** 2).sum()

        before = trace_counts.snapshot()
        gp = jax.grad(lp, argnums=(0, 1, 2))(q, k, v)
        assert added(before, FUSED) == counts
        gr = jax.grad(lr, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gp, gr):
            np.testing.assert_allclose(a, b, atol=5e-4)
        np.testing.assert_allclose(
            flash_attention(q, k, v, force="pallas", **kw),
            flash_attention_reference(q, k, v, **kw_ref), atol=2e-5,
        )

    @pytest.mark.parametrize("offsets", [(0, 0), (128, 128), (128, 0)])
    def test_traced_offsets_take_the_square(self, offsets, monkeypatch):
        # a ring hop: the offsets are values of the program, so even
        # equal ones are not known equal when it is traced
        monkeypatch.setattr(fa, "_TRI_ROW_TILE", 32)
        q, k, v = _qkv(T=128)

        @jax.jit
        def hop(q, k, v, q_off, k_off):
            o, lse = flash_attention_fwd(
                q, k, v, causal=True, q_offset=q_off, k_offset=k_off,
                interpret=True,
            )
            grads = flash_attention_bwd(
                q, k, v, o, lse, jnp.ones_like(o), causal=True,
                q_offset=q_off, k_offset=k_off, interpret=True,
            )
            return o, lse, grads

        before = trace_counts.snapshot()
        o, lse, grads = hop(q, k, v, *(jnp.int32(n) for n in offsets))
        assert added(before, FUSED) == (0, 2, 0, 0)
        q_off, k_off = offsets
        o_ref, lse_ref = flash_attention_reference(
            q, k, v, q_offset=q_off, k_offset=k_off, return_residuals=True
        )
        np.testing.assert_allclose(o, o_ref, atol=2e-5)
        np.testing.assert_allclose(lse, lse_ref, atol=2e-5)
        want = jax.grad(
            lambda q, k, v: flash_attention_reference(
                q, k, v, q_offset=q_off, k_offset=k_off
            ).sum(),
            argnums=(0, 1, 2),
        )(q, k, v)
        for a, b in zip(grads, want):
            np.testing.assert_allclose(a, b, atol=5e-4)
        # the same offsets as Python ints are known: equal ones walk
        before = trace_counts.snapshot()
        o_static, _ = flash_attention_fwd(
            q, k, v, causal=True, q_offset=q_off, k_offset=k_off,
            interpret=True,
        )
        walked = (1, 0, 10, 16) if q_off == k_off else (0, 1, 0, 0)
        assert added(before, FUSED) == walked
        np.testing.assert_allclose(o_static, o_ref, atol=2e-5)

    def test_t520_takes_the_square(self):
        # fused-eligible, and no row tile divides it
        q, k, v = _qkv(B=1, T=520, H=2, Hkv=2)
        assert fa._row_tile(520) is None and fa._row_tile(1024)
        before = trace_counts.snapshot()
        # the call the public entry makes on the chip for such a T
        # (no block size tiles it, and the fused family needs none)
        gp = jax.grad(
            lambda q, k, v: (
                fa._flash_pallas(
                    q, k, v, (0, 0), True, None, 32**-0.5, 8, 8, "bthd",
                    True,
                ) ** 2
            ).sum(),
            argnums=(0, 1, 2),
        )(q, k, v)
        assert added(before, FUSED) == (0, 2, 0, 0)
        gr = jax.grad(
            lambda q, k, v: (flash_attention_reference(q, k, v) ** 2).sum(),
            argnums=(0, 1, 2),
        )(q, k, v)
        for a, b in zip(gp, gr):
            np.testing.assert_allclose(a, b, atol=5e-4)


    # -- the streaming family's triangle path (causal, equal static
    # offsets, square blocks): a grid step, a fetch and a mask only
    # where a query can see
    @staticmethod
    def _stream_case(dtype, n, H, Hkv, offset, seed=0):
        """Forward and backward through the raw entries in blocks of 8
        (so T = 8 n has n blocks a side), against the reference."""
        B, D, block = 1, 32, 8
        q, k, v = _qkv(B=B, T=n * block, H=H, Hkv=Hkv, D=D, seed=seed,
                       dtype=dtype)
        do = jnp.asarray(
            np.random.default_rng(seed + 1).normal(size=q.shape), dtype
        )
        kw = dict(
            causal=True, q_offset=offset, k_offset=offset, block_q=block,
            block_k=block, interpret=True, allow_fused=False,
        )
        o, lse = flash_attention_fwd(q, k, v, **kw)
        grads = flash_attention_bwd(q, k, v, o, lse, do, **kw)
        f32 = lambda x: x.astype(jnp.float32)  # noqa: E731
        o_ref, lse_ref = flash_attention_reference(
            q, k, v, return_residuals=True
        )
        want = jax.grad(
            lambda q, k, v: (
                f32(flash_attention_reference(q, k, v)) * f32(do)
            ).sum(),
            argnums=(0, 1, 2),
        )(q, k, v)
        return (o, lse, grads), (o_ref, lse_ref, want)

    @staticmethod
    def _assert_close(got, want, atol_o, atol_g):
        f32 = lambda x: x.astype(jnp.float32)  # noqa: E731
        (o, lse, grads), (o_ref, lse_ref, g_ref) = got, want
        np.testing.assert_allclose(f32(o), f32(o_ref), atol=atol_o)
        np.testing.assert_allclose(lse, lse_ref, atol=2e-5)
        for a, b, name in zip(grads, g_ref, "qkv"):
            np.testing.assert_allclose(
                f32(a), f32(b), atol=atol_g, err_msg=f"d{name}"
            )

    @pytest.mark.parametrize("offset", [0, 40], ids=["at0", "at40"])
    @pytest.mark.parametrize(
        "H,Hkv", [(4, 4), (8, 2), (16, 1)], ids=["mha", "gqa4", "gqa16"]
    )
    @pytest.mark.parametrize("n", [2, 4, 8])
    @pytest.mark.parametrize(
        "dtype,atol_o,atol_g",
        [(jnp.float32, 2e-5, 2e-4), (jnp.bfloat16, 2e-2, 2.5e-1)],
        ids=["f32", "bf16"],
    )
    def test_stream_triangle_matches_reference(
        self, dtype, atol_o, atol_g, n, H, Hkv, offset
    ):
        before = trace_counts.snapshot()
        got, want = self._stream_case(dtype, n, H, Hkv, offset, seed=n)
        # a forward and a one-pass backward, n(n+1)/2 of n^2 blocks each
        assert added(before, STREAM) == (
            2, 0, n * (n + 1), 2 * n * n
        )
        self._assert_close(got, want, atol_o, atol_g)

    @pytest.mark.parametrize("n", [2, 4, 8])
    @pytest.mark.parametrize(
        "dtype,atol_o,atol_g",
        [(jnp.float32, 2e-5, 2e-4), (jnp.bfloat16, 2e-2, 2.5e-1)],
        ids=["f32", "bf16"],
    )
    def test_stream_triangle_split_backward(
        self, dtype, atol_o, atol_g, n, monkeypatch
    ):
        # a head whose float32 dq no longer fits VMEM beside its dq
        # block: the dq kernel and the dk / dv kernel over the triangle
        assert fa._one_pass_fits(8192, 128, 2)
        assert not fa._one_pass_fits(65536, 128, 2)
        monkeypatch.setattr(fa, "_ONE_PASS_MAX_BYTES", 0)
        before = trace_counts.snapshot()
        got, want = self._stream_case(dtype, n, 8, 2, 0, seed=n)
        assert added(before, STREAM) == (
            3, 0, 3 * n * (n + 1) // 2, 3 * n * n
        )
        self._assert_close(got, want, atol_o, atol_g)

    # which grid a streaming site takes is decided by what is known
    # when the program is traced; either way it matches the reference.
    # T = 128 in blocks of 32: 10 of 16 blocks a kernel, a forward and
    # a one-pass backward; the rectangle's backward is two kernels
    @pytest.mark.parametrize(
        "case,kw,counts",
        [
            ("in_sequence", dict(), (2, 0, 20, 32)),
            ("equal_offsets", dict(q_offset=96, k_offset=96), (2, 0, 20, 32)),
            ("overlap", dict(q_offset=32, k_offset=0), (0, 3, 0, 0)),
            ("all_visible", dict(q_offset=128, k_offset=0), (0, 3, 0, 0)),
            ("all_future", dict(q_offset=0, k_offset=128), (0, 3, 0, 0)),
            ("mask_fn", dict(
                mask_fn=lambda qp, kp: (qp >= kp) & (qp - kp < 48)
            ), (0, 3, 0, 0)),
            ("not_causal", dict(causal=False), (0, 3, 0, 0)),
            ("oblong_blocks", dict(block_k=64), (0, 3, 0, 0)),
            ("too_many_blocks", dict(), (0, 3, 0, 0)),
        ],
    )
    def test_stream_grid_taken_and_grads(self, case, kw, counts, monkeypatch):
        if case == "too_many_blocks":  # the tables would crowd SMEM
            monkeypatch.setattr(fa, "_TRI_MAX_BLOCKS", 2)
        q, k, v = _qkv(T=128, H=4, Hkv=2)
        kw = dict(dict(block_q=32, block_k=32), **kw)

        def lp(q, k, v):
            return (flash_attention(q, k, v, force="pallas", **kw) ** 2).sum()

        kw_ref = {
            k_: v_ for k_, v_ in kw.items() if not k_.startswith("block")
        }

        def lr(q, k, v):
            return (flash_attention_reference(q, k, v, **kw_ref) ** 2).sum()

        before = trace_counts.snapshot()
        gp = jax.grad(lp, argnums=(0, 1, 2))(q, k, v)
        assert added(before, STREAM) == counts
        # GQA: never the fused family
        assert added(before, FUSED) == (0, 0, 0, 0)
        gr = jax.grad(lr, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gp, gr):
            np.testing.assert_allclose(a, b, atol=5e-4)
        np.testing.assert_allclose(
            flash_attention(q, k, v, force="pallas", **kw),
            flash_attention_reference(q, k, v, **kw_ref), atol=2e-5,
        )

    @pytest.mark.parametrize("offsets", [(0, 0), (128, 128), (128, 0)])
    def test_stream_traced_offsets_take_the_rectangle(self, offsets):
        # a ring hop: the offsets are values of the program, so even
        # equal ones are not known equal when it is traced
        q, k, v = _qkv(T=128, H=4, Hkv=2)
        kw = dict(causal=True, block_q=32, block_k=32, interpret=True)

        @jax.jit
        def hop(q, k, v, q_off, k_off):
            o, lse = flash_attention_fwd(
                q, k, v, q_offset=q_off, k_offset=k_off, **kw
            )
            grads = flash_attention_bwd(
                q, k, v, o, lse, jnp.ones_like(o), q_offset=q_off,
                k_offset=k_off, **kw,
            )
            return o, lse, grads

        before = trace_counts.snapshot()
        o, lse, grads = hop(q, k, v, *(jnp.int32(n) for n in offsets))
        assert added(before, STREAM) == (0, 3, 0, 0)
        q_off, k_off = offsets
        o_ref, lse_ref = flash_attention_reference(
            q, k, v, q_offset=q_off, k_offset=k_off, return_residuals=True
        )
        np.testing.assert_allclose(o, o_ref, atol=2e-5)
        np.testing.assert_allclose(lse, lse_ref, atol=2e-5)
        want = jax.grad(
            lambda q, k, v: flash_attention_reference(
                q, k, v, q_offset=q_off, k_offset=k_off
            ).sum(),
            argnums=(0, 1, 2),
        )(q, k, v)
        for a, b in zip(grads, want):
            np.testing.assert_allclose(a, b, atol=5e-4)
        # the same offsets as Python ints are known: equal ones walk
        before = trace_counts.snapshot()
        o_static, _ = flash_attention_fwd(
            q, k, v, q_offset=q_off, k_offset=k_off, **kw
        )
        walked = (1, 0, 10, 16) if q_off == k_off else (0, 1, 0, 0)
        assert added(before, STREAM) == walked
        np.testing.assert_allclose(o_static, o_ref, atol=2e-5)

    def test_fused_eligible_call_is_no_streaming_site(self):
        q, k, v = _qkv(T=128)  # H = H_kv, T <= 1024: the fused family
        before = trace_counts.snapshot()
        jax.grad(
            lambda q, k, v: flash_attention(q, k, v, force="pallas").sum(),
            argnums=(0, 1, 2),
        )(q, k, v)
        assert added(before, STREAM) == (0, 0, 0, 0)
        assert added(before, FUSED) == (0, 2, 0, 0)

    # the blocks of a call that states none: 1024 where it will take the
    # triangle path and 1024 divides its one sequence, else the 512 the
    # rectangular grid was measured at; a stated block is the caller's
    @pytest.mark.parametrize(
        "T,Tk,D,stated,triangle,want",
        [
            (4096, 4096, 128, (None, None), True, (1024, 1024)),
            (8192, 8192, 64, (None, None), True, (1024, 1024)),
            (1024, 1024, 128, (None, None), True, (1024, 1024)),
            (4096, 4096, 128, (None, None), False, (512, 512)),
            (1536, 1536, 128, (None, None), True, (512, 512)),
            (4096, 4096, 256, (None, None), True, (512, 512)),
            (1024, 4096, 128, (None, None), True, (512, 512)),
            (4096, 4096, 128, (256, None), True, (256, 512)),
            (4096, 4096, 128, (512, 512), True, (512, 512)),
            (256, 256, 128, (None, None), True, (256, 256)),
        ],
    )
    def test_blocks_of_a_call_that_states_none(
        self, T, Tk, D, stated, triangle, want
    ):
        q = jax.ShapeDtypeStruct((1, 2, T, D), jnp.bfloat16)
        k = jax.ShapeDtypeStruct((1, 2, Tk, D), jnp.bfloat16)
        assert fa._validate_blocks(
            q, k, *stated, "bhtd", triangle=triangle
        ) == want

    def test_the_triangle_is_seen_only_when_traced_facts_show_it(self):
        assert fa._sees_triangle(True, None, fa._on_diagonal(7, 7))
        assert not fa._sees_triangle(True, None, fa._on_diagonal(7, 0))
        assert not fa._sees_triangle(
            True, None, fa._on_diagonal(jnp.int32(7), jnp.int32(7))
        )
        assert not fa._sees_triangle(False, None, True)
        assert not fa._sees_triangle(True, lambda q, k: q >= k, True)


class TestFusedShortSeq:
    """The fused single-program kernels (T <= 1024, H == Hkv) vs the
    streaming block-tiled kernels and the jnp reference."""

    def test_dispatch_criteria(self):
        from dlrover_tpu.ops.flash_attention import _fused_eligible

        assert _fused_eligible((2, 128, 4, 32), (2, 128, 4, 32), "bthd")
        assert _fused_eligible((2, 4, 128, 32), (2, 4, 128, 32), "bhtd")
        # GQA -> streaming
        assert not _fused_eligible((2, 128, 8, 32), (2, 128, 2, 32), "bthd")
        # cross-attention shapes -> streaming
        assert not _fused_eligible((2, 64, 4, 32), (2, 128, 4, 32), "bthd")
        # long seq -> streaming
        assert not _fused_eligible(
            (2, 2048, 4, 32), (2, 2048, 4, 32), "bthd"
        )

    def test_fwd_matches_streaming(self):
        q, k, v = _qkv()
        of, lf = flash_attention_fwd(q, k, v, causal=True, block_q=64)
        os_, ls = flash_attention_fwd(
            q, k, v, causal=True, block_q=64, allow_fused=False
        )
        np.testing.assert_allclose(of, os_, atol=2e-5)
        np.testing.assert_allclose(lf, ls, atol=2e-5)

    @pytest.mark.slow  # ~27s: heaviest tier-1 test; budget-gated out
    def test_chunked_fwd_matches_full(self):
        """flash_attention_fwd_chunked (fused tiles + online merges)
        must equal the one-call forward — same o AND lse, causal and
        non-causal, so Ulysses' full-seq path can chunk onto the fused
        kernel without a numerics change."""
        from dlrover_tpu.ops.flash_attention import (
            flash_attention_fwd_chunked,
        )

        q, k, v = _qkv(T=256)
        for causal in (True, False):
            o_full, lse_full = flash_attention_fwd(
                q, k, v, causal=causal, block_q=64
            )
            o_ch, lse_ch = flash_attention_fwd_chunked(
                q, k, v, causal=causal, chunk=64
            )
            np.testing.assert_allclose(
                np.asarray(o_ch, np.float32),
                np.asarray(o_full, np.float32),
                atol=3e-5,
            )
            np.testing.assert_allclose(lse_ch, lse_full, atol=3e-5)

    def test_chunked_fwd_respects_offsets(self):
        """Global q/k offsets flow through to every tile (a ring hop
        holding a chunked long block must mask correctly)."""
        from dlrover_tpu.ops.flash_attention import (
            flash_attention_fwd_chunked,
        )

        q, k, v = _qkv(T=128)
        o_full, lse_full = flash_attention_fwd(
            q, k, v, causal=True, q_offset=128, k_offset=0, block_q=64
        )
        o_ch, lse_ch = flash_attention_fwd_chunked(
            q, k, v, causal=True, q_offset=128, k_offset=0, chunk=64
        )
        np.testing.assert_allclose(
            np.asarray(o_ch, np.float32),
            np.asarray(o_full, np.float32),
            atol=3e-5,
        )
        np.testing.assert_allclose(lse_ch, lse_full, atol=3e-5)

    def test_bwd_matches_streaming(self):
        q, k, v = _qkv()
        o, lse = flash_attention_fwd(q, k, v, causal=True, block_q=64)
        rng = np.random.default_rng(7)
        do = jnp.asarray(rng.normal(size=o.shape), o.dtype)
        gf = flash_attention_bwd(q, k, v, o, lse, do, causal=True)
        gs = flash_attention_bwd(
            q, k, v, o, lse, do, causal=True, allow_fused=False
        )
        for a, b in zip(gf, gs):
            np.testing.assert_allclose(a, b, atol=5e-4)

    def test_grads_match_reference(self):
        # H == Hkv: the custom-vjp path dispatches to the fused kernels
        q, k, v = _qkv(T=128, H=4, Hkv=4)

        def lp(q, k, v):
            return (flash_attention(q, k, v, force="pallas") ** 2).sum()

        def lr(q, k, v):
            return (flash_attention_reference(q, k, v) ** 2).sum()

        gp = jax.grad(lp, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(lr, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gp, gr):
            np.testing.assert_allclose(a, b, atol=5e-4)

    def test_bhtd_layout_matches_bthd(self):
        q, k, v = _qkv()
        qt, kt, vt = (x.transpose(0, 2, 1, 3) for x in (q, k, v))

        def lt(qt, kt, vt):
            o = flash_attention(
                qt, kt, vt, force="pallas", layout="bhtd"
            )
            return (o**2).sum()

        def lb(q, k, v):
            return (flash_attention(q, k, v, force="pallas") ** 2).sum()

        o_t = flash_attention(qt, kt, vt, force="pallas", layout="bhtd")
        o_b = flash_attention(q, k, v, force="pallas")
        np.testing.assert_allclose(
            o_t.transpose(0, 2, 1, 3), o_b, atol=2e-5
        )
        gt = jax.grad(lt, argnums=(0, 1, 2))(qt, kt, vt)
        gb = jax.grad(lb, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gt, gb):
            np.testing.assert_allclose(
                a.transpose(0, 2, 1, 3), b, atol=5e-4
            )

    def test_custom_mask_and_masked_rows(self):
        # sliding window AND fully-blind early rows through the fused
        # backward (regression guard mirroring the streaming-path test)
        blind = lambda qp, kp: (qp >= kp) & (qp >= 64)  # noqa: E731
        q, k, v = _qkv(T=128)

        def lp(q, k, v):
            return (
                flash_attention(q, k, v, mask_fn=blind, force="pallas")
                ** 2
            ).sum()

        def lr(q, k, v):
            return (
                flash_attention_reference(q, k, v, mask_fn=blind) ** 2
            ).sum()

        out = flash_attention(q, k, v, mask_fn=blind, force="pallas")
        assert float(jnp.abs(out[:, :64]).max()) == 0.0
        gp = jax.grad(lp, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(lr, argnums=(0, 1, 2))(q, k, v)
        assert float(jnp.abs(gp[0][:, :64]).max()) == 0.0
        for a, b in zip(gp, gr):
            np.testing.assert_allclose(a, b, atol=5e-4)

    def test_offsets(self):
        q, k, v = _qkv(T=64)
        o, lse = flash_attention_fwd(
            q, k, v, causal=True, q_offset=64, k_offset=0
        )
        ref = flash_attention_reference(
            q, k, v, causal=True, q_offset=64, k_offset=0
        )
        np.testing.assert_allclose(o, ref, atol=2e-5)

    def test_causal_skip_fully_future_kv(self):
        # a ring hop whose KV block is entirely in the future: output 0,
        # lse NEG_INF, zero grads — via the fused whole-program skip
        from dlrover_tpu.ops.flash_attention import NEG_INF

        q, k, v = _qkv(T=64)
        o, lse = flash_attention_fwd(
            q, k, v, causal=True, q_offset=0, k_offset=64
        )
        assert float(jnp.abs(o).max()) == 0.0
        assert float(lse.max()) == float(np.float32(NEG_INF))
        do = jnp.ones_like(o)
        dq, dk, dv = flash_attention_bwd(
            q, k, v, o, lse, do, causal=True, q_offset=0, k_offset=64
        )
        assert float(jnp.abs(dq).max()) == 0.0
        assert float(jnp.abs(dk).max()) == 0.0

    def test_streaming_masked_rows_via_public_entry(self):
        # allow_fused=False pins the STREAMING kernels on fused-eligible
        # shapes, keeping the original masked-row regression guard alive
        # through the public differentiable entry
        blind = lambda qp, kp: (qp >= kp) & (qp >= 64)  # noqa: E731
        q, k, v = _qkv(T=128)

        def lp(q, k, v):
            return (
                flash_attention(
                    q, k, v, mask_fn=blind, force="pallas",
                    block_q=64, allow_fused=False,
                )
                ** 2
            ).sum()

        def lr(q, k, v):
            return (
                flash_attention_reference(q, k, v, mask_fn=blind) ** 2
            ).sum()

        out = flash_attention(
            q, k, v, mask_fn=blind, force="pallas", block_q=64,
            allow_fused=False,
        )
        assert float(jnp.abs(out[:, :64]).max()) == 0.0
        gp = jax.grad(lp, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(lr, argnums=(0, 1, 2))(q, k, v)
        assert float(jnp.abs(gp[0][:, :64]).max()) == 0.0
        for a, b in zip(gp, gr):
            np.testing.assert_allclose(a, b, atol=5e-4)

    def test_streaming_bhtd_gqa_grads(self):
        # GQA + layout="bhtd" exercises the streaming backward's bhtd
        # head-group reduction (reshape(B, Hkv, group, Tk, D).sum(2))
        q, k, v = _qkv(T=128, H=8, Hkv=2)
        qt, kt, vt = (x.transpose(0, 2, 1, 3) for x in (q, k, v))

        def lt(qt, kt, vt):
            o = flash_attention(
                qt, kt, vt, force="pallas", layout="bhtd"
            )
            return (o**2).sum()

        def lr(q, k, v):
            return (flash_attention_reference(q, k, v) ** 2).sum()

        gt = jax.grad(lt, argnums=(0, 1, 2))(qt, kt, vt)
        gr = jax.grad(lr, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gt, gr):
            np.testing.assert_allclose(
                a.transpose(0, 2, 1, 3), b, atol=5e-4
            )


class TestKernelRing:
    def test_ring_kernel_matches_reference(self, sp_mesh):
        from dlrover_tpu.parallel.ring_attention import ring_self_attention

        q, k, v = _qkv(T=256, H=4, Hkv=2)
        ref = flash_attention_reference(q, k, v, causal=True)
        out = ring_self_attention(
            q, k, v, sp_mesh, causal=True, use_kernel=True
        )
        np.testing.assert_allclose(out, ref, atol=2e-5)

    def test_ring_kernel_grads(self, sp_mesh):
        from dlrover_tpu.parallel.ring_attention import ring_self_attention

        q, k, v = _qkv(T=256, H=4, Hkv=2)

        def lk(q, k, v):
            return (
                ring_self_attention(
                    q, k, v, sp_mesh, causal=True, use_kernel=True
                )
                ** 2
            ).sum()

        def lr(q, k, v):
            return (flash_attention_reference(q, k, v) ** 2).sum()

        gk = jax.grad(lk, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(lr, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gk, gr):
            np.testing.assert_allclose(a, b, atol=1e-3)


@pytest.fixture(scope="module")
def sp_mesh():
    from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh

    return build_mesh(MeshConfig(sp=4, dp=2))


class TestAGD:
    def test_converges_on_quadratic(self):
        params = {"w": jnp.full((64,), 5.0)}
        tx = agd(1e-1)
        st = tx.init(params)

        def loss(p):
            return jnp.sum(p["w"] ** 2)

        for _ in range(200):
            g = jax.grad(loss)(params)
            u, st = tx.update(g, st, params)
            params = optax.apply_updates(params, u)
        assert float(loss(params)) < 1e-3

    def test_weight_decay_and_clip(self):
        params = {"w": jnp.full((8,), 2.0)}
        tx = agd(1e-2, weight_decay=0.1, clip=1.0)
        st = tx.init(params)
        g = {"w": jnp.full((8,), 1e6)}  # huge grad: clip caps the update
        u, st = tx.update(g, st, params)
        # |update| <= lr_adjust*clip + lr*wd*|p|
        assert float(jnp.abs(u["w"]).max()) < 1.0

    def test_amsgrad_state(self):
        params = {"w": jnp.zeros((4,))}
        tx = agd(1e-3, amsgrad=True)
        st = tx.init(params)
        assert st.max_exp_avg_sq is not None
        u, st2 = tx.update({"w": jnp.ones((4,))}, st, params)
        assert float(st2.max_exp_avg_sq["w"].max()) >= 0.0


class TestWSAM:
    def _grad_fn(self, p, _batch):
        loss = jnp.sum((p["w"] - 1.0) ** 2)
        return loss, jax.grad(lambda q: jnp.sum((q["w"] - 1.0) ** 2))(p)

    def test_decoupled_converges(self):
        wg = make_wsam_grad_fn(self._grad_fn, rho=0.05, decouple=True)
        p = {"w": jnp.full((16,), 3.0)}
        tx = optax.sgd(1e-1)
        st = tx.init(p)
        for _ in range(100):
            loss, g, sh = wg(p, None)
            u, st = tx.update(g, st, p)
            u = apply_wsam_sharpness(u, sh, 1e-1)
            p = optax.apply_updates(p, u)
        assert float(loss) < 1e-2

    def test_blended_converges(self):
        wg = make_wsam_grad_fn(self._grad_fn, rho=0.05, decouple=False)
        p = {"w": jnp.full((16,), 3.0)}
        tx = optax.sgd(1e-1)
        st = tx.init(p)
        for _ in range(100):
            loss, g, sh = wg(p, None)
            assert float(jnp.abs(sh["w"]).max()) == 0.0  # zero tree
            u, st = tx.update(g, st, p)
            p = optax.apply_updates(p, u)
        assert float(loss) < 1e-2


class TestQuantizedOptim:
    def test_quant_roundtrip(self):
        x = jnp.asarray(
            np.random.default_rng(0).normal(size=(1000,)), jnp.float32
        )
        q = quantize_8bit(x, signed=True)
        err = float(
            jnp.abs(dequantize_8bit(q) - x).max() / jnp.abs(x).max()
        )
        assert err < 0.02

    def test_tracks_fp32_adam(self):
        p8 = {
            "w": jnp.asarray(
                np.random.default_rng(1).normal(size=(8192,)), jnp.float32
            )
        }
        pf = jax.tree.map(lambda x: x, p8)
        tx8, txf = adamw_8bit(1e-2), optax.adamw(1e-2)
        s8, sf = tx8.init(p8), txf.init(pf)

        def loss(p):
            return jnp.sum((p["w"] - 1.0) ** 2)

        for _ in range(100):
            u8, s8 = tx8.update(jax.grad(loss)(p8), s8, p8)
            p8 = optax.apply_updates(p8, u8)
            uf, sf = txf.update(jax.grad(loss)(pf), sf, pf)
            pf = optax.apply_updates(pf, uf)
        # trajectories stay close despite 8-bit moments
        assert float(jnp.abs(p8["w"] - pf["w"]).max()) < 0.2
        assert float(loss(p8)) < 2.0 * float(loss(pf)) + 1.0

    def test_small_params_stay_fp32(self):
        p = {"small": jnp.zeros((16,)), "big": jnp.zeros((8192,))}
        tx = adamw_8bit(1e-3, min_quantized_size=4096)
        st = tx.init(p)
        assert isinstance(st.mu["small"], jnp.ndarray)
        assert not isinstance(st.mu["big"], jnp.ndarray)

    def test_update_is_jittable(self):
        p = {"w": jnp.zeros((8192,))}
        tx = adamw_8bit(1e-3)
        st = tx.init(p)

        @jax.jit
        def step(g, st, p):
            return tx.update(g, st, p)

        u, st2 = step({"w": jnp.ones((8192,))}, st, p)
        assert u["w"].shape == (8192,)

    def test_eps_conventions(self):
        """eps (classic, outside sqrt) must track optax.adamw exactly on
        fp32 leaves; eps_root is the optax eps_root convention; both at
        once is an error."""
        with pytest.raises(ValueError, match="either eps"):
            adamw_8bit(eps=1e-8, eps_root=1e-8)
        # small (fp32) leaves use the shared math: classic eps must
        # reproduce optax.adamw bit-for-bit over several steps
        p8 = {"w": jnp.asarray(np.linspace(-1, 1, 64), jnp.float32)}
        pf = jax.tree.map(lambda x: x, p8)
        tx8 = adamw_8bit(1e-2, eps=1e-8, min_quantized_size=4096)
        txf = optax.adam(1e-2, eps=1e-8)
        s8, sf = tx8.init(p8), txf.init(pf)
        for _ in range(10):
            g = {"w": jnp.cos(p8["w"])}
            u8, s8 = tx8.update(g, s8, p8)
            p8 = optax.apply_updates(p8, u8)
            uf, sf = txf.update(g, sf, pf)
            pf = optax.apply_updates(pf, uf)
        np.testing.assert_allclose(
            np.asarray(p8["w"]), np.asarray(pf["w"]), rtol=1e-6
        )

    @pytest.mark.parametrize(
        "shape", [(16, 256), (4, 8, 384), (6288, 256)], ids=str
    )
    def test_tile_layout_is_the_block_layout_in_another_order(self, shape):
        """A leaf of whole (8, 128) tiles keeps its moments in its own
        tile order; its 128-element blocks are the ones the [nblocks,
        128] layout has, so 20 steps give the same parameters, scales
        and codes as the block-layout update called directly."""
        from dlrover_tpu.ops.quantized_optim import (
            BLOCKS,
            TILES,
            _from_blocks,
            _from_tiles,
        )

        lr, b1, b2, eps, wd = 1e-2, 0.9, 0.95, 1e-8, 0.1
        rng = np.random.default_rng(7)
        p0 = jnp.asarray(rng.normal(size=shape), jnp.float32)
        target = jnp.asarray(rng.normal(size=shape), jnp.float32)
        weight = jnp.asarray(
            np.exp(rng.normal(size=shape) * 2.0), jnp.float32
        )
        grad = jax.grad(lambda p: jnp.sum(weight * (p - target) ** 2))

        tx = adamw_8bit(
            lr, b1=b1, b2=b2, eps=eps, weight_decay=wd,
            min_quantized_size=0,
        )
        st = tx.init({"w": p0})
        assert st.mu["w"].layout == st.nu["w"].layout == TILES
        R, C = shape[-2:]
        assert st.mu["w"].codes.shape == (
            *shape[:-2], R // 8, C // 128, 8, 128,
        )
        assert st.nu["w"].scales.shape == (*shape[:-2], R // 8, C // 128, 8)

        @jax.jit
        def step_tiles(p, st):
            u, st = tx.update({"w": grad(p)}, st, {"w": p})
            return optax.apply_updates({"w": p}, u)["w"], st

        @jax.jit
        def step_blocks(p, mq, vq, count):
            count = count + 1
            cf = count.astype(jnp.float32)
            sc = jnp.stack([
                jnp.float32(lr) / (1.0 - b1**cf),
                1.0 / (1.0 - b2**cf),
                jnp.float32(eps),
            ])
            mq, vq, delta = _adam8_update_jnp(
                _to_blocks(grad(p)), mq, vq, sc, b1, b2
            )
            u = _from_blocks(delta, shape) - lr * wd * p
            return p + u, mq, vq, count

        zeros = jnp.zeros(shape, jnp.float32)
        mq = quantize_8bit(zeros, True, BLOCKS)
        vq = quantize_8bit(zeros, False, BLOCKS)
        assert mq.layout == BLOCKS and mq.codes.shape[1:] == (128,)
        pt = pb = p0
        count = jnp.zeros((), jnp.int32)
        for _ in range(20):
            pt, st = step_tiles(pt, st)
            pb, mq, vq, count = step_blocks(pb, mq, vq, count)
        np.testing.assert_allclose(pt, pb, rtol=1e-6, atol=1e-7)
        for tiled, blocked in ((st.mu["w"], mq), (st.nu["w"], vq)):
            assert tiled.layout == TILES and tiled.shape == shape
            np.testing.assert_array_equal(
                np.asarray(tiled.scales).swapaxes(-2, -1).reshape(-1),
                np.asarray(blocked.scales).reshape(-1),
            )
            # the same numbers but for a rounding tie where the backend
            # contracts a multiply-add differently in the two programs
            d = np.abs(
                np.asarray(_from_tiles(tiled.codes, shape), np.int32)
                - np.asarray(_from_blocks(blocked.codes, shape), np.int32)
            )
            assert d.max() <= 1 and (d > 0).mean() < 1e-4, (
                d.max(), (d > 0).mean(),
            )

    @pytest.mark.parametrize("shape", [(4097,), (48, 100), (7, 1024)], ids=str)
    def test_odd_leaves_keep_the_block_layout(self, shape):
        """1-D leaves, widths that are not whole lane rows and row
        counts that are not whole sublane groups stay on the padded
        [nblocks, 128] path, a row of a width that is not whole lane
        rows padded to whole blocks: same state shapes, same values."""
        from dlrover_tpu.ops.quantized_optim import BLOCKS, _from_blocks

        nblocks = {(4097,): 33, (48, 100): 48, (7, 1024): 56}[shape]
        rng = np.random.default_rng(8)
        g = jnp.asarray(rng.normal(size=shape), jnp.float32)
        p = jnp.asarray(rng.normal(size=shape), jnp.float32)
        tx = adamw_8bit(1e-2, min_quantized_size=0)
        st = tx.init({"w": p})
        for q in (st.mu["w"], st.nu["w"]):
            assert q.layout == BLOCKS
            assert q.codes.shape == (nblocks, 128)
            assert q.scales.shape == (nblocks, 1)
        u, st2 = tx.update({"w": g}, st, {"w": p})
        cf = jnp.float32(1.0)  # the first step's bias corrections
        sc = jnp.stack([
            jnp.float32(1e-2) / (1.0 - 0.9**cf), 1.0 / (1.0 - 0.999**cf),
            jnp.float32(1e-8),
        ])
        mq, vq, delta = _adam8_update_jnp(
            _to_blocks(g), st.mu["w"], st.nu["w"], sc, 0.9, 0.999
        )
        np.testing.assert_array_equal(st2.mu["w"].codes, mq.codes)
        np.testing.assert_array_equal(st2.nu["w"].codes, vq.codes)
        np.testing.assert_array_equal(st2.nu["w"].scales, vq.scales)
        np.testing.assert_allclose(
            u["w"], _from_blocks(delta, shape), rtol=1e-6
        )

    @pytest.mark.parametrize(
        "shape,layout",
        [
            ((16, 256), "tiles"), ((4, 8, 384), "tiles"),
            ((4097,), "blocks"), ((48, 100), "blocks"),
            ((16, 256), "forced-blocks"),
        ],
        ids=str,
    )
    def test_quant_roundtrip_returns_the_leafs_shape(self, shape, layout):
        from dlrover_tpu.ops.quantized_optim import BLOCKS

        x = jnp.asarray(
            np.random.default_rng(9).normal(size=shape), jnp.float32
        )
        forced = layout == "forced-blocks"
        q = quantize_8bit(x, True, BLOCKS if forced else None)
        assert q.layout == (BLOCKS if forced else layout)
        back = dequantize_8bit(q)
        assert back.shape == shape and q.shape == shape
        assert float(jnp.abs(back - x).max() / jnp.abs(x).max()) < 0.02
        # the blocks are the same 128 elements in either layout
        np.testing.assert_array_equal(
            back, dequantize_8bit(quantize_8bit(x, True, BLOCKS))
        )

    @pytest.mark.parametrize("make", ["8bit", "4bit"])
    def test_no_block_crosses_a_row(self, make):
        """A head ``[d, vocab]`` whose vocabulary is one and a half lane
        tiles wide, its first ids frequent and its last ones rare: were
        the leaf flattened before it is cut into blocks, every second
        block would hold a row's rare columns beside the next row's
        frequent ones, the rare columns' second moments would round to
        zero under the block's scale while their first moments did not,
        and their update would be lr * m / eps (the Trinity-Mini cell at
        25,024 rows lost its loss so: PERF.md, Findings PR 50)."""
        from dlrover_tpu.ops.quantized_optim import _from_blocks, adamw_4bit

        shape, lr = (64, 192), 1e-3
        rng = np.random.default_rng(11)
        col = np.where(np.arange(192) < 128, 1.0, 1e-4)
        grads = [
            jnp.asarray(rng.normal(size=shape) * col, jnp.float32)
            for _ in range(4)
        ]
        np.testing.assert_array_equal(
            _from_blocks(_to_blocks(grads[0]), shape), grads[0]
        )
        blocks = np.asarray(_to_blocks(grads[0])).reshape(64, 2, 128)
        assert (np.abs(blocks[:, 1, :64]).max(-1) < 1e-3).all()
        assert (blocks[:, 1, 64:] == 0).all()
        tx = {"8bit": adamw_8bit, "4bit": adamw_4bit}[make](
            learning_rate=lr, min_quantized_size=4096
        )
        p = {"head": jnp.zeros(shape, jnp.float32)}
        st = tx.init(p)
        for g in grads:
            u, st = tx.update({"head": g}, st, p)
        u = u["head"]
        assert u.shape == shape
        # Adam moves no entry much further than lr a step; m / eps is 1e4 lr
        assert float(jnp.abs(u).max()) < 20 * lr

    @pytest.mark.parametrize("shape", [(16, 256), (4097,)], ids=str)
    def test_tree_flatten_keeps_the_layout_tag(self, shape):
        q = quantize_8bit(jnp.ones(shape, jnp.float32), False)
        leaves, treedef = jax.tree.flatten({"m": q})
        assert len(leaves) == 2
        back = jax.tree.unflatten(treedef, leaves)["m"]
        assert (back.layout, back.shape, back.signed) == (
            q.layout, shape, False,
        )
        # through jit too: the tag is aux data and never traced
        out = jax.jit(lambda t: t)(q)
        assert out.layout == q.layout and out.codes.shape == q.codes.shape
        assert q.layout in repr(q)

    def test_4bit_roundtrip_and_memory(self):
        from dlrover_tpu.ops.quantized_optim import (
            dequantize_4bit,
            quantize_4bit,
        )

        x = jnp.asarray(
            np.random.default_rng(0).normal(size=(4096,)), jnp.float32
        )
        q = quantize_4bit(x, signed=True)
        assert q.packed.dtype == jnp.uint8
        assert q.packed.size == 2048  # two codes per byte: 8x under fp32
        err = float(
            jnp.abs(dequantize_4bit(q) - x).max() / jnp.abs(x).max()
        )
        assert err < 0.2  # 4-bit sqrt map: coarse but bounded

    def test_4bit_adam_tracks_fp32(self):
        from dlrover_tpu.ops.quantized_optim import adamw_4bit

        p4 = {
            "w": jnp.asarray(
                np.random.default_rng(1).normal(size=(8192,)), jnp.float32
            )
        }
        pf = jax.tree.map(lambda x: x, p4)
        tx4, txf = adamw_4bit(learning_rate=1e-2), optax.adamw(1e-2)
        s4, sf = tx4.init(p4), txf.init(pf)

        def loss(p):
            return jnp.sum((p["w"] - 1.0) ** 2)

        @jax.jit
        def step4(g, s, p):
            return tx4.update(g, s, p)

        for _ in range(100):
            u4, s4 = step4(jax.grad(loss)(p4), s4, p4)
            p4 = optax.apply_updates(p4, u4)
            uf, sf = txf.update(jax.grad(loss)(pf), sf, pf)
            pf = optax.apply_updates(pf, uf)
        # 4-bit first moment is coarse per-coordinate, but the OBJECTIVE
        # must track fp32 Adam closely (the meaningful criterion for a
        # quantized optimizer; individual coordinates wander within the
        # quantization noise floor)
        assert float(loss(p4)) < 1.5 * float(loss(pf)) + 10.0


class TestModelUsesFlash:
    def test_transformer_attention_dispatches(self):
        # _causal_attention now routes through ops.flash_attention
        from dlrover_tpu.models.transformer import _causal_attention

        q, k, v = _qkv(T=64)
        out = _causal_attention(q, k, v)
        ref = flash_attention_reference(q, k, v, causal=True)
        np.testing.assert_allclose(out, ref, atol=2e-5)


# ---------------------------------------------------------------------------
# int8 quantized matmul (AQT-style, the FP8-optimization analog)
# ---------------------------------------------------------------------------
def test_int8_matmul_accuracy():
    from dlrover_tpu.ops import int8_matmul

    rng = np.random.default_rng(0)
    a = rng.normal(size=(64, 128)).astype(np.float32)
    b = rng.normal(size=(128, 32)).astype(np.float32)
    exact = a @ b
    got = np.asarray(int8_matmul(jnp.asarray(a), jnp.asarray(b)))
    # per-slice symmetric int8: relative error ~1/127 per operand
    rel = np.abs(got - exact) / (np.abs(exact) + 1e-3)
    assert float(np.median(rel)) < 0.05, float(np.median(rel))


def test_int8_matmul_ste_grads():
    """Straight-through backward equals the exact matmul's gradients."""
    from dlrover_tpu.ops import int8_matmul

    rng = np.random.default_rng(1)
    a = jnp.asarray(rng.normal(size=(8, 16)).astype(np.float32))
    b = jnp.asarray(rng.normal(size=(16, 4)).astype(np.float32))

    da, db = jax.grad(lambda a, b: jnp.sum(int8_matmul(a, b) ** 2), (0, 1))(
        a, b
    )
    # cotangent g = 2*out; STE: da = g @ b.T, db = a.T @ g with the
    # QUANTIZED out inside g
    out = int8_matmul(a, b)
    np.testing.assert_allclose(
        np.asarray(da), np.asarray(2 * out @ b.T), rtol=1e-5, atol=1e-5
    )
    np.testing.assert_allclose(
        np.asarray(db), np.asarray(a.T @ (2 * out)), rtol=1e-5, atol=1e-5
    )


def test_int8_mlp_trains():
    """tiny model with int8 MLP projections still converges."""
    import optax

    from dlrover_tpu.models import init_params, tiny
    from dlrover_tpu.models.transformer import loss_fn

    cfg = tiny(int8_mlp=True)
    params = init_params(jax.random.PRNGKey(0), cfg)
    tx = optax.adamw(1e-2)
    opt = tx.init(params)
    rng = np.random.default_rng(0)
    x = rng.integers(0, cfg.vocab_size, (8, 16)).astype(np.int32)

    @jax.jit
    def step(params, opt):
        l, g = jax.value_and_grad(lambda p: loss_fn(p, x, x, cfg))(params)
        upd, opt = tx.update(g, opt, params)
        return optax.apply_updates(params, upd), opt, l

    losses = []
    for _ in range(8):
        params, opt, l = step(params, opt)
        losses.append(float(l))
    assert losses[-1] < losses[0] - 0.5, losses
