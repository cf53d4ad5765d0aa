"""The delta rule's serial pass as kernels against its plain statement
(``gated_delta._pass_scan`` / ``_pass_scan_bwd``), for the three families'
tests: the comparison of both results and all five cotangents on the
stand-alone bench's seeded arguments."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

from dlrover_tpu.ops import gated_delta
from dlrover_tpu.ops import gated_delta_kernels as kernels

sys.path.insert(
    0, os.path.join(os.path.dirname(os.path.dirname(__file__)), "tools")
)
import gdn_pass_bench  # noqa: E402

# the results to the dtype's rounding; a cotangent sums a chunk's or a
# head's products in another order (float32), or is rounded twice
TOL = {"float32": (2e-6, 2e-4), "bfloat16": (8e-3, 2e-2)}


def pass_inputs(n, b, g, r, C, d_k, d_v, channel, dtype, seed=0):
    """``chunk_state_pass``'s arguments as the stand-alone bench makes them
    (``tools/gdn_pass_bench.inputs``)."""
    return gdn_pass_bench.inputs(
        n, b, g, r, C, d_k, d_v, channel, seed=seed, dtype=dtype
    )


def _close(got, want, tol):
    assert got.shape == want.shape and got.dtype == want.dtype
    got, want = (np.asarray(x, np.float32) for x in (got, want))
    assert np.max(np.abs(got - want)) <= tol * np.max(np.abs(want))


def check_pass(n, b, g, r, C, d_k, d_v, channel, dtype, blocks):
    """The kernels' ``V'`` and entered states, and their cotangents of
    ``U, W, K, delta, a`` from seeded cotangents of both, against the plain
    scan's; ``blocks`` is the chunk runs a head's state must cross."""
    tol, grad_tol = TOL[dtype]
    args = pass_inputs(n, b, g, r, C, d_k, d_v, channel, jnp.dtype(dtype))
    _, m = kernels._pass_block(
        n, g, r, C, d_k, d_v, jnp.dtype(dtype).itemsize
    )
    assert n // m == blocks
    want = jax.jit(gated_delta._pass_scan)(*args)
    got = jax.jit(kernels.state_pass)(*args)
    for x, y in zip(got, want):
        _close(x, y, tol)
    ks = jax.random.split(jax.random.PRNGKey(7), 2)
    cts = tuple(
        (s * jax.random.normal(k, x.shape)).astype(x.dtype)
        for k, x, s in zip(ks, want, (1.0, 0.1))
    )
    res = (*args[1:], *want)
    d_want = jax.jit(gated_delta._pass_scan_bwd)(res, cts)
    d_got = jax.jit(kernels.state_pass_rev)(*res, *cts)
    assert (d_got[3] is None) == (d_want[3] is None) == channel
    for x, y in zip(d_got, d_want):
        if y is not None:
            _close(x, y, grad_tol)
