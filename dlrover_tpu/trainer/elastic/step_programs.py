"""StepPrograms: which program runs this step.

One world's train step is up to three callables: the donating jit
function, its non-donating twin (a checkpoint stage may read the state
while it runs), and that twin's AOT executable out of the compile
cache, which outlives the jit wrapper a resize throws away. This object
owns them, the batch shapes they were lowered for, and the cache."""

from __future__ import annotations

import os

import numpy as np

from dlrover_tpu.accel.accelerate import AccelerateResult
from dlrover_tpu.accel.compile_cache import (
    CompileCache,
    fingerprint,
    mesh_signature,
    tree_signature,
)
from dlrover_tpu.common.constants import NodeEnv
from dlrover_tpu.common.log import default_logger as logger
from dlrover_tpu.obs.trace import span


def aot_supported(strategy) -> bool:
    # the pipeline step takes host arrays (different signature) and
    # the offload step's mixed host/device shardings defeat the
    # spec-keyed cache — both keep their lazy jit path
    return strategy.mesh.pp == 1 and not strategy.offload_opt


def step_cache_key(strategy, mesh, state_like, batch_like) -> str:
    """Compile-cache key of the SAFE train step for one world:
    (strategy fingerprint, mesh shape + device assignment, abstract
    state/batch shapes, donation signature). ``state_like`` and
    ``batch_like`` may be concrete arrays or ShapeDtypeStructs —
    both produce the same key (``tree_signature`` drops
    weak_type), so a speculative pre-lower from specs collides
    with the resize that consumes it. The job-name salt keeps two
    jobs sharing one on-disk cache apart (a key assumes tx was
    constructed identically, which holds within one SPMD job)."""
    return fingerprint(
        "train_step",
        strategy.to_json(),
        mesh_signature(mesh),
        tree_signature(state_like),
        tree_signature(batch_like),
        "donate=0",
        os.getenv(NodeEnv.JOB_NAME, ""),
    )


class StepPrograms:
    def __init__(
        self, accel: AccelerateResult, stats, donation_aware: bool = True
    ):
        self._stats = stats
        self._donation_aware = donation_aware
        # AOT executables keyed by (mesh, shapes, donation, strategy):
        # the first step on any mesh lands here, so a later resize back
        # to that mesh skips the XLA compile entirely
        self.cache = CompileCache(stats=stats)
        self.batch_avals = None  # ((shape, dtype), ...) of (x, y)
        self._state_nbytes = 0
        self.rebuild(accel)

    def rebuild(self, accel: AccelerateResult):
        """The programs of a new world (a resize): its two jit functions;
        the old world's executable is dropped."""
        self._accel = accel
        self.safe_step = accel.step_fn
        # donation-aware stepping: the donating twin runs whenever no
        # async staging reads the state; flip back to the safe step for
        # the staging window
        self.donating_step = (
            accel.donating_step_fn if self._donation_aware else None
        )
        # the AOT executable + the exact batch shapes it was lowered
        # for; other shapes (short final batch, master-retuned batch
        # size) fall through to the retracing jit wrapper
        self.aot_exec = None
        self._aot_shapes = None
        self._aot_primed = False

    def donates(self, staging: bool) -> bool:
        """Whether the next step donates the state and the batch."""
        return self.donating_step is not None and not staging

    def step_for(self, state, x, y, donate: bool):
        """The program to call for this step, accounted for in
        ``donated_steps`` / ``safe_steps`` / ``donated_bytes``; the first
        safe step builds its executable here, through the AOT cache."""
        if self.batch_avals is None:
            self._record(state, x, y)
        stats = self._stats
        if donate:
            stats.donated_steps += 1
            stats.donated_bytes += self._state_nbytes + sum(
                getattr(b, "nbytes", 0) for b in (x, y)
            )
            return self.donating_step
        stats.safe_steps += 1
        if not self._aot_primed:
            self._prime(state, x, y)
        # the AOT executable when the shapes match what it was lowered
        # for, else the jit wrapper — a Compiled rejects differing avals
        # where jit retraces, and both the dataloader's short final
        # batch and a master-retuned batch size legitimately change the
        # shape mid-run
        if self.aot_exec is not None and self._aot_shapes == (
            tuple(x.shape), tuple(y.shape)
        ):
            return self.aot_exec
        return self.safe_step

    def _record(self, state, x, y):
        """Shapes/dtypes of the live batch — speculative compiles for
        other meshes lower against these. Recorded at the REAL row
        count: a rebalanced strategy's zero-weight pad rows are its
        own physical artifact (``lowering_for`` re-pads per target
        strategy). And the bytes one donating step reuses."""
        import jax

        self._state_nbytes = sum(
            a.size * a.dtype.itemsize
            for a in jax.tree_util.tree_leaves(state)
            if hasattr(a, "dtype")
        )
        pad = int(getattr(self._accel.strategy, "batch_pad", 0) or 0)
        try:
            self.batch_avals = tuple(
                ((int(b.shape[0]) - pad,) + tuple(b.shape[1:]), str(b.dtype))
                for b in (x, y)
            )
        except (AttributeError, TypeError, IndexError):
            pass

    def _prime(self, state, x, y):
        """First SAFE step on a mesh: route it through the AOT compile
        cache. This replaces (not adds to) the lazy jit compile that
        would happen at this exact moment, but the executable lands in
        a cache that outlives the wrapper a resize throws away — the
        entry is what makes resizing BACK to this mesh warm. Donating
        steps never prime: their twin is a different program, and a
        donation-only run pays no extra compile for a cache it may
        never need (the resize itself populates it then)."""
        self._aot_primed = True
        strategy = self._accel.strategy
        if not aot_supported(strategy):
            return
        step_fn = self.safe_step
        key = step_cache_key(strategy, self._accel.mesh, state, (x, y))
        try:
            with span("compile_prime"):
                fn, _ = self.cache.get_or_compile(
                    key, lambda: step_fn.lower(state, x, y).compile()
                )
            self.install(fn, (x.shape, y.shape))
        except Exception as e:
            # AOT is an optimization: a lowering quirk must not take
            # down training — the lazy jit path still works
            logger.warning(f"AOT step-cache priming failed: {e!r}")

    def install(self, exec_fn, shapes=None):
        """Hand over the safe step's executable, built ahead of the
        first step on this world (a resize: out of the compile cache);
        it serves batches of ``shapes``, by default the recorded ones."""
        if shapes is None:
            shapes = [shape for shape, _ in self.batch_avals]
        self.aot_exec = exec_fn
        self._aot_shapes = tuple(tuple(s) for s in shapes)
        self._aot_primed = True

    def lowering_for(self, strategy, mesh, state_like):
        """``(cache key, abstract (x, y))`` of the safe step on ``mesh``
        under ``strategy``, from the REAL batch avals recorded at the
        first step — re-padded for the target strategy's micro-batch
        rebalance (batch_pad differs per world, so the same real batch
        lowers to different physical shapes on different strategies)."""
        import jax

        from dlrover_tpu.parallel.mesh import batch_sharding

        pad = int(getattr(strategy, "batch_pad", 0) or 0)
        sh = batch_sharding(mesh)
        xy = tuple(
            jax.ShapeDtypeStruct(
                (shape[0] + pad,) + tuple(shape[1:]),
                np.dtype(dt),
                sharding=sh,
            )
            for shape, dt in self.batch_avals
        )
        return step_cache_key(strategy, mesh, state_like, xy), xy
