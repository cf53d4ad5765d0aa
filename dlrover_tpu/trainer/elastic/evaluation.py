"""ElasticTrainer's evaluation rider: the grad-free loss over the eval
set, the best-eval checkpoint with its sidecar, and early stopping (the
AtorchTrainer save-strategy / EarlyStoppingCallback surface)."""

from __future__ import annotations

import json
import os
import time
from typing import Callable, Dict, Optional

import numpy as np

from dlrover_tpu.ckpt.checkpointer import FlashCheckpointer, StorageType
from dlrover_tpu.common import storage
from dlrover_tpu.common.log import default_logger as logger


class Evaluator:
    """``place(batch)`` puts one host batch on the current mesh,
    ``first_build(what)`` is the trainer's ``build:<what>`` span, and
    ``ckpt_state()`` the checkpoint tree a best-save persists."""

    def __init__(
        self,
        tcfg,
        dataset,
        collate_fn: Optional[Callable],
        place: Callable,
        first_build: Callable,
        ckpt_state: Callable,
    ):
        self._tcfg = tcfg
        self.dataset = dataset
        self._collate_fn = collate_fn
        self._place = place
        self._first_build = first_build
        self._ckpt_state = ckpt_state
        self.step_fn = None  # per-mesh memo; a resize drops it
        self.best_ckptr: Optional[FlashCheckpointer] = None
        # the historical best survives restarts via a sidecar; a fresh
        # run starts at +inf
        self.best_loss = float("inf")
        self._last_best_save = 0.0
        self.begin_run()
        if tcfg.ckpt_dir and tcfg.save_best:
            self._best_dir = os.path.join(tcfg.ckpt_dir, "best")
            self._sidecar = os.path.join(self._best_dir, "best_eval.json")
            self.best_ckptr = FlashCheckpointer(self._best_dir)
            try:
                with open(self._sidecar) as f:
                    self.best_loss = float(json.load(f)["eval_loss"])
            except (OSError, ValueError, KeyError):
                pass

    def begin_run(self):
        """A ``train()`` call starts: the run-local best for the
        patience counter is reset; the PERSISTED best (``best_loss``,
        sidecar-loaded) deliberately survives so a restarted run's first
        (worse) eval can't supersede it on disk."""
        self.last: Dict[str, float] = {}
        self._run_best_loss = float("inf")
        self._evals_since_best = 0

    def due(self, step: int) -> bool:
        return bool(
            self.dataset is not None
            and self._tcfg.eval_interval
            and step % self._tcfg.eval_interval == 0
        )

    def staging_in_flight(self) -> bool:
        return (
            self.best_ckptr is not None
            and self.best_ckptr.staging_in_flight()
        )

    def close(self):
        if self.best_ckptr is not None:
            self.best_ckptr.engine.close()

    def _build_step(self, accel, cache):
        """Eval loss step, memoized per mesh through the compile cache:
        a resize invalidates the stale wrapper, but resizing back to a
        previously-seen mesh reuses the jitted step instead of
        re-tracing (the old behavior re-``jax.jit``-ed after every
        mesh change)."""
        import jax

        from dlrover_tpu.accel.compile_cache import (
            fingerprint,
            mesh_signature,
        )

        cfg, mesh, strategy = accel.cfg, accel.mesh, accel.strategy
        key = fingerprint(
            "eval_step",
            strategy.to_json(),
            mesh_signature(mesh),
            repr(cfg),
        )

        def build():
            if strategy.mesh.pp > 1:
                from dlrover_tpu.parallel.pipeline import (
                    pipeline_loss_fn,
                )

                mb = strategy.num_microbatches
                # the state layout is [pp, v, lc] iff the TRAINING
                # schedule is interleaved — eval must read the same
                # layout. The schedule may live in pp_schedule OR
                # (pre-apply) only in opts; resolved_virtual() honors
                # both sources
                virtual = strategy.resolved_virtual()

                def eval_loss(params, x, y):
                    return pipeline_loss_fn(
                        params, x, y, cfg, mesh, mb, virtual=virtual
                    )

            else:
                from dlrover_tpu.models.transformer import (
                    forward,
                    token_nll,
                )

                def eval_loss(params, x, y):
                    # PURE NLL — no MoE aux regularizers, so eval_loss /
                    # ppl are comparable across parallelism modes and
                    # configs (the pp path wraps the pipeline's own loss)
                    logits, _ = forward(params, x, cfg, mesh)
                    return token_nll(logits, y)

            return jax.jit(eval_loss)

        fn, _ = cache.get_or_build(key, build)
        return fn

    def _batches(self, max_batches: int):
        """Sequential fixed-size batches over the eval set (no sampler
        elasticity — eval restarts from the top every call)."""
        bs = self._tcfg.batch_size
        n = len(self.dataset)
        for start in range(0, min(max_batches * bs, n - bs + 1), bs):
            rows = [self.dataset[i] for i in range(start, start + bs)]
            if self._collate_fn is not None:
                yield self._collate_fn(rows)
            elif isinstance(rows[0], dict):
                yield {
                    k: np.stack([r[k] for r in rows]) for k in rows[0]
                }
            else:
                yield tuple(
                    np.stack([r[j] for r in rows])
                    for j in range(len(rows[0]))
                )

    def evaluate(
        self, accel, cache, params, max_batches: Optional[int] = None
    ) -> Dict[str, float]:
        """Run the eval set through a grad-free sharded loss step on
        ``accel``'s world. Returns {"eval_loss": mean NLL, "eval_ppl":
        exp(mean NLL)}."""
        if self.dataset is None:
            raise ValueError("ElasticTrainer built without eval_dataset")
        if self.step_fn is None:
            self.step_fn = self._build_step(accel, cache)
        max_batches = max_batches or self._tcfg.eval_steps
        losses = []
        for batch in self._batches(max_batches):
            x, y = self._place(batch)
            with self._first_build("eval"):
                losses.append(float(self.step_fn(params, x, y)))
        if not losses:
            # a silent NaN here would poison every later metrics report
            raise ValueError(
                f"eval dataset ({len(self.dataset)} rows) yields "
                f"zero batches of size {self._tcfg.batch_size}"
            )
        mean = float(np.mean(losses))
        return {
            "eval_loss": mean,
            "eval_ppl": float(np.exp(min(mean, 20.0))),
        }

    def after_eval(self, step: int, scalars: Dict[str, float]) -> bool:
        """save-best / early-stopping bookkeeping over one pass's
        ``scalars``; True = stop now.

        Two distinct "best" trackers on purpose:

        - ``_run_best_loss`` (reset every train() call) drives the
          patience counter — a restarted run that is still improving
          run-locally must not be stopped just because it hasn't yet
          beaten the historical best it restarted below;
        - ``best_loss`` is the best PERSISTED loss (sidecar) and
          only advances when a checkpoint actually commits — a save
          skipped by the rate limit stays beatable, so the next
          improvement past the window persists instead of being lost.
        """
        self.last = scalars
        loss = scalars.get("eval_loss", float("inf"))
        if loss < self._run_best_loss:
            self._run_best_loss = loss
            self._evals_since_best = 0
        else:
            self._evals_since_best += 1
        if (
            self.best_ckptr is not None
            and loss < self.best_loss
            and time.time() - self._last_best_save
            >= self._tcfg.save_best_min_interval_s
        ):
            logger.info(
                f"step {step}: new best eval_loss={loss:.4f}; "
                f"persisting to {self._best_dir}"
            )
            if self.best_ckptr.save_checkpoint(
                step, self._ckpt_state(), StorageType.DISK
            ):
                # the sidecar records the PERSISTED best — written only
                # after the commit, so a crash mid-save cannot leave it
                # claiming a checkpoint that isn't there; durable
                # (fsync-before-rename) because its whole contract is
                # being as durable as the checkpoint it describes
                # (graftlint durable-rename)
                storage.durable_replace(
                    self._sidecar,
                    lambda f: json.dump(
                        {"eval_loss": loss, "step": step}, f
                    ),
                )
                self.best_loss = loss
                self._last_best_save = time.time()
        return (
            self._tcfg.early_stopping_patience > 0
            and self._evals_since_best
            >= self._tcfg.early_stopping_patience
        )
