"""AOT compile cache + speculative compiler for elastic resizes.

The elastic-resize cost model (ElasWave, PAPERS.md): a scale event that
re-jits the train step from scratch pays minutes of XLA compile at
large-model scale — pure downtime, since the program for any given
(mesh, shapes, donation, strategy) tuple is deterministic. This module
makes resize a *live reconfiguration*:

- ``CompileCache``: an in-process LRU of AOT-compiled executables keyed
  by ``fingerprint(mesh shape, abstract state/batch shapes, donation
  signature, strategy fingerprint)``, with an optional on-disk layer
  (``jax.experimental.serialize_executable`` through
  ``common.jax_compat``) so a replacement worker warm-starts from a
  peer's serialized executable.  A generic ``get_or_build`` memo rides
  along for callables that cannot be serialized (lazily-jitted eval
  steps — the per-mesh memoization ``trainer.elastic.evaluation.Evaluator``
  uses).
- ``SpeculativeCompiler``: a background thread that pre-lowers the
  train step for the *likely next* meshes (the master's
  ``JobAutoScaler`` publishes its top-k candidate worker counts through
  the paral-config channel) while the current mesh trains.  Budgeted —
  a wall-clock cap per candidate batch — and pausable, so checkpoint
  staging windows are never contended.

The executables a ``jax.jit`` wrapper caches internally die with the
wrapper; caching the *compiled* stage instead survives the wrapper
being rebuilt on resize, which is what makes a warm resize skip the
compile entirely.
"""

from __future__ import annotations

import hashlib
import os
import threading
import time
from collections import OrderedDict, deque
from dataclasses import dataclass, field
from typing import Any, Callable, List, Optional, Sequence, Tuple

from dlrover_tpu.common import storage
from dlrover_tpu.common.log import default_logger as logger


def fingerprint(*parts: Any) -> str:
    """Stable hex key from heterogeneous parts (strings, numbers,
    tuples...). Object identity never leaks in — only ``repr`` of
    value-like parts — so two processes computing the same logical key
    agree (the disk layer depends on that)."""
    h = hashlib.sha256()
    for p in parts:
        h.update(repr(p).encode())
        h.update(b"\x00")
    return h.hexdigest()


def mesh_signature(mesh) -> Tuple:
    """(axis names, axis sizes, sorted device ids, platform) — the part
    of a compile key that pins the executable to a concrete device
    assignment."""
    devs = list(mesh.devices.flat)
    return (
        tuple(mesh.axis_names),
        tuple(mesh.devices.shape),
        tuple(sorted(d.id for d in devs)),
        getattr(devs[0], "platform", "unknown") if devs else "none",
    )


def tree_signature(tree: Any) -> Tuple:
    """Per-leaf (path, shape, dtype, partition spec) of a pytree whose
    leaves are arrays OR ``ShapeDtypeStruct``s. weak_type is excluded on
    purpose: a key computed from a concrete state and one computed from
    ``eval_shape`` specs must collide (speculative compiles key off
    specs, the resize that consumes them keys off the live state)."""
    import jax

    out = []
    for kp, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        path = "/".join(str(k) for k in kp)
        sharding = getattr(leaf, "sharding", None)
        spec = str(getattr(sharding, "spec", None))
        out.append(
            (path, tuple(leaf.shape), str(leaf.dtype), spec)
        )
    return tuple(out)


@dataclass
class _Entry:
    obj: Any
    serializable: bool = False


class CompileCache:
    """LRU of compiled/bulit artifacts keyed by ``fingerprint`` keys.

    Two tiers:

    - ``get_or_build``: pure in-memory memo for arbitrary callables
      (jit wrappers, eval steps) — never touches disk;
    - ``get_or_compile``: for AOT ``Compiled`` executables; misses
      consult the on-disk layer before building, and fresh builds are
      serialized back (both legs best-effort through
      ``common.jax_compat`` — a program that cannot be pickled
      degrades to memory-only).

    Hit/miss counters land in an ``accel.profiler.PipelineStats`` when
    one is attached, so ``compile_cache_hit_pct`` rides the same record
    the rest of the pipeline reports through.
    """

    def __init__(
        self,
        capacity: int = 8,
        cache_dir: Optional[str] = None,
        stats=None,
    ):
        self._capacity = max(1, int(capacity))
        self._cache_dir = (
            cache_dir
            if cache_dir is not None
            else os.getenv("DLROVER_TPU_AOT_CACHE", "")
        )
        self._lock = threading.Lock()
        self._entries: "OrderedDict[str, _Entry]" = OrderedDict()
        self.stats = stats
        self.hits = 0
        self.misses = 0
        self.disk_hits = 0

    # -- introspection -------------------------------------------------
    @property
    def hit_pct(self) -> Optional[float]:
        n = self.hits + self.misses
        if not n:
            return None
        return round(100.0 * self.hits / n, 2)

    def peek(self, key: str) -> bool:
        """True when ``key`` is resident (no counters touched — the
        speculative compiler polls this to skip work already done)."""
        with self._lock:
            return key in self._entries

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    # -- core ----------------------------------------------------------
    def _count(self, hit: bool):
        if hit:
            self.hits += 1
        else:
            self.misses += 1
        if self.stats is not None:
            if hit:
                self.stats.compile_cache_hits += 1
            else:
                self.stats.compile_cache_misses += 1

    def _get_locked(self, key: str) -> Optional[_Entry]:
        e = self._entries.get(key)
        if e is not None:
            self._entries.move_to_end(key)
        return e

    def _put(self, key: str, entry: _Entry):
        with self._lock:
            self._entries[key] = entry
            self._entries.move_to_end(key)
            while len(self._entries) > self._capacity:
                evicted, _ = self._entries.popitem(last=False)
                logger.info(f"compile cache evicted {evicted[:12]}…")

    def get_or_build(
        self, key: str, build: Callable[[], Any]
    ) -> Tuple[Any, bool]:
        """Memory-only memo: ``(artifact, hit)``."""
        with self._lock:
            e = self._get_locked(key)
        if e is not None:
            self._count(True)
            return e.obj, True
        obj = build()
        self._count(False)
        self._put(key, _Entry(obj))
        return obj, False

    def get_or_compile(
        self, key: str, build: Callable[[], Any]
    ) -> Tuple[Any, bool]:
        """Memory LRU → disk layer → build. ``(compiled, hit)`` where a
        disk load counts as a hit (the compile was skipped, which is
        the number that matters)."""
        with self._lock:
            e = self._get_locked(key)
        if e is not None:
            self._count(True)
            return e.obj, True
        obj = self._load_disk(key)
        if obj is not None:
            self._count(True)
            self.disk_hits += 1
            self._put(key, _Entry(obj, serializable=True))
            return obj, True
        t0 = time.perf_counter()
        obj = build()
        self._count(False)
        logger.info(
            f"compile cache miss {key[:12]}…: compiled in "
            f"{time.perf_counter() - t0:.2f}s"
        )
        self._put(key, _Entry(obj, serializable=True))
        self._save_disk(key, obj)
        return obj, False

    # -- disk layer (best-effort) --------------------------------------
    def _disk_path(self, key: str) -> str:
        return os.path.join(self._cache_dir, f"{key}.aotx")

    def _load_disk(self, key: str) -> Optional[Any]:
        if not self._cache_dir:
            return None
        from dlrover_tpu.common.jax_compat import deserialize_compiled

        path = self._disk_path(key)
        try:
            with open(path, "rb") as f:
                blob = f.read()
        except OSError:
            return None
        obj = deserialize_compiled(blob)
        if obj is None:
            # stale/incompatible entry: drop it so the next miss rewrites
            try:
                os.unlink(path)
            except OSError:
                pass
        return obj

    def _save_disk(self, key: str, compiled: Any):
        if not self._cache_dir:
            return
        from dlrover_tpu.common.jax_compat import serialize_compiled

        blob = serialize_compiled(compiled)
        if blob is None:
            return
        try:
            os.makedirs(self._cache_dir, exist_ok=True)
            # durable, not just atomic: a torn cache entry after a crash
            # deserializes garbage on the NEXT process's warm resize —
            # fsync costs µs against a multi-second compile (graftlint
            # durable-rename)
            storage.durable_replace(
                self._disk_path(key), lambda f: f.write(blob), mode="wb"
            )
        except OSError as e:
            logger.warning(f"compile cache disk write failed: {e!r}")


@dataclass
class CompileTask:
    """One speculative pre-lower: ``build`` must return the compiled
    executable for ``key``."""

    label: str
    key: str
    build: Callable[[], Any]


class SpeculativeCompiler:
    """Background pre-lowering of likely-next-mesh executables.

    ``submit`` REPLACES the pending queue (the newest scale prediction
    wins — stale candidates are worthless) and resets the wall-clock
    budget; the worker thread then drains tasks into the cache unless
    ``pause_fn()`` holds (checkpoint staging windows: the D2H drain and
    a concurrent compile fight for the same host cores) or the budget
    is spent (remaining candidates are dropped with a log — the next
    prediction resubmits what still matters).
    """

    def __init__(
        self,
        cache: CompileCache,
        pause_fn: Optional[Callable[[], bool]] = None,
        budget_s: float = 120.0,
        poll_s: float = 0.05,
    ):
        self.cache = cache
        self._pause_fn = pause_fn
        self._budget_s = float(budget_s)
        self._poll_s = poll_s
        self._cond = threading.Condition()
        self._tasks: deque = deque()
        self._spent = 0.0
        self._closed = False
        self._gen = 0  # bumped per submit; stale pops never requeue
        self.compiled = 0
        self.dropped = 0
        self.errors = 0
        # key currently being compiled (best-effort, unlocked read is
        # fine): a resize landing on this exact key should wait_idle()
        # for the hit instead of duplicating a multi-minute compile
        self.in_flight_key: Optional[str] = None
        self._idle = threading.Event()
        self._idle.set()
        self._thread = threading.Thread(
            target=self._run, daemon=True, name="speculative-compile"
        )
        self._thread.start()

    def submit(self, tasks: Sequence[CompileTask]):
        """Replace the pending candidates with a fresh prediction."""
        with self._cond:
            self._tasks.clear()
            self._tasks.extend(tasks)
            self._spent = 0.0
            self._gen += 1
            if tasks:
                self._idle.clear()
            self._cond.notify_all()

    def wait_idle(self, timeout: float = 60.0) -> bool:
        """Block until the queue drains (tests / resize barriers)."""
        return self._idle.wait(timeout)

    def _run(self):
        while True:
            with self._cond:
                while not self._closed and not self._tasks:
                    self._idle.set()
                    self._cond.wait()
                if self._closed:
                    self._idle.set()
                    return
                task = self._tasks.popleft()
                gen = self._gen
            if self._pause_fn is not None and self._pause_fn():
                # staging window: put the task back and doze — compiling
                # now would contend the drain's host cores. Requeue only
                # if no newer submit replaced the prediction meanwhile
                # (a stale candidate must not resurrect into the fresh
                # queue and burn its budget)
                with self._cond:
                    if self._gen == gen:
                        self._tasks.appendleft(task)
                time.sleep(self._poll_s)
                continue
            if self.cache.peek(task.key):
                continue
            if self._spent >= self._budget_s:
                self.dropped += 1
                logger.info(
                    f"speculative compile budget spent "
                    f"({self._spent:.1f}s); dropping {task.label}"
                )
                continue
            t0 = time.perf_counter()
            self.in_flight_key = task.key
            try:
                _, hit = self.cache.get_or_compile(task.key, task.build)
                if not hit:
                    self.compiled += 1
                    logger.info(
                        f"speculatively compiled {task.label} in "
                        f"{time.perf_counter() - t0:.2f}s"
                    )
            except Exception as e:
                # a candidate that cannot compile must not kill the
                # thread — the real resize will surface the error
                self.errors += 1
                logger.warning(
                    f"speculative compile of {task.label} failed: {e!r}"
                )
            finally:
                self.in_flight_key = None
            with self._cond:
                self._spent += time.perf_counter() - t0

    def close(self):
        with self._cond:
            self._closed = True
            self._tasks.clear()
            self._cond.notify_all()
        self._thread.join(timeout=5.0)
