"""Training-process side of Flash Checkpoint.

Parity: ``CheckpointEngine`` engine.py:131 —
``save_state_dict_to_memory`` (engine.py:284) stages the state into shm
under a non-blocking shard lock (if the agent is still persisting the
previous step, this save is *skipped*, never blocked on), then notifies
the agent saver through the event queue. ``get_state_dict_from_memory``
(engine.py:315) restores straight from shm after a restart.

TPU-native: the "state dict" is any JAX pytree; sharded ``jax.Array``
leaves are staged as per-host shard records with global indices
(``sharding.host_shard_records``), with async D2H overlapping the copies.
"""

from __future__ import annotations

import collections
import os
import threading
import time
import zlib
from typing import Any, Dict, Optional, Tuple

import numpy as np

from dlrover_tpu.common import faults
from dlrover_tpu.common.log import default_logger as logger
from dlrover_tpu.common.multi_process import (
    SharedLock,
    SharedQueue,
    server_exists,
)
from dlrover_tpu.common.storage import CheckpointStorage, PosixDiskStorage
from dlrover_tpu.ckpt import saver as saver_mod
from dlrover_tpu.ckpt.saver import SaveEvent
from dlrover_tpu.ckpt.sharding import (
    ShardRecord,
    host_shard_index_set,
    host_shard_plan,
    host_shard_records,
    restore_state,
)
from dlrover_tpu.ckpt.shm_handler import ShmHandler
from dlrover_tpu.obs.trace import TimedSpan, span


def _env_int(name: str, default: int) -> int:
    try:
        return int(os.getenv(name, default))
    except ValueError:
        return default


def _overlaps(a, b) -> bool:
    """Two index tuples ((lo,hi),...) intersect."""
    if len(a) != len(b):
        return False
    return all(max(alo, blo) < min(ahi, bhi) for (alo, ahi), (blo, bhi) in zip(a, b)) if a else True


def _ready(state: Any) -> Any:
    """Block until every restored leaf is on its device: the transfer
    is part of the restore, whoever times it."""
    import jax

    return jax.block_until_ready(state)


class ChunkedStager:
    """Incremental device→shm staging of one checkpoint.

    ``save_to_memory`` drains the whole state in one go — either a
    synchronous block on the train loop or a background thread that
    forbids donation for its whole lifetime. The chunked stager instead
    interleaves fixed-size chunks *between* train steps: the trainer
    calls ``advance(budget_s)`` once per step (bounded critical-path
    cost, default a few ms), and ``commit()`` is the only barrier — it
    drains what is left, publishes the shm metadata and notifies the
    agent saver. Until commit the metadata stays invalid, so a
    concurrent restore can never observe a half-staged step (the same
    crash-safe ordering ``ShmHandler.save_records`` uses).

    D2H is pipelined one chunk ahead (``copy_to_host_async`` on chunk
    N+1 while chunk N memcpys into shm). State buffers are read across
    many steps, so the train loop must not donate them while
    ``CheckpointEngine.staging_in_flight()`` is True — the trainer's
    donation-aware stepping handles this.

    Recovery-window tradeoff: like every shm save, ``begin`` invalidates
    the PREVIOUS in-memory checkpoint before the first byte moves, and
    here the invalid window spans the whole multi-step drain, not one
    blocking memcpy. A crash inside that window restores from the last
    *committed* storage step instead of shm. Callers who cannot afford
    the longer window (very long drains between rare disk commits)
    should keep ``save_to_memory`` for some cadence or shorten the
    drain via a bigger per-step budget.
    """

    def __init__(
        self,
        engine: "CheckpointEngine",
        step: int,
        state: Any,
        checkpoint_dir: str,
        sync: bool,
        chunk_bytes: int,
        priority=None,
        stripe_min_bytes: Optional[int] = None,
    ):
        self._engine = engine
        self.step = step
        self.checkpoint_dir = checkpoint_dir
        self._sync = sync
        self._chunk_bytes = max(int(chunk_bytes), 1 << 10)
        # host-link arbitration (parallel/transfer_sched.py): each
        # chunk's write rides one grant of the shared host link, so
        # checkpoint staging interleaves with embedding spills by
        # priority instead of queueing blindly. BACKGROUND by default;
        # the eviction emergency save passes EMERGENCY and preempts
        # background holders at their next chunk boundary. The arbiter
        # reorders transfers, never contents.
        from dlrover_tpu.parallel import transfer_sched

        self._priority = (
            transfer_sched.Priority.BACKGROUND
            if priority is None
            else priority
        )
        self._stream = transfer_sched.get_arbiter().register(
            "ckpt_stage",
            transfer_sched.Priority.BACKGROUND,
            direction="d2h",
        )
        # standing demand hint while this drain is live (the
        # dry-runner's aggregate host-leg pricing)
        self._stream.demand_bytes_per_step = self._chunk_bytes
        # multi-rail striping: a write group at least this large is
        # split across every admitted rail (host_d2h + the DCN peer
        # path) with per-chunk grants and crc32_combine-folded digests
        # — byte-identical to the single-rail path. Below the
        # threshold (and with fewer than two admitted rails) the exact
        # PR-14 single-grant path runs unchanged.
        self._stripe_min_bytes = (
            transfer_sched.DEFAULT_STRIPE_MIN_BYTES
            if stripe_min_bytes is None
            else max(int(stripe_min_bytes), 1)
        )
        self._striper = transfer_sched.StripedTransfer(
            self._stream.arbiter,
            name="ckpt_stage",
            direction="d2h",
            priority=self._priority,
            chunk_bytes=max(self._chunk_bytes // 4, 1 << 16),
            ignore_window=True,
        )
        # the plan holds live references to every device shard: the
        # buffers stay alive (and unmutated — jax.Array is immutable)
        # until the drain finishes, whatever the caller does to `state`
        with span("ckpt_begin_plan"):
            self._plan = host_shard_plan(state)
            self._metas = ShmHandler.layout_records(
                [rec for rec, _ in self._plan]
            )
        self.total_bytes = sum(m.nbytes for m in self._metas)
        self._staged_bytes = 0
        self.chunks_written = 0
        self._cursor = 0  # plan index
        self._elem_off = 0  # element offset within the current record
        # running crc32 per record index, folded chunk-by-chunk as the
        # bytes are written (writes are in offset order per record, so
        # the incremental crc equals the whole-record crc); published
        # with the metas at commit for end-to-end shm integrity
        self._crcs: Dict[int, int] = {}
        # write groups whose D2H has been issued, oldest first; each a
        # list of (rec_idx, byte_offset, nbytes, producer)
        self._inflight: collections.deque = collections.deque()
        self._finished = False
        self._failed = False
        with span("ckpt_begin_shm"):
            self._engine._shm.begin_save(max(self.total_bytes, 1))

    # -- introspection -------------------------------------------------
    @property
    def backlog_bytes(self) -> int:
        return self.total_bytes - self._staged_bytes

    @property
    def done(self) -> bool:
        """Every byte staged (commit may still be pending)."""
        return self._cursor >= len(self._plan) and not self._inflight

    @property
    def finished(self) -> bool:
        """Committed or aborted — the engine's lock is out of our hands."""
        return self._finished

    # small write groups are never deferred on readiness: their D2H
    # completes in microseconds and deferring would crawl the drain at
    # one group per step
    _DEFER_MIN_BYTES = 1 << 20

    # Write groups kept issued ahead of the one being consumed. The
    # train loop calls advance() with a step in flight on the device,
    # and device work runs in stream order: the copies (and slices) of a
    # group issued now start only when that step ends. Two ahead, the
    # group consumed in a step was issued two steps ago, so its copy has
    # had a whole device step to land and a budgeted advance() finds one
    # group ready on EVERY step (one ahead, every other step: the drain
    # would take twice the steps).
    _GROUPS_AHEAD = 2

    # -- chunk pipeline ------------------------------------------------
    def _start_next(self):
        """Build the next write group and start its D2H. A group is a
        list of ``(rec_idx, byte_offset, nbytes, source)`` members
        totalling at most ``chunk_bytes``: consecutive small records
        coalesce into one group (a pytree of many tiny leaves must not
        become one chunk per leaf), a record larger than ``chunk_bytes``
        is split into equal-size windows (consistent slice shapes, so the
        eager slice op compiles once). Returns None at plan's end.

        A whole record is copied to the host as it is, with no
        computation on the device (its bytes in C order are the same in
        any shape). The train loop calls this with a step in flight, and
        the runtime lets 32 computations be in flight at once: the 32nd
        eager op queued behind a running step blocks the host until that
        step ends (a group of 32 small leaves stalled a chunk step for
        110 of 115 ms on the v5e, PERF.md PR 26). Copies to the host do
        not count against that limit."""
        import jax

        group = []
        budget = self._chunk_bytes
        while self._cursor < len(self._plan) and budget > 0:
            idx = self._cursor
            rec, src = self._plan[self._cursor]
            meta = self._metas[self._cursor]
            if isinstance(src, np.ndarray):
                if src.nbytes > budget and group:
                    break
                group.append((idx, meta.offset, src.nbytes, src))
                budget -= src.nbytes
                self._cursor += 1
                continue
            itemsize = np.dtype(rec.dtype).itemsize
            n_elems = meta.nbytes // itemsize
            if self._elem_off >= n_elems:
                self._cursor += 1
                self._elem_off = 0
                continue
            if meta.nbytes <= budget and self._elem_off == 0:
                # whole small record joins the group, no slicing
                dev = src
                lo, hi = 0, n_elems
            elif group:
                break  # the big record starts its own group next call
            else:
                per_chunk = max(1, self._chunk_bytes // itemsize)
                lo = self._elem_off
                hi = min(lo + per_chunk, n_elems)
                dev = jax.numpy.ravel(src)[lo:hi]
            self._elem_off = hi
            if self._elem_off >= n_elems:
                self._cursor += 1
                self._elem_off = 0
            try:
                dev.copy_to_host_async()
            except Exception:
                pass
            group.append(
                (
                    idx,
                    meta.offset + lo * itemsize,
                    (hi - lo) * itemsize,
                    dev,
                )
            )
            budget -= (hi - lo) * itemsize
        return group or None

    @classmethod
    def _may_defer(cls, group) -> bool:
        """True when a budgeted advance should leave this group to ride
        the async stream instead of blocking on its transfer."""
        total = sum(n for _, _, n, _ in group)
        if total < cls._DEFER_MIN_BYTES:
            return False
        for _, _, _, src in group:
            if isinstance(src, np.ndarray):
                continue
            try:
                if not src.is_ready():
                    return True
            except AttributeError:
                return False
        return False

    def _group_stripes(self, group) -> bool:
        """True when this write group takes the multi-rail striped
        path (single big member, above the stripe floor, at least two
        admitted rails). advance() uses the same predicate to SKIP the
        outer stream grant for striped groups: the stripe's per-chunk
        rail grants are the only arbitration, so the striper can never
        deadlock against its own stream's held grant."""
        return (
            len(group) == 1
            and group[0][2] >= self._stripe_min_bytes
            and len(self._striper.rails()) >= 2
        )

    def _issue_ahead(self) -> None:
        """Start the D2H of further write groups until ``_GROUPS_AHEAD``
        are issued besides the one at the head (or the plan ends)."""
        with span("stage_d2h_issue"):
            while len(self._inflight) <= self._GROUPS_AHEAD:
                group = self._start_next()
                if group is None:
                    break
                self._inflight.append(group)

    def _write_one(self) -> int:
        """Consume the oldest issued group (start the D2H of the groups
        behind it first so the transfers overlap this memcpy). Returns
        bytes written."""
        self._issue_ahead()
        if not self._inflight:
            return 0
        group = self._inflight.popleft()
        stripes = self._group_stripes(group)
        written = 0
        shm = self._engine._shm
        for idx, offset, nbytes, src in group:
            # the first touch: blocks until the group's device→host
            # copy (started ``_GROUPS_AHEAD`` groups ahead) has landed
            with span("stage_d2h_wait"):
                data = (
                    src if isinstance(src, np.ndarray)
                    else np.asarray(src)
                )
            # fold the chunk into the record's running crc BEFORE
            # write_chunk (whose ckpt.shm_stage fault point corrupts):
            # per-record writes are in offset order, so the incremental
            # crc equals the whole-record crc published at commit
            flat = np.ascontiguousarray(data).reshape(-1).view(np.uint8)
            if stripes:
                # split the group across rails: disjoint shm offsets,
                # so concurrent chunk memcpys never overlap; the
                # striper's combined crc is bitwise the crc of `flat`,
                # folded into the record's running digest exactly like
                # the single-rail incremental fold
                from dlrover_tpu.parallel import transfer_sched

                # crc and copy run together, chunk by chunk per rail
                with span("stage_shm_copy", striped=True):
                    rep = self._striper.run(
                        lambda rail, off, ln, _o=offset, _f=flat: (
                            shm.write_chunk(_o + off, _f[off:off + ln])
                        ),
                        payload=flat,
                    )
                self._crcs[idx] = transfer_sched.crc32_combine(
                    self._crcs.get(idx, 0), rep.crc32, flat.nbytes
                )
            else:
                with span("stage_crc"):
                    self._crcs[idx] = zlib.crc32(
                        flat, self._crcs.get(idx, 0)
                    )
                with span("stage_shm_copy"):
                    shm.write_chunk(offset, data)
            written += nbytes
        self._staged_bytes += written
        self.chunks_written += 1
        return written

    def advance(
        self,
        budget_s: Optional[float] = None,
        stats=None,
    ) -> int:
        """Stage chunks until ``budget_s`` of wall time is spent (None =
        drain everything). A budgeted call never blocks on a D2H that
        has not landed yet — the chunk stays in flight and the next
        step's call consumes it, so the per-step cost is the shm memcpy
        of chunks whose transfer already overlapped compute. Bounded
        overshoot: at most one chunk past the budget. Returns bytes
        staged by this call."""
        if self._finished:
            return 0
        t0 = time.perf_counter()
        copied = 0
        chunks0 = self.chunks_written
        # Groups issued by earlier calls. A budgeted call touches no
        # other: the copy of a group it has just issued queues behind
        # whatever the device is running and cannot have landed, however
        # small the group and even where its sources read as ready (a
        # whole record is the state's own leaf), so touching it would
        # wait out the step in flight.
        older = len(self._inflight)
        try:
            with span("ckpt_stage", step=self.step):
                while not self.done:
                    if not self._inflight:
                        self._issue_ahead()
                        if not self._inflight:
                            break
                    head = self._inflight[0]
                    if budget_s is not None and (
                        older <= 0 or self._may_defer(head)
                    ):
                        break  # transfer still riding the async stream
                    older -= 1
                    # one link grant per chunk: higher-priority traffic
                    # (emergency ckpt, spill backpressure) interleaves
                    # between chunks instead of waiting out the drain
                    # ignore_window: this advance IS the inter-step
                    # host section's own budgeted work on the train
                    # thread — the window gate must defer background
                    # THREADS to it, never it to itself
                    nbytes = sum(m[2] for m in head)
                    if self._group_stripes(head):
                        # striped group: the per-chunk rail grants
                        # inside the striper are the only arbitration
                        # (holding the stream grant here would deadlock
                        # the stripe's own host_d2h chunk acquires)
                        copied += self._write_one()
                        grant = None
                    else:
                        with span("stage_grant_wait"):
                            grant = self._stream.transfer(
                                nbytes,
                                priority=self._priority,
                                ignore_window=True,
                            )
                        with grant:
                            copied += self._write_one()
                    if (
                        budget_s is not None
                        and grant is not None
                        and grant.should_yield()
                    ):
                        break  # yield the link to the preemptor
                    if (
                        budget_s is not None
                        and time.perf_counter() - t0 >= budget_s
                    ):
                        break
        except BaseException:
            self.abort()
            raise
        if stats is not None:
            stats.stage_chunks += self.chunks_written - chunks0
            stats.stage_bytes += copied
            stats.stage_backlog_bytes = self.backlog_bytes
            stats.stage_block_s += time.perf_counter() - t0
        return copied

    # -- barrier -------------------------------------------------------
    def commit(self, stats=None) -> bool:
        """The commit barrier: drain the backlog, publish metadata,
        notify the agent saver. After this the shm checkpoint is
        visible and the saver owns the shard lock."""
        if self._finished:
            return not self._failed
        try:
            with span("ckpt_commit", step=self.step):
                self.advance(budget_s=None, stats=stats)
                for i, m in enumerate(self._metas):
                    m.crc32 = self._crcs.get(i)
                self._engine._shm.commit_save(
                    self.step,
                    self._metas,
                    {
                        "checkpoint_dir": self.checkpoint_dir,
                        "global_shard_id": self._engine.global_shard_id,
                        "global_shard_num": self._engine.global_shard_num,
                    },
                )
        except BaseException as e:
            self.abort()
            logger.error(
                f"step {self.step}: chunked staging commit failed: {e!r}"
            )
            raise
        self._finished = True
        self._plan = []
        self._stream.demand_bytes_per_step = 0
        if stats is not None:
            stats.stage_commits += 1
        self._engine._queue.put(
            SaveEvent(
                step=self.step,
                checkpoint_dir=self.checkpoint_dir,
                local_rank=self._engine.local_rank,
                global_shard_id=self._engine.global_shard_id,
                global_shard_num=self._engine.global_shard_num,
                sync=self._sync,
            )
        )
        return True

    def abort(self):
        """Give up: metadata stays invalid (begin_save cleared it), the
        shard lock goes back so future saves are not starved."""
        if self._finished:
            return
        self._finished = True
        self._failed = True
        self._plan = []
        self._inflight.clear()
        self._stream.demand_bytes_per_step = 0
        # force_release, not release: abort may run from a thread other
        # than the acquirer's (same rationale as _stage_and_notify)
        self._engine._lock.force_release()


class _SyncFallbackStager:
    """No agent (plain ``python train.py``): chunked staging has no shm
    to stage into, so the commit barrier just runs the synchronous
    storage save. advance() is free; the caller's loop stays uniform."""

    def __init__(self, engine, step, state, checkpoint_dir):
        self._engine = engine
        self.step = step
        self._state = state
        self.checkpoint_dir = checkpoint_dir
        self.total_bytes = 0
        self.chunks_written = 0
        self.backlog_bytes = 0
        self.done = True
        self.finished = False

    def advance(self, budget_s=None, stats=None) -> int:
        return 0

    def commit(self, stats=None) -> bool:
        if self.finished:
            return True
        self.finished = True
        ok = self._engine._save_sync(
            self.step, self._state, self.checkpoint_dir
        )
        self._state = None
        if stats is not None:
            stats.stage_commits += 1
        return ok

    def abort(self):
        self.finished = True
        self._state = None


class CheckpointEngine:
    """One per training process. Talks to the per-host agent saver when one
    is serving the IPC endpoints; otherwise falls back to synchronous
    storage writes (plain ``python train.py`` without the launcher)."""

    def __init__(self, storage: Optional[CheckpointStorage] = None):
        self.local_rank = _env_int("DLROVER_TPU_LOCAL_RANK", 0)
        self.global_shard_id = _env_int("DLROVER_TPU_PROCESS_ID", 0)
        self.global_shard_num = _env_int("DLROVER_TPU_NUM_PROCESSES", 1)
        self.storage = storage or PosixDiskStorage()
        self._agent_mode = server_exists(saver_mod.CKPT_EVENT_QUEUE)
        self._shm: Optional[ShmHandler] = None
        self._queue: Optional[SharedQueue] = None
        self._lock: Optional[SharedLock] = None
        self._staging_threads: list = []
        self._active_stager = None
        # phases of the last ``load`` (None until one ran)
        self.last_restore: Optional[Dict[str, float]] = None
        # the shard lock's side of every memory save that fell due
        # (``_take_shard_lock``): seconds asking for it, the saves
        # skipped because the saver held it, and how many of those the
        # lock's mirror answered without a request to the agent
        self.save_begin: Dict[str, float] = {
            "begin_lock_s": 0.0, "save_skips": 0, "lock_local_answers": 0,
        }
        if self._agent_mode:
            self._shm = ShmHandler(self.local_rank, create=False)
            self._queue = SharedQueue(saver_mod.CKPT_EVENT_QUEUE)
            self._lock = SharedLock(
                saver_mod.shard_lock_name(self.local_rank)
            )

    # ------------------------------------------------------------------
    # save
    # ------------------------------------------------------------------
    def save_to_memory(
        self,
        step: int,
        state: Any,
        checkpoint_dir: str,
        sync: bool = False,
        block: bool = True,
    ) -> bool:
        """Stage ``state`` into shm and notify the agent. Returns False when
        skipped because the saver still holds the shard lock.

        ``block=False`` runs the device→host copy + shm staging on a
        background thread and returns immediately — safe because
        ``jax.Array`` leaves are immutable (the train loop's next step
        builds new arrays). Do NOT combine with a train step that donates
        its state buffers: donation invalidates the arrays the staging
        thread is still reading.
        """
        if not self._agent_mode:
            return self._save_sync(step, state, checkpoint_dir)
        assert self._lock and self._shm and self._queue
        # Lock-handoff protocol (parity: engine.py:284 + ckpt_saver.py:534):
        # we take the shard lock here and the *saver* force-releases it after
        # persisting, so shm can never be overwritten before it is safe on
        # storage — a save issued while the saver is busy is skipped, never
        # blocked on.
        if not self._take_shard_lock():
            logger.warning(
                f"step {step}: saver busy persisting a previous checkpoint; "
                f"skipping this save"
            )
            return False
        if block:
            self._stage_and_notify(step, state, checkpoint_dir, sync)
        else:
            t = threading.Thread(
                target=self._stage_and_notify,
                args=(step, state, checkpoint_dir, sync),
                name=f"ckpt-stage-{step}",
                daemon=True,
            )
            self._staging_threads = [
                th for th in self._staging_threads if th.is_alive()
            ] + [t]
            t.start()
        return True

    def begin_chunked_save(
        self,
        step: int,
        state: Any,
        checkpoint_dir: str,
        sync: bool = False,
        chunk_bytes: int = 64 << 20,
        priority=None,
        stripe_min_bytes: Optional[int] = None,
    ):
        """Chunked variant of ``save_to_memory``: returns a stager whose
        ``advance(budget_s)`` the train loop calls between steps and
        whose ``commit()`` is the barrier, or None when the saver still
        holds the shard lock (save skipped, never blocked on — same
        contract as ``save_to_memory``). Without an agent the returned
        stager falls back to a synchronous storage save at commit.
        ``priority`` is the host-link arbitration class
        (``transfer_sched.Priority``; the eviction drain passes
        EMERGENCY so its chunks preempt background spills).
        ``stripe_min_bytes`` is the multi-rail stripe floor: write
        groups at least this large split across every admitted rail
        (default ``transfer_sched.DEFAULT_STRIPE_MIN_BYTES``)."""
        if self._agent_mode:
            assert self._lock and self._shm and self._queue
            if not self._take_shard_lock():
                logger.warning(
                    f"step {step}: saver busy persisting a previous "
                    f"checkpoint; skipping this chunked save"
                )
                return None
            try:
                stager = ChunkedStager(
                    self, step, state, checkpoint_dir, sync,
                    chunk_bytes, priority=priority,
                    stripe_min_bytes=stripe_min_bytes,
                )
            except BaseException:
                self._lock.force_release()
                raise
        else:
            stager = _SyncFallbackStager(
                self, step, state, checkpoint_dir
            )
        self._active_stager = stager
        return stager

    def _take_shard_lock(self) -> bool:
        """The non-blocking acquire a memory save begins with, whole under
        the ``ckpt_begin_lock`` span. While the saver holds the lock the
        answer comes from the lock's mirror (``SharedLock``), not from
        the agent, whose interpreter is busy persisting just then."""
        rec = self.save_begin
        with TimedSpan(rec, "begin_lock_s", name="ckpt_begin_lock"):
            got = self._lock.acquire(blocking=False)
        if not got:
            rec["save_skips"] += 1
            rec["lock_local_answers"] = self._lock.local_answers
        return got

    def staging_in_flight(self) -> bool:
        """True while ANY staging still reads state buffers — a
        ``block=False`` background drain or an uncommitted chunked
        stager. The train loop must not run a state-donating step while
        this holds (donation would invalidate the buffers mid-read)."""
        self._staging_threads = [
            t for t in self._staging_threads if t.is_alive()
        ]
        if self._staging_threads:
            return True
        st = self._active_stager
        if st is not None and st.finished:
            self._active_stager = st = None
        return st is not None

    def wait_staging(self, timeout: float = 60.0):
        """Join in-flight ``block=False`` staging threads. Call before
        process exit: a daemon thread doing D2H against a runtime that is
        tearing down can abort the process."""
        deadline = time.time() + timeout
        for t in self._staging_threads:
            t.join(timeout=max(0.0, deadline - time.time()))
        self._staging_threads = [
            t for t in self._staging_threads if t.is_alive()
        ]

    def close(self, timeout: float = 60.0):
        """Drain staging threads and drop IPC clients."""
        if (
            self._active_stager is not None
            and not self._active_stager.finished
        ):
            # an uncommitted chunked stage dies with the process — abort
            # so the shard lock is not leaked (metadata is already
            # invalid, so no reader can see the partial bytes)
            logger.warning(
                f"closing engine with an uncommitted chunked stage at "
                f"step {self._active_stager.step}; aborting it"
            )
            self._active_stager.abort()
        self._active_stager = None
        self.wait_staging(timeout)
        if self._staging_threads:
            # a wedged thread is about to race the shm close below — make
            # the broken shutdown visible instead of identical to a clean one
            logger.warning(
                "closing engine with staging threads still alive: "
                f"{[t.name for t in self._staging_threads]}"
            )
        for attr in ("_queue", "_lock"):
            obj = getattr(self, attr)
            if obj is not None:
                try:
                    obj.close()
                except OSError as e:
                    # teardown race (saver side already gone) is expected;
                    # anything else should surface
                    logger.warning(f"{attr} close failed: {e!r}")
        if self._shm is not None:
            try:
                self._shm.close(unlink=False)
            except (OSError, BufferError) as e:
                # BufferError = a wedged staging thread still holds a view
                # into the shm buffer (the case warned about above)
                logger.warning(f"shm close failed: {e!r}")

    def _stage_and_notify(
        self, step: int, state: Any, checkpoint_dir: str, sync: bool
    ):
        try:
            t0 = time.time()
            with span("ckpt_stage", step=step):
                records = host_shard_records(state)
                extra = {
                    "checkpoint_dir": checkpoint_dir,
                    "global_shard_id": self.global_shard_id,
                    "global_shard_num": self.global_shard_num,
                }
                self._shm.save_records(step, records, extra)
            logger.info(
                f"step {step}: staged {len(records)} shard records to shm "
                f"in {time.time() - t0:.3f}s"
            )
        except BaseException as e:
            # force_release, not release: under block=False this runs on the
            # staging thread, whose owner id differs from the acquirer's, so
            # an owner-checked release would silently leak the lock and end
            # checkpointing for the rest of the job
            self._lock.force_release()
            logger.error(f"step {step}: shm staging failed: {e!r}")
            raise
        self._queue.put(
            SaveEvent(
                step=step,
                checkpoint_dir=checkpoint_dir,
                local_rank=self.local_rank,
                global_shard_id=self.global_shard_id,
                global_shard_num=self.global_shard_num,
                sync=sync,
            )
        )

    def save_to_storage(
        self,
        step: int,
        state: Any,
        checkpoint_dir: str,
        timeout: float = 600.0,
    ) -> bool:
        """Stage to shm, ask the agent to persist this step, and wait until
        the commit tracker names it (the reference's ``StorageType.DISK``
        contract: returning True means the checkpoint is on storage)."""
        if not self.save_to_memory(step, state, checkpoint_dir, sync=True):
            return False
        if not self._agent_mode:
            return True  # _save_sync already committed
        deadline = time.time() + timeout
        while time.time() < deadline:
            if self.latest_step(checkpoint_dir) >= step:
                return True
            time.sleep(0.2)
        logger.error(f"step {step}: storage persist not committed in time")
        return False

    def _save_sync(self, step: int, state: Any, checkpoint_dir: str) -> bool:
        """No agent: write this process's shard directly to storage through
        the same payload/done/commit helpers the saver uses, so files stay
        interchangeable. A storage failure (ENOSPC, transient FS error)
        returns False instead of killing the train loop — the next save
        cadence retries; the last verified step stays restorable."""
        try:
            with span("ckpt_persist", step=step):
                faults.fire("ckpt.persist")
                records = host_shard_records(state)
                self.storage.safe_makedirs(
                    os.path.join(
                        saver_mod.step_dir(checkpoint_dir, step),
                        saver_mod.DONE_DIR,
                    )
                )
                payload = saver_mod.build_shard_payload(
                    step, self.global_shard_id, self.global_shard_num,
                    records, {},
                )
                saver_mod.write_shard_and_done(
                    self.storage, checkpoint_dir, step, payload
                )
                if self.global_shard_id == 0:
                    return saver_mod.commit_checkpoint(
                        self.storage, checkpoint_dir, step,
                        self.global_shard_num,
                    )
                return True
        except OSError as e:
            logger.error(f"step {step}: sync persist failed: {e!r}")
            saver_mod._metric_counter(
                "dlrover_ckpt_persist_failures_total",
                "failed checkpoint persist attempts",
            ).inc()
            return False

    # ------------------------------------------------------------------
    # load
    # ------------------------------------------------------------------
    def latest_step(self, checkpoint_dir: str) -> int:
        return saver_mod.read_tracker(self.storage, checkpoint_dir)

    def latest_verified_step(
        self, checkpoint_dir: str, repair: Optional[bool] = None
    ) -> int:
        """Newest committed step whose shards pass integrity
        verification. ``repair`` (default: only global shard 0, so one
        process per job mutates the store) quarantines corrupt step
        dirs and rolls the tracker back; the repairing rank runs the
        deep read+crc pass, the others the cheap completeness/length
        check (N ranks each reading every shard's full bytes just to
        pick the restore step would swamp restart I/O). Known tradeoff:
        the repairing rank reads the verified step once to checksum it
        and again to restore — 2x one checkpoint read on the rare
        restart path, accepted for the simplicity of keeping
        verification separate from the sliced ``.idx``-driven load."""
        if repair is None:
            repair = self.global_shard_id == 0
        return saver_mod.resolve_verified_step(
            self.storage, checkpoint_dir, repair=repair, deep=repair
        )

    def load(
        self, target: Any, checkpoint_dir: str, prefer_memory: bool = True
    ) -> Tuple[int, Optional[Any]]:
        """Restore ``target``-shaped state. Prefers shm when *every*
        process holds the same usable step at least as new as the committed
        one (fast elastic-restart path, engine.py:315), else reads the
        newest *verified* committed step from storage. ``prefer_memory=
        False`` skips the shm proposal entirely — the full-loss path
        (replacement node, no surviving agent shm).

        Both sources are integrity-checked: the shm proposal recomputes
        each record's crc32 against the writer's published checksum (a
        corrupt segment downgrades to the storage path), and the storage
        step comes from ``latest_verified_step`` — a torn/bit-flipped/
        partial newest step is quarantined and restore falls back to the
        newest older step that verifies, never silently restoring
        corrupt bytes.

        The cross-process agreement mirrors the reference's
        ``verify_all_rank_step_consistent`` (engine.py:318): because
        ``save_to_memory`` skips per-host when the shard lock is busy,
        hosts can hold *different* shm steps after an elastic restart —
        restoring them as-is would silently diverge the replicas. Every
        process must call ``load`` (it's the restart path), so the
        allgather below cannot deadlock.

        The storage step is cross-rank agreed too (fleet MINIMUM): only
        the repairing rank deep-verifies, so after it quarantines a
        length-preserving bit flip and rolls the tracker back, the other
        ranks' shallow check may still name the corrupt newer step —
        without the min they would restore different steps (or read a
        step dir mid-quarantine-rename)."""
        # the phases of this load, timed where they happen: the trainer
        # folds the record into ``PipelineStats`` (``restore_*``), and
        # each phase is a span named like its field less the ``_s``
        rec = self.last_restore = {
            "restore_source": 0, "restore_bytes": 0,
            "restore_storage_verify_s": 0.0, "restore_agree_s": 0.0,
            "restore_lock_wait_s": 0.0, "restore_shm_verify_s": 0.0,
            "restore_storage_read_s": 0.0, "restore_h2d_s": 0.0,
        }
        with TimedSpan(rec, "restore_storage_verify_s"):
            verified = self.latest_verified_step(checkpoint_dir)
        with TimedSpan(rec, "restore_agree_s"):
            committed = self._agree_committed(verified)
        # propose this host's usable shm step (-1 = none). The shard lock
        # guards against reading shm mid-rewrite by an in-flight
        # block=False staging thread or the persisting saver; a lock
        # timeout just downgrades the proposal to -1.
        candidate = -1
        records = []
        got_lock = False
        if prefer_memory and self._agent_mode and self._shm is not None:
            with TimedSpan(rec, "restore_lock_wait_s"):
                try:
                    got_lock = self._lock.acquire(blocking=True)
                except (TimeoutError, RuntimeError):
                    got_lock = False
            if got_lock:
                try:
                    # zero-copy views: consumed (packed into transfer
                    # buffers) inside restore_state below, all before the
                    # lock is released in the finally. verify=True: a
                    # corrupt segment (bit rot, partial staging) raises
                    # ValueError and the proposal downgrades to -1
                    with TimedSpan(rec, "restore_shm_verify_s"):
                        shm_step, records, _ = self._shm.load_records(
                            copy=False, verify=True
                        )
                    if shm_step >= committed and self._shm_covers(
                        records, target
                    ):
                        candidate = shm_step
                except (LookupError, ValueError):
                    candidate = -1
        by_path: Dict[str, list] = {}
        try:
            # every process reaches this collective exactly once per load,
            # whatever its agent/lock state — a host that failed to read
            # shm proposes -1 rather than skipping the allgather (which
            # would deadlock the others)
            with TimedSpan(rec, "restore_agree_s"):
                agreed = self._all_processes_agree(candidate)
            if agreed and candidate >= 0:
                for r in records:
                    by_path.setdefault(r.path, []).append(r)
                try:
                    with TimedSpan(rec, "restore_h2d_s"):
                        state = _ready(restore_state(
                            target, lambda p: by_path.get(p, [])
                        ))
                    rec["restore_source"] = 1
                    rec["restore_bytes"] = sum(
                        int(r.data.nbytes) for r in records
                    )
                    logger.info(f"restored step {candidate} from memory")
                    return candidate, state
                except (LookupError, ValueError) as e:
                    logger.warning(
                        f"shm restore of step {candidate} failed ({e!r}); "
                        f"falling back to storage"
                    )
            elif candidate >= 0:
                logger.warning(
                    f"shm holds step {candidate} but processes disagree; "
                    f"falling back to committed step {committed}"
                )
        finally:
            # records may hold zero-copy views into the shm segment
            # (load_records(copy=False)) — drop every reference BEFORE
            # releasing the lock, or a concurrent save that outgrows the
            # segment hits BufferError on shm.close() with live views
            records = []
            by_path.clear()
            if got_lock:
                self._lock.force_release()
        if committed < 0:
            return -1, None
        # a failed shm attempt's seconds stay in the record: they were
        # spent, and restore_source says which path gave the state
        rec["restore_source"] = 2
        return committed, self._load_from_storage(
            target, checkpoint_dir, committed
        )

    def _agree_committed(self, committed: int) -> int:
        """Fleet minimum of per-rank verified storage steps. The min is
        always a step the repairing rank verified deeply (its own value
        after any rollback), so every rank restores the same bytes."""
        try:
            import jax

            if jax.process_count() <= 1:
                return committed
            from jax.experimental import multihost_utils

            steps = multihost_utils.process_allgather(
                np.asarray([committed], np.int64)
            )
            agreed = int(np.min(steps))
            if agreed != committed:
                logger.warning(
                    f"verified storage step disagreement: local "
                    f"{committed}, fleet min {agreed}; using the min"
                )
            return agreed
        except Exception as e:
            logger.warning(
                f"storage step agreement check unavailable: {e!r}"
            )
            return committed

    def _all_processes_agree(self, candidate: int) -> bool:
        """True iff every JAX process proposes the same shm step. Uses a
        host allgather when ``jax.distributed`` is up; single-process (or
        uninitialized) trivially agrees with itself."""
        try:
            import jax

            if jax.process_count() <= 1:
                return True
            import numpy as np
            from jax.experimental import multihost_utils

            steps = multihost_utils.process_allgather(
                np.asarray([candidate], np.int64)
            )
            return len({int(s) for s in np.ravel(steps)}) == 1
        except Exception as e:
            # no distributed runtime: be conservative only when we know
            # there are peers we could not reach
            logger.warning(f"shm step agreement check unavailable: {e!r}")
            return self.global_shard_num <= 1

    def _shm_covers(self, records, target) -> bool:
        """shm restore is only safe when this process's target shards match
        what this process staged (same world split)."""
        have = {(r.path, r.index) for r in records}
        return host_shard_index_set(target) <= have

    def _load_from_storage(
        self, target: Any, checkpoint_dir: str, step: int
    ) -> Any:
        sdir = saver_mod.step_dir(checkpoint_dir, step)
        files = [
            f for f in self.storage.listdir(sdir) if f.endswith(".ckpt")
        ]
        phases = self.last_restore if self.last_restore is not None else {}
        by_path: Dict[str, list] = {}
        with TimedSpan(phases, "restore_storage_read_s"):
            needed = self._filter_needed_shards(sdir, files, target)
            for fname in needed:
                payload = self.storage.read_state_dict(
                    os.path.join(sdir, fname)
                )
                for m in payload["records"]:
                    rec = ShardRecord(
                        path=m["path"],
                        global_shape=tuple(m["global_shape"]),
                        dtype=m["dtype"],
                        index=tuple(tuple(i) for i in m["index"]),
                        data=m["data"],
                    )
                    by_path.setdefault(rec.path, []).append(rec)
                    phases["restore_bytes"] = phases.get(
                        "restore_bytes", 0
                    ) + int(getattr(rec.data, "nbytes", 0))
        with TimedSpan(phases, "restore_h2d_s"):
            return _ready(
                restore_state(target, lambda p: by_path.get(p, []))
            )

    def _filter_needed_shards(self, sdir, files, target):
        """Use the .idx sidecars to read only shard files overlapping this
        host's slices of ``target`` (restart I/O stays O(local state), not
        O(global state) × hosts). Falls back to all files when any sidecar
        is missing."""
        wanted = host_shard_index_set(target)
        needed = []
        for fname in files:
            index = None
            try:
                index = self.storage.read_state_dict(
                    os.path.join(sdir, fname + ".idx")
                )
            except Exception:
                index = None
            if index is None:
                return files
            for m in index:
                ridx = tuple(tuple(i) for i in m["index"])
                if any(
                    p == m["path"] and _overlaps(ridx, widx)
                    for p, widx in wanted
                ):
                    needed.append(fname)
                    break
        return needed
