"""Flash Checkpoint tests: real shm, real unix-socket IPC, real saver
threads (parity with reference test_ckpt_saver.py / ddp_checkpointer_test).
"""

import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.ckpt.engine import CheckpointEngine
from dlrover_tpu.ckpt.checkpointer import FlashCheckpointer, StorageType
from dlrover_tpu.ckpt.saver import (
    AsyncCheckpointSaver,
    TRACKER_FILE,
    shard_file,
)
from dlrover_tpu.ckpt.sharding import (
    ShardRecord,
    assemble_leaf,
    host_shard_records,
    restore_state,
)
from dlrover_tpu.ckpt.shm_handler import ShmHandler
from dlrover_tpu.common.multi_process import (
    _FREE,
    _HELD,
    _MIRROR,
    attach_shared_memory,
)


@pytest.fixture
def saver(tmp_path):
    AsyncCheckpointSaver.reset()
    s = AsyncCheckpointSaver.start_async_saving_ckpt(local_shard_num=1)
    yield s
    AsyncCheckpointSaver.reset()


def _sharded_state(mesh_axis="x"):
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    devs = jax.devices()
    mesh = Mesh(np.array(devs).reshape(len(devs)), (mesh_axis,))
    sharding = NamedSharding(mesh, P(mesh_axis))
    w = jax.device_put(jnp.arange(16.0).reshape(16), sharding)
    b = jnp.ones((3,))  # replicated
    return {"w": w, "b": b, "step": 7}


def _mirror_state(lock):
    """What a client that attaches now reads of ``lock``'s state."""
    shm = attach_shared_memory(lock._mirror_name)
    try:
        return _MIRROR.unpack_from(shm.buf, 0)[0]
    finally:
        shm.close()


def _begin(eng, how, step, ckpt_dir):
    """Ask ``eng`` for a memory save of ``step``; True when it began."""
    state = {"w": np.arange(8.0)}
    if how == "save_to_memory":
        return eng.save_to_memory(step, state, ckpt_dir)
    return eng.begin_chunked_save(step, state, ckpt_dir) is not None


class TestSkippedSaveAsksNobody:
    """A memory save that falls due while the saver holds the shard lock
    is skipped from the lock's mirror, whatever the agent is busy with."""

    @pytest.mark.parametrize("how", ["begin_chunked_save", "save_to_memory"])
    def test_skip_does_not_wait_for_a_stalled_agent(
        self, how, saver, tmp_path, monkeypatch
    ):
        lock = saver._shard_locks[0]
        answer = lock._do_acquire

        def stalled(blocking, owner):
            time.sleep(1.0)  # the agent's interpreter, mid-persist
            return answer(blocking, owner)

        monkeypatch.setattr(lock, "_do_acquire", stalled)
        eng = CheckpointEngine()
        # the saver holds the lock over a persist (the handoff's far end)
        assert answer(False, "saver")
        for skipped in (1, 2):
            t0 = time.perf_counter()
            assert _begin(eng, how, 10 * skipped, str(tmp_path)) is False
            assert time.perf_counter() - t0 < 0.05
            assert eng.save_begin["save_skips"] == skipped
            assert eng.save_begin["lock_local_answers"] == skipped
        assert lock.locked() and lock._owner == "saver"  # untouched
        # the persist ends: the next due save asks, waits its turn, begins
        assert lock.force_release()
        t0 = time.perf_counter()
        assert _begin(eng, how, 30, str(tmp_path)) is True
        assert time.perf_counter() - t0 >= 1.0
        assert lock.locked()
        assert eng.save_begin["save_skips"] == 2
        assert eng.save_begin["lock_local_answers"] == 2
        assert eng.save_begin["begin_lock_s"] >= 1.0
        eng.wait_staging()

    @pytest.mark.parametrize("how", ["begin_chunked_save", "save_to_memory"])
    def test_begin_lock_span_holds_the_whole_decision(
        self, how, saver, tmp_path
    ):
        from dlrover_tpu.obs.trace import get_tracer

        tracer = get_tracer()
        tracer.reset()
        eng = CheckpointEngine()
        assert _begin(eng, how, 1, str(tmp_path)) is True  # asked
        eng.wait_staging()
        assert _begin(eng, how, 2, str(tmp_path)) is False  # mirror
        spans = [r for r in tracer.drain(0)[0] if r[0] == "ckpt_begin_lock"]
        tracer.reset()
        assert len(spans) == 2
        seconds = sum(r[3] for r in spans) / 1e9
        assert eng.save_begin["begin_lock_s"] == pytest.approx(
            seconds, abs=2e-3
        )
        assert eng.save_begin["save_skips"] == 1


class TestShardRecords:
    def test_host_shard_records_covers_global(self):
        state = _sharded_state()
        recs = host_shard_records(state)
        paths = {r.path for r in recs}
        assert paths == {"w", "b", "step"}
        w_recs = [r for r in recs if r.path == "w"]
        covered = sum(r.nbytes for r in w_recs)
        assert covered == 16 * 4

    def test_assemble_roundtrip_any_resharding(self):
        # saved as 8 shards of 2; reassemble as 2 slices of 8
        recs = [
            ShardRecord(
                path="w",
                global_shape=(16,),
                dtype="float32",
                index=((i * 2, i * 2 + 2),),
                data=np.arange(i * 2, i * 2 + 2, dtype=np.float32),
            )
            for i in range(8)
        ]
        out = assemble_leaf((16,), "float32", ((4, 12),), recs)
        np.testing.assert_array_equal(
            out, np.arange(4, 12, dtype=np.float32)
        )

    def test_assemble_detects_holes(self):
        recs = [
            ShardRecord(
                path="w",
                global_shape=(4,),
                dtype="float32",
                index=((0, 2),),
                data=np.zeros(2, np.float32),
            )
        ]
        with pytest.raises(ValueError):
            assemble_leaf((4,), "float32", ((0, 4),), recs)

    def test_restore_state_matches_sharding(self):
        state = _sharded_state()
        recs = host_shard_records(state)
        by_path = {}
        for r in recs:
            by_path.setdefault(r.path, []).append(r)
        restored = restore_state(state, lambda p: by_path.get(p, []))
        np.testing.assert_array_equal(
            np.asarray(restored["w"]), np.asarray(state["w"])
        )
        assert restored["w"].sharding == state["w"].sharding
        assert restored["step"] == 7

    def test_restore_state_from_abstract_spec(self):
        # a restarted worker passes ShapeDtypeStructs + shardings — no
        # zeros template on device (ckpt/sharding.py target_shards)
        state = _sharded_state()
        recs = host_shard_records(state)
        by_path = {}
        for r in recs:
            by_path.setdefault(r.path, []).append(r)
        spec = {
            "w": jax.ShapeDtypeStruct(
                state["w"].shape, state["w"].dtype,
                sharding=state["w"].sharding,
            ),
            "b": jax.ShapeDtypeStruct(
                state["b"].shape, state["b"].dtype,
                sharding=state["b"].sharding,
            ),
            "step": np.asarray(0),
        }
        restored = restore_state(spec, lambda p: by_path.get(p, []))
        np.testing.assert_array_equal(
            np.asarray(restored["w"]), np.asarray(state["w"])
        )
        assert restored["w"].sharding.is_equivalent_to(
            state["w"].sharding, state["w"].ndim
        )
        np.testing.assert_array_equal(
            np.asarray(restored["b"]), np.asarray(state["b"])
        )
        assert restored["step"] == 7

    def test_restore_spec_reshards_across_axes(self):
        # saved row-sharded on 8 devices, restored column-sharded on a
        # 2x4 mesh via an abstract spec: packed transfer must reshuffle
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        devs = jax.devices()
        mesh1 = Mesh(np.array(devs).reshape(len(devs)), ("x",))
        w = jax.device_put(
            jnp.arange(64.0).reshape(8, 8), NamedSharding(mesh1, P("x"))
        )
        recs = host_shard_records({"w": w})
        by_path = {}
        for r in recs:
            by_path.setdefault(r.path, []).append(r)
        mesh2 = Mesh(np.array(devs).reshape(2, len(devs) // 2), ("a", "b"))
        spec = {
            "w": jax.ShapeDtypeStruct(
                (8, 8), jnp.float32,
                sharding=NamedSharding(mesh2, P("b", "a")),
            )
        }
        restored = restore_state(spec, lambda p: by_path.get(p, []))
        np.testing.assert_array_equal(
            np.asarray(restored["w"]), np.arange(64.0).reshape(8, 8)
        )


class TestShmHandler:
    def test_write_read_roundtrip(self, saver):
        writer = ShmHandler(0, create=False)
        recs = host_shard_records({"a": np.arange(10.0)})
        writer.save_records(3, recs, {"checkpoint_dir": "/tmp/x"})
        step, out, extra = writer.load_records()
        assert step == 3
        np.testing.assert_array_equal(out[0].data, np.arange(10.0))
        assert extra["checkpoint_dir"] == "/tmp/x"


class TestEngineWithSaver:
    def test_async_save_persists_and_commits(self, saver, tmp_path):
        ckpt_dir = str(tmp_path / "ckpt")
        engine = CheckpointEngine()
        assert engine._agent_mode
        state = _sharded_state()
        assert engine.save_to_memory(10, state, ckpt_dir)
        deadline = time.time() + 30
        tracker = os.path.join(ckpt_dir, TRACKER_FILE)
        while time.time() < deadline and not os.path.exists(tracker):
            time.sleep(0.1)
        assert os.path.exists(tracker), "saver never committed"
        assert open(tracker).read().strip() == "10"
        assert os.path.exists(shard_file(ckpt_dir, 10, 0))

    def test_load_prefers_memory_then_storage(self, saver, tmp_path):
        ckpt_dir = str(tmp_path / "ckpt")
        engine = CheckpointEngine()
        state = _sharded_state()
        engine.save_to_memory(5, state, ckpt_dir)
        deadline = time.time() + 30
        while (
            time.time() < deadline
            and engine.latest_step(ckpt_dir) != 5
        ):
            time.sleep(0.1)
        # memory path
        step, restored = engine.load(state, ckpt_dir)
        assert step == 5
        np.testing.assert_array_equal(
            np.asarray(restored["w"]), np.asarray(state["w"])
        )
        # storage path (fresh process simulation: invalidate shm)
        saver._shm_handlers[0]._meta.set("valid", False)
        step2, restored2 = engine.load(state, ckpt_dir)
        assert step2 == 5
        np.testing.assert_array_equal(
            np.asarray(restored2["w"]), np.asarray(state["w"])
        )

    def test_save_at_breakpoint_persists_unsaved_shm(self, saver, tmp_path):
        """Agent persists shm on restart even though no event was sent
        (workers died before the queue put)."""
        ckpt_dir = str(tmp_path / "ckpt")
        writer = ShmHandler(0, create=False)
        recs = host_shard_records({"a": np.arange(4.0)})
        writer.save_records(
            9,
            recs,
            {
                "checkpoint_dir": ckpt_dir,
                "global_shard_id": 0,
                "global_shard_num": 1,
            },
        )
        saver.save_shm_to_storage()
        assert os.path.exists(shard_file(ckpt_dir, 9, 0))
        assert open(os.path.join(ckpt_dir, TRACKER_FILE)).read() == "9"


class TestCheckpointerNoAgent:
    def test_sync_fallback_without_agent(self, tmp_path):
        AsyncCheckpointSaver.reset()
        ckpt_dir = str(tmp_path / "ckpt")
        ckptr = FlashCheckpointer(ckpt_dir)
        assert not ckptr.engine._agent_mode
        state = {"w": np.arange(6.0), "n": 2}
        assert ckptr.save_checkpoint(4, state, StorageType.DISK)
        step, restored = ckptr.load_checkpoint(state)
        assert step == 4
        np.testing.assert_array_equal(restored["w"], np.arange(6.0))
        assert restored["n"] == 2


class TestAdviceFixes:
    """Regressions for the round-1 advisor findings (ADVICE.md)."""

    def test_stale_event_releases_shard_lock(self, saver, tmp_path):
        # a SaveEvent at/below the persisted step must release the shard
        # lock the trainer left held, or every later save reports busy
        from dlrover_tpu.ckpt.saver import SaveEvent

        saver._persisted_step = 50
        eng = CheckpointEngine()
        assert eng._agent_mode
        assert eng._lock.acquire(blocking=False)  # trainer holds the lock
        # the straggler actually staged step 50 before its event arrived;
        # the release guard checks shm still holds exactly that step
        eng._shm.save_records(
            50,
            host_shard_records({"w": jnp.arange(4.0)}),
            {"checkpoint_dir": str(tmp_path)},
        )
        eng._queue.put(
            SaveEvent(
                step=50,
                checkpoint_dir=str(tmp_path),
                local_rank=0,
                global_shard_id=0,
                global_shard_num=1,
            )
        )
        deadline = time.time() + 10
        released = False
        while time.time() < deadline:
            if eng._lock.acquire(blocking=False):
                released = True
                eng._lock.force_release()
                break
            time.sleep(0.1)
        assert released, "stale event did not release the shard lock"

    def test_reset_shared_memory_frees_orphaned_locks(self, saver):
        eng = CheckpointEngine()
        assert eng._lock.acquire(blocking=False)
        # dead worker: lock held, no persist in flight
        saver.reset_shared_memory()
        assert eng._lock.acquire(blocking=False)
        eng._lock.force_release()

    def test_reset_after_a_dead_worker_leaves_a_free_mirror(
        self, saver, tmp_path
    ):
        # a worker killed between its begin and its commit: lock held,
        # mirror held, nothing to persist
        dead = CheckpointEngine()
        state = {"w": jnp.arange(8.0)}
        assert dead.begin_chunked_save(3, state, str(tmp_path)) is not None
        lock = saver._shard_locks[0]
        assert lock.locked() and _mirror_state(lock) == _HELD
        saver.reset_shared_memory()
        assert not lock.locked() and _mirror_state(lock) == _FREE
        # the next incarnation attaches to the same names, and its save
        # begins: asked for, not answered from a stale mirror
        eng = CheckpointEngine()
        stager = eng.begin_chunked_save(4, state, str(tmp_path))
        assert stager is not None and _mirror_state(lock) == _HELD
        assert eng.save_begin["save_skips"] == 0
        stager.abort()
        assert _mirror_state(lock) == _FREE

    def test_step_agreement_single_process(self, saver):
        eng = CheckpointEngine()
        assert eng._all_processes_agree(42) is True

    def test_step_agreement_disagreement_falls_back(
        self, saver, tmp_path, monkeypatch
    ):
        # simulate two processes proposing different shm steps: the load
        # must come from committed storage, not shm
        eng = CheckpointEngine()
        state = {"w": jnp.arange(8.0)}
        assert eng.save_to_storage(3, state, str(tmp_path))
        newer = {"w": jnp.arange(8.0) + 100.0}
        assert eng.save_to_memory(7, newer, str(tmp_path))
        # wait until the saver persisted step 7 and released the lock,
        # then re-stage step 9 in shm only (not persisted)
        deadline = time.time() + 10
        while time.time() < deadline and eng.latest_step(str(tmp_path)) < 7:
            time.sleep(0.1)
        monkeypatch.setattr(
            eng, "_all_processes_agree", lambda candidate: False
        )
        step, restored = eng.load({"w": jnp.zeros(8)}, str(tmp_path))
        assert step == eng.latest_step(str(tmp_path))
        np.testing.assert_allclose(restored["w"], newer["w"])


class TestDiskSaveTimeout:
    def test_disk_save_commits_in_agent_mode(self, saver, tmp_path):
        """Agent-mode DISK save waits for the global commit and returns
        True once the tracker names the step."""
        from dlrover_tpu.ckpt.checkpointer import (
            FlashCheckpointer,
            StorageType,
        )

        ckptr = FlashCheckpointer(str(tmp_path / "ck"))
        state = {"w": np.arange(8.0)}
        assert ckptr.save_checkpoint(
            3, state, storage_type=StorageType.DISK, timeout=30.0
        )
        step, restored = ckptr.load_checkpoint({"w": np.zeros(8)})
        assert step == 3
        np.testing.assert_array_equal(np.asarray(restored["w"]), state["w"])

    def test_disk_save_timeout_returns_false(self, saver, tmp_path, monkeypatch):
        """If the global commit never lands (e.g. a diverged peer's shard
        is missing), the bounded wait returns False instead of hanging."""
        from dlrover_tpu.ckpt.checkpointer import (
            FlashCheckpointer,
            StorageType,
        )

        ckptr = FlashCheckpointer(str(tmp_path / "ck2"))
        monkeypatch.setattr(
            ckptr.engine, "latest_step", lambda d: -1
        )
        t0 = time.time()
        ok = ckptr.save_checkpoint(
            5, {"w": np.zeros(4)}, storage_type=StorageType.DISK,
            timeout=1.0,
        )
        assert not ok
        assert time.time() - t0 < 10.0


class TestChunkedStaging:
    """ISSUE-1 tentpole: chunked async checkpoint staging — fixed-size
    chunks interleaved between steps, a barrier only at commit, and a
    result bitwise-identical to the synchronous drain."""

    def _state(self):
        state = _sharded_state()
        # add a record big enough to split into many chunks
        state["big"] = jnp.asarray(
            np.random.default_rng(3).standard_normal(16384),
            jnp.float32,
        )
        return state

    def test_chunked_commit_bitwise_identical_to_sync(
        self, saver, tmp_path
    ):
        engine = CheckpointEngine()
        try:
            state = self._state()
            d_sync = str(tmp_path / "sync")
            d_chunk = str(tmp_path / "chunk")
            assert engine.save_to_memory(
                1, state, d_sync, block=True
            )
            _, recs, _ = engine._shm.load_records(copy=True)
            sync_bytes = {
                (r.path, r.index): r.data.tobytes() for r in recs
            }
            # wait out the saver so the shard lock is free again
            deadline = time.time() + 60
            while engine.latest_step(d_sync) < 1:
                time.sleep(0.05)
                assert time.time() < deadline
            stager = engine.begin_chunked_save(
                2, state, d_chunk, chunk_bytes=4096
            )
            assert stager is not None
            assert engine.staging_in_flight()
            # mid-stage the metadata stays invalid: a reader can never
            # see a half-staged step
            stager.advance(budget_s=0.001)
            if not stager.done:
                assert not engine._shm.metadata().get("valid")
            while not stager.done:
                stager.advance(budget_s=0.001)
            assert stager.backlog_bytes == 0
            assert stager.commit()
            assert stager.chunks_written > len(sync_bytes)  # really split
            assert not engine.staging_in_flight()
            step, recs2, extra = engine._shm.load_records(copy=True)
            assert step == 2
            chunk_bytes_map = {
                (r.path, r.index): r.data.tobytes() for r in recs2
            }
            assert chunk_bytes_map == sync_bytes
            assert extra["checkpoint_dir"] == d_chunk
            # the commit barrier also notified the saver: it persists
            deadline = time.time() + 60
            while engine.latest_step(d_chunk) < 2:
                time.sleep(0.05)
                assert time.time() < deadline
        finally:
            engine.close()

    def test_chunked_restore_roundtrip(self, saver, tmp_path):
        """A restore after a chunked commit returns the exact state."""
        engine = CheckpointEngine()
        try:
            state = self._state()
            d = str(tmp_path / "ck")
            stager = engine.begin_chunked_save(
                4, state, d, chunk_bytes=4096
            )
            assert stager is not None
            assert stager.commit()  # commit drains the whole backlog
            deadline = time.time() + 60
            while engine.latest_step(d) < 4:
                time.sleep(0.05)
                assert time.time() < deadline
            template = jax.tree_util.tree_map(
                lambda x: (
                    jnp.zeros_like(x) if hasattr(x, "dtype") else x
                ),
                state,
            )
            step, restored = engine.load(template, d)
            assert step == 4
            for path in ("w", "b", "big"):
                np.testing.assert_array_equal(
                    np.asarray(restored[path]),
                    np.asarray(state[path]),
                )
        finally:
            engine.close()

    def test_chunked_commit_bitwise_under_link_contention(
        self, saver, tmp_path
    ):
        """ISSUE 14 (multi-path arbiter): a chunked save racing
        EMERGENCY-priority link traffic commits byte-identically to the
        synchronous drain — the arbiter reorders transfers, never
        contents."""
        import threading

        from dlrover_tpu.parallel.transfer_sched import (
            Priority,
            TransferArbiter,
            set_arbiter,
        )

        arb = TransferArbiter(aging_s=0.05, enabled=True)
        set_arbiter(arb)
        engine = CheckpointEngine()
        stop = threading.Event()

        def contender():
            st = arb.register("emergency_rival", Priority.EMERGENCY)
            while not stop.is_set():
                with st.transfer(1 << 20):
                    time.sleep(0.002)

        t = threading.Thread(target=contender, daemon=True)
        try:
            state = self._state()
            d_sync = str(tmp_path / "sync")
            d_chunk = str(tmp_path / "chunk")
            assert engine.save_to_memory(1, state, d_sync, block=True)
            _, recs, _ = engine._shm.load_records(copy=True)
            sync_bytes = {
                (r.path, r.index): r.data.tobytes() for r in recs
            }
            deadline = time.time() + 60
            while engine.latest_step(d_sync) < 1:
                time.sleep(0.05)
                assert time.time() < deadline
            t.start()
            stager = engine.begin_chunked_save(
                2, state, d_chunk, chunk_bytes=2048
            )
            assert stager is not None
            yielded = 0
            while not stager.done:
                before = stager.chunks_written
                stager.advance(budget_s=0.002)
                yielded += stager.chunks_written == before
            assert stager.commit()
            stop.set()
            step, recs2, _ = engine._shm.load_records(copy=True)
            assert step == 2
            assert {
                (r.path, r.index): r.data.tobytes() for r in recs2
            } == sync_bytes
        finally:
            stop.set()
            t.join(timeout=2)
            set_arbiter(None)
            engine.close()

    def test_a_budgeted_advance_stages_one_chunk_at_most(
        self, saver, tmp_path
    ):
        """What a train step pays for a save in flight is a share of one
        synchronous drain of the same state: a budgeted ``advance()``
        touches no group it has just issued, and overshoots its budget by
        at most one write group of ``chunk_bytes``."""
        engine = CheckpointEngine()
        try:
            state = {"big": jnp.arange(1 << 16, dtype=jnp.float32)}
            chunk = 1 << 14
            stager = engine.begin_chunked_save(
                1, state, str(tmp_path / "ck"), chunk_bytes=chunk
            )
            assert stager is not None
            total = stager.total_bytes
            assert total == 16 * chunk
            # the first call only issues copies: they ride behind the
            # step in flight
            assert stager.advance(budget_s=0.0) == 0
            per_step = []
            while not stager.done:
                per_step.append(stager.advance(budget_s=0.0))
                assert len(per_step) <= 64
            assert max(per_step) <= chunk < total
            assert sum(per_step) == total and len(per_step) >= 16
            assert stager.backlog_bytes == 0
            assert stager.commit()
            _, recs, _ = engine._shm.load_records(copy=True)
            (rec,) = recs
            np.testing.assert_array_equal(
                rec.data.reshape(-1), np.asarray(state["big"])
            )
        finally:
            engine.close()

    def test_lock_busy_skips(self, saver, tmp_path):
        """Starting a chunked save while the saver owns the lock is a
        skip, never a block (the save_to_memory contract)."""
        engine = CheckpointEngine()
        try:
            state = {"w": np.arange(32.0)}
            d = str(tmp_path / "ck")
            s1 = engine.begin_chunked_save(1, state, d)
            assert s1 is not None
            # lock is held by the open stage: a second must skip
            assert engine.begin_chunked_save(2, state, d) is None
            assert s1.commit()
        finally:
            engine.close()

    def test_abort_releases_lock_and_invalidates(self, saver, tmp_path):
        engine = CheckpointEngine()
        try:
            state = {"w": np.arange(64.0)}
            d = str(tmp_path / "ck")
            s1 = engine.begin_chunked_save(1, state, d)
            assert s1 is not None
            s1.advance(budget_s=0.001)
            s1.abort()
            assert not engine.staging_in_flight()
            assert engine._shm.no_checkpoint()
            # the lock came back: a new save can start immediately
            s2 = engine.begin_chunked_save(2, state, d)
            assert s2 is not None
            assert s2.commit()
        finally:
            engine.close()

    def test_host_leaves_snapshot_at_begin(self, saver, tmp_path):
        """Mutable host leaves (sampler state) are copied at begin time:
        mutations during the drain must not leak into the checkpoint."""
        engine = CheckpointEngine()
        try:
            samp = np.array([10, 20], np.int64)
            state = {
                "w": jnp.asarray(np.ones(8192, np.float32)),
                "sampler": samp,
            }
            d = str(tmp_path / "ck")
            stager = engine.begin_chunked_save(
                1, state, d, chunk_bytes=4096
            )
            assert stager is not None
            samp[:] = [999, 999]  # the live sampler moves on
            assert stager.commit()
            _, recs, _ = engine._shm.load_records(copy=True)
            got = {r.path: r.data for r in recs}
            np.testing.assert_array_equal(
                got["sampler"], [10, 20]
            )
        finally:
            engine.close()
