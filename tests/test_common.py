"""Tests for common: node state machine, messages, IPC, storage."""

import multiprocessing as mp
import os
import queue
import time

import pytest

from dlrover_tpu.common import comm
from dlrover_tpu.common.constants import NodeExitReason, NodeStatus
from dlrover_tpu.common.multi_process import (
    SharedDict,
    SharedLock,
    SharedMemory,
    SharedQueue,
    attach_shared_memory,
    create_shared_memory,
)
from dlrover_tpu.common.node import Node, NodeResource, is_allowed_transition
from dlrover_tpu.common.storage import (
    KeepLatestStepStrategy,
    KeepStepIntervalStrategy,
    PosixDiskStorage,
)


class TestNode:
    def test_status_flow(self):
        node = Node(node_id=0)
        assert node.update_status(NodeStatus.PENDING)
        assert node.update_status(NodeStatus.RUNNING)
        assert node.start_time is not None
        # illegal: RUNNING -> PENDING
        assert not node.update_status(NodeStatus.PENDING)
        assert node.update_status(NodeStatus.FAILED)
        assert node.finish_time is not None
        assert not is_allowed_transition(NodeStatus.DELETED, NodeStatus.RUNNING)

    def test_relaunch(self):
        node = Node(node_id=3, max_relaunch_count=2)
        node.exit_reason = NodeExitReason.KILLED
        assert not node.is_unrecoverable_failure()
        node.relaunch_count = 2
        assert node.is_unrecoverable_failure()
        node.relaunch_count = 0
        node.exit_reason = NodeExitReason.FATAL_ERROR
        assert node.is_unrecoverable_failure()

        node.exit_reason = NodeExitReason.KILLED
        new = node.get_relaunch_node_info(new_id=7)
        assert new.id == 7
        assert new.rank_index == node.rank_index
        assert new.relaunch_count == 1
        assert new.status == NodeStatus.INITIAL

    def test_resource(self):
        r = NodeResource(cpu=4, memory_mb=8192, tpu_chips=4, tpu_type="v5p")
        r2 = NodeResource.from_dict(r.to_dict())
        assert r2 == r


class TestComm:
    def test_roundtrip(self):
        msg = comm.CommWorld(
            rdzv_name="elastic-training",
            round=2,
            world={0: 4, 1: 4},
            coordinator_addr="10.0.0.1:8899",
        )
        data = comm.serialize_message(msg)
        out = comm.deserialize_message(data)
        assert out == msg

    def test_restricted_unpickle(self):
        import pickle

        class Evil:
            def __reduce__(self):
                return (os.system, ("true",))

        payload = pickle.dumps(Evil())
        with pytest.raises(Exception):
            comm.deserialize_message(payload)

    def test_find_free_port(self):
        p = comm.find_free_port()
        assert 0 < p < 65536


class TestIPC:
    def test_shared_queue(self):
        q = SharedQueue("test-q", create=True)
        client = SharedQueue("test-q", create=False)
        client.put({"step": 5})
        assert q.qsize() == 1
        assert client.get(timeout=5) == {"step": 5}
        with pytest.raises(queue.Empty):
            client.get(timeout=0.2)
        q.close()

    def test_shared_dict(self):
        d = SharedDict("test-d", create=True)
        client = SharedDict("test-d", create=False)
        client.set("rank0", {"step": 1})
        d.set("rank1", {"step": 2})
        assert client.as_dict() == {"rank0": {"step": 1}, "rank1": {"step": 2}}
        assert client.pop("rank0") == {"step": 1}
        assert client.get("rank0", "gone") == "gone"
        d.close()

    def test_shared_lock(self):
        lock = SharedLock("test-l", create=True)
        client = SharedLock("test-l", create=False)
        assert client.acquire(blocking=False)
        assert lock.locked()
        # a different thread (different owner id) cannot release it
        import threading

        results = []
        t = threading.Thread(target=lambda: results.append(lock.release()))
        t.start()
        t.join()
        assert results == [False]
        assert client.release()
        assert not lock.locked()
        assert not client.release()  # releasing an unlocked lock is a no-op
        lock.close()

    def test_force_release_breaks_dead_owner_lock(self):
        lock = SharedLock("test-fr", create=True)
        client = SharedLock("test-fr", create=False)
        assert client.acquire(blocking=False)
        # lock-handoff: the server side releases on behalf of the client
        assert lock.force_release()
        assert not lock.locked()
        assert not lock.force_release()  # idempotent on unlocked
        lock.close()

    # -- the lock's mirror: a "no" that needs no request -----------------
    @staticmethod
    def _calls(monkeypatch, client):
        """Count the requests ``client`` makes from here on."""
        made = []
        real = client._call

        def counted(method, *args, **kw):
            made.append(method)
            return real(method, *args, **kw)

        monkeypatch.setattr(client, "_call", counted)
        return made

    @staticmethod
    def _mirror(lock):
        """``(state, pid)`` as a client that attaches now reads them."""
        from dlrover_tpu.common.multi_process import _MIRROR

        shm = attach_shared_memory(lock._mirror_name)
        if shm is None:
            return None
        try:
            return _MIRROR.unpack_from(shm.buf, 0)
        finally:
            shm.close()

    def test_held_lock_says_no_without_a_request(self, monkeypatch):
        lock = SharedLock("test-m-held", create=True)
        holder = SharedLock("test-m-held", create=False)
        client = SharedLock("test-m-held", create=False)
        assert holder.acquire(blocking=False)
        made = self._calls(monkeypatch, client)
        # no connection either: a request would hang on a dead socket
        monkeypatch.setattr(client, "_path", client._path + ".nowhere")
        t0 = time.perf_counter()
        assert client.acquire(blocking=False) is False
        assert client.acquire(blocking=False) is False
        assert time.perf_counter() - t0 < 0.05
        assert made == [] and client.local_answers == 2
        assert lock.locked()  # a failed try has no side effect
        lock.close()

    def test_free_lock_is_taken_through_the_request(self, monkeypatch):
        lock = SharedLock("test-m-free", create=True)
        client = SharedLock("test-m-free", create=False)
        made = self._calls(monkeypatch, client)
        assert client.acquire(blocking=False) is True
        assert made == ["acquire"] and client.local_answers == 0
        assert lock.locked() and lock._owner == client._owner_id()
        assert client.release() and not lock.locked()
        lock.close()

    def test_blocking_acquire_always_asks(self, monkeypatch):
        import threading

        lock = SharedLock("test-m-block", create=True)
        client = SharedLock("test-m-block", create=False)
        assert lock.acquire(blocking=False)
        made = self._calls(monkeypatch, client)
        t = threading.Timer(0.2, lock.force_release)
        t.start()
        assert client.acquire(blocking=True) is True  # waited it out
        t.join(timeout=5)
        assert made == ["acquire"] and client.local_answers == 0
        lock.close()

    @pytest.mark.parametrize(
        "after",
        ["created", "acquire", "release", "force_release", "recreated",
         "recreated_after_kill", "close"],
    )
    def test_mirror_follows_the_lock(self, after):
        from dlrover_tpu.common.multi_process import _FREE, _HELD

        name = "test-m-" + after
        lock = SharedLock(name, create=True)
        client = SharedLock(name, create=False)
        me = os.getpid()
        if after == "created":
            assert self._mirror(lock) == (_FREE, me)
        elif after == "acquire":
            assert client.acquire(blocking=False)
            assert self._mirror(lock) == (_HELD, me)
        elif after == "release":
            assert client.acquire(blocking=False) and client.release()
            assert self._mirror(lock) == (_FREE, me)
            assert not lock.release()  # not held: still free
            assert self._mirror(lock) == (_FREE, me)
        elif after == "force_release":
            assert client.acquire(blocking=False)
            assert client.force_release()
            assert self._mirror(lock) == (_FREE, me)
            # a mirror gone wrong is repaired by the next force_release
            lock._publish(_HELD)
            assert not lock.force_release()
            assert self._mirror(lock) == (_FREE, me)
        elif after == "recreated":
            # the host closes holding the lock; a new one takes the name
            assert client.acquire(blocking=False)
            assert client.acquire(blocking=False) is False  # maps the mirror
            lock.close()
            lock = SharedLock(name, create=True)
            assert self._mirror(lock) == (_FREE, me)
            # the client still maps the segment the old host unlinked: it
            # reads free there, asks, and takes the new host's lock
            assert client.acquire(blocking=False) and lock.locked()
            assert self._mirror(lock) == (_HELD, me)
        elif after == "recreated_after_kill":
            # a killed host unlinks nothing: its segment says held
            assert client.acquire(blocking=False)
            lock._mirror = None  # what close() would have unlinked
            lock.close()
            lock = SharedLock(name, create=True)
            assert self._mirror(lock) == (_FREE, me)
            assert client.acquire(blocking=False) and lock.locked()
        elif after == "close":
            assert client.acquire(blocking=False)
            assert client.acquire(blocking=False) is False
            lock.close()
            # unlinked, and free for whoever still maps it
            assert self._mirror(lock) is None
            assert not client._mirror_reads_held()
        client.close()
        lock.close()

    @pytest.mark.parametrize("mirror", ["missing", "truncated", "dead_host"])
    def test_unreadable_mirror_means_ask(self, mirror, monkeypatch):
        from dlrover_tpu.common.multi_process import _HELD, _MIRROR

        name = "test-m-" + mirror
        lock = SharedLock(name, create=True)
        client = SharedLock(name, create=False)
        assert lock.acquire(blocking=False)
        if mirror == "dead_host":
            child = mp.get_context("spawn").Process(target=int)
            child.start()
            child.join(timeout=30)
            assert child.exitcode == 0
            _MIRROR.pack_into(lock._mirror.buf, 0, _HELD, child.pid)
        else:
            lock._mirror.unlink()
            if mirror == "truncated":
                short = SharedMemory(lock._mirror_name, create=True, size=1)
                short.buf[0] = _HELD
        made = self._calls(monkeypatch, client)
        assert client.acquire(blocking=False) is False  # the host's answer
        assert made == ["acquire"] and client.local_answers == 0
        assert lock.force_release()
        assert client.acquire(blocking=False) is True
        assert made == ["acquire", "acquire"]
        if mirror == "truncated":
            short.close()
        client.close()
        lock.close()

    def test_mirror_never_reads_held_over_a_free_lock(self):
        """Acquires and releases from more threads than cores: whenever
        the lock is at rest the mirror says what it is."""
        import sys
        import threading

        from dlrover_tpu.common.multi_process import _FREE, _HELD

        lock = SharedLock("test-m-stress", create=True)
        stop = threading.Event()

        def taker(owner):
            while not stop.is_set():
                if lock._do_acquire(False, owner):
                    lock._do_release(owner)

        def breaker():
            while not stop.is_set():
                lock._do_force_release()

        n = 2 * (os.cpu_count() or 4)
        threads = [
            threading.Thread(target=taker, args=(f"o{i}",)) for i in range(n)
        ] + [threading.Thread(target=breaker) for _ in range(4)]
        interval = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            for t in threads:
                t.start()
            time.sleep(1.0)
            stop.set()
            for t in threads:
                t.join(timeout=30)
        finally:
            sys.setswitchinterval(interval)
        assert not any(t.is_alive() for t in threads)
        assert not lock.locked()
        assert self._mirror(lock) == (_FREE, os.getpid())
        assert lock._do_acquire(False, "last")
        assert self._mirror(lock) == (_HELD, os.getpid())
        lock.close()

    def test_server_exists_probes_liveness(self):
        from dlrover_tpu.common.multi_process import (
            _socket_path,
            server_exists,
        )

        q = SharedQueue("test-alive", create=True)
        assert server_exists("test-alive")
        q.close()
        # dead socket file left behind must probe False (and get cleaned)
        with open(_socket_path("test-stale"), "w"):
            pass
        assert not server_exists("test-stale")
        assert not os.path.exists(_socket_path("test-stale"))
        assert not server_exists("test-never-existed")

    def test_shared_memory_survives_process(self):
        name = f"dlrover-tpu-test-{os.getpid()}"
        p = mp.get_context("spawn").Process(target=_shm_child, args=(name,))
        p.start()
        p.join()
        assert p.exitcode == 0
        shm = attach_shared_memory(name)
        assert shm is not None
        assert bytes(shm.buf[:5]) == b"hello"
        shm.close()
        shm.unlink()
        assert attach_shared_memory(name) is None


def _shm_child(n):
    shm = create_shared_memory(n, 1024)
    shm.buf[:5] = b"hello"
    shm.close()  # close mapping but do NOT unlink


class TestStorage:
    def test_atomic_write_read(self, tmp_path):
        storage = PosixDiskStorage()
        path = str(tmp_path / "ckpt" / "model.bin")
        storage.write(b"\x00\x01payload", path)
        assert storage.read(path) == b"\x00\x01payload"
        storage.write_state_dict({"w": [1, 2, 3]}, path)
        assert storage.read_state_dict(path) == {"w": [1, 2, 3]}
        assert storage.read(str(tmp_path / "missing")) is None

    def test_keep_latest_strategy(self, tmp_path):
        strat = KeepLatestStepStrategy(max_to_keep=2, checkpoint_dir=str(tmp_path))
        storage = PosixDiskStorage(strat)
        for step in (10, 20, 30):
            d = tmp_path / str(step)
            d.mkdir()
            storage.commit(step, success=True)
        assert not (tmp_path / "10").exists()
        assert (tmp_path / "20").exists()
        assert (tmp_path / "30").exists()

    def test_keep_interval_strategy(self, tmp_path):
        strat = KeepStepIntervalStrategy(keep_interval=100, checkpoint_dir=str(tmp_path))
        storage = PosixDiskStorage(strat)
        for step in (100, 150):
            (tmp_path / str(step)).mkdir()
            storage.commit(step, success=True)
        assert (tmp_path / "100").exists()
        assert not (tmp_path / "150").exists()
