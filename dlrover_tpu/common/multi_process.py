"""Cross-process IPC primitives: SharedLock / SharedQueue / SharedDict over
unix-domain sockets, and resource-tracker-free POSIX shared memory.

Parity: dlrover/python/common/multi_process.py:234,355,462,542. These are
the substrate of flash checkpoint: the training process and the agent
process exchange save events through a ``SharedQueue`` and hand gigabytes of
checkpoint bytes through ``SharedMemory`` segments that *survive the death
of the creating process* (Python's resource tracker would normally unlink
them — we unregister, like the reference does).

Design: every named primitive is hosted by the process that creates it with
``create=True`` (a daemon thread serves requests on a unix socket); any
process on the host attaches with ``create=False``. Requests are
length-prefixed pickled tuples ``(method, args)``.

One answer needs no request. The process that hosts a ``SharedLock`` also
writes the lock's state (free / held, and its own pid) into a few bytes of
named shared memory, the lock's *mirror*, in the same place as every
transition of the real lock. A client's non-blocking acquire reads the
mirror first and returns False at once where it reads *held*; everything
else, taking the lock included, is still a request. The flash save that
falls due while the agent's saver persists the last one is skipped that
way, without waiting for a turn of the busy agent's interpreter.
"""

from __future__ import annotations

import hashlib
import os
import pickle
import queue
import socket
import struct
import threading
import time
from typing import Any, Dict, Optional

from dlrover_tpu.common.log import default_logger as logger

SOCKET_DIR_ENV = "DLROVER_TPU_SOCKET_DIR"


def _socket_dir() -> str:
    # namespaced per job so two launchers on one host cannot clobber each
    # other's endpoints (the shm segments are namespaced the same way).
    # The env var overrides the BASE dir only — the job namespace always
    # applies (an as-is override once let a multi-node local cluster's
    # agents share un-namespaced endpoints and deadlock; chaos soak)
    job = os.getenv("DLROVER_TPU_JOB_NAME", "job")
    base = os.getenv(SOCKET_DIR_ENV, "/tmp/dlrover_tpu")
    d = os.path.join(base, job, "sockets")
    os.makedirs(d, exist_ok=True)
    return d


def _socket_path(name: str) -> str:
    return os.path.join(_socket_dir(), f"{name}.sock")


def server_exists(name: str) -> bool:
    """True when some process is *actually serving* the named IPC endpoint
    (a stale socket file left by a killed process probes as dead and is
    removed)."""
    path = _socket_path(name)
    if not os.path.exists(path):
        return False
    try:
        with socket.socket(socket.AF_UNIX, socket.SOCK_STREAM) as s:
            s.settimeout(1.0)
            s.connect(path)
        return True
    except OSError:
        try:
            os.unlink(path)
        except OSError:
            pass
        return False


def clear_sockets():
    d = _socket_dir()
    for f in os.listdir(d):
        if f.endswith(".sock"):
            try:
                os.unlink(os.path.join(d, f))
            except OSError:
                pass


def _send_msg(sock: socket.socket, obj: Any):
    data = pickle.dumps(obj, protocol=pickle.HIGHEST_PROTOCOL)
    sock.sendall(struct.pack("<Q", len(data)) + data)


def _recv_exact(sock: socket.socket, n: int) -> bytes:
    buf = b""
    while len(buf) < n:
        chunk = sock.recv(n - len(buf))
        if not chunk:
            raise ConnectionError("socket closed")
        buf += chunk
    return buf


def _recv_msg(sock: socket.socket) -> Any:
    (length,) = struct.unpack("<Q", _recv_exact(sock, 8))
    return pickle.loads(_recv_exact(sock, length))


class LocalSocketComm:
    """Base for a named primitive shared between local processes."""

    def __init__(self, name: str, create: bool = False):
        self.name = name
        self._create = create
        self._path = _socket_path(name)
        self._server: Optional[socket.socket] = None
        self._stopped = False
        if create:
            self._start_server()

    # -- server side ---------------------------------------------------
    def _start_server(self):
        if os.path.exists(self._path):
            os.unlink(self._path)
        self._server = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        self._server.bind(self._path)
        self._server.listen(64)
        t = threading.Thread(
            target=self._serve, name=f"ipc-{self.name}", daemon=True
        )
        t.start()

    def _serve(self):
        while not self._stopped:
            try:
                conn, _ = self._server.accept()
            except OSError:
                break
            threading.Thread(
                target=self._handle_conn, args=(conn,), daemon=True
            ).start()

    def _handle_conn(self, conn: socket.socket):
        with conn:
            try:
                while True:
                    method, args = _recv_msg(conn)
                    try:
                        result = getattr(self, f"_do_{method}")(*args)
                        _send_msg(conn, (True, result))
                    except Exception as e:  # serve errors back to client
                        _send_msg(conn, (False, repr(e)))
            except (ConnectionError, EOFError):
                pass

    def close(self):
        self._stopped = True
        if self._server is not None:
            try:
                self._server.close()
            except OSError:
                pass
            try:
                os.unlink(self._path)
            except OSError:
                pass

    # -- client side ---------------------------------------------------
    def _call(self, method: str, *args, timeout: float = 60.0):
        if self._create:
            # host process short-circuits straight to the implementation
            return getattr(self, f"_do_{method}")(*args)
        deadline = time.time() + timeout
        last_err: Optional[Exception] = None
        while time.time() < deadline:
            try:
                with socket.socket(socket.AF_UNIX, socket.SOCK_STREAM) as s:
                    s.settimeout(max(1.0, deadline - time.time()))
                    s.connect(self._path)
                    _send_msg(s, (method, args))
                    ok, result = _recv_msg(s)
                if not ok:
                    raise RuntimeError(result)
                return result
            except (ConnectionError, FileNotFoundError, socket.timeout) as e:
                last_err = e
                time.sleep(0.1)
        raise TimeoutError(
            f"IPC call {self.name}.{method} failed: {last_err!r}"
        )


# the mirror of a SharedLock: one state byte and the hosting process's pid
_MIRROR = struct.Struct("<B3xI")
_FREE, _HELD = 0, 1


class SharedLock(LocalSocketComm):
    """Named lock usable across processes (parity: multi_process.py:234).

    The real lock is a ``threading.Lock`` in the hosting process, and
    every transition of it runs there (``_do_acquire``, ``_do_release``,
    ``_do_force_release``). The host mirrors the state into a named shm
    segment of ``_MIRROR.size`` bytes beside the socket (made with the
    server, unlinked in ``close()``): *held* is written after the lock
    was taken, *free* before it is let go, both under ``_transition``,
    so the mirror never reads *held* over a free lock. Only the host
    writes it. A client's ``acquire(blocking=False)`` reads it first:
    *held* by a host that is alive is the answer the request would have
    brought, a failed non-blocking acquire having no side effect;
    *free*, or a mirror that is missing, short or unreadable, means ask.
    Taking the lock is always a request. ``local_answers`` counts the
    acquires this client answered from the mirror."""

    def __init__(self, name: str, create: bool = False):
        self._lock = threading.Lock() if create else None
        self._owner: Optional[str] = None
        # serialises a release with the mirror's write and with other
        # releases (host only)
        self._transition = threading.Lock()
        self._mirror: Optional[SharedMemory] = None
        self.local_answers = 0
        # named from the socket's path: whoever finds this server's
        # socket finds its mirror, and no other job's or node's
        digest = hashlib.sha1(_socket_path(name).encode()).hexdigest()[:12]
        self._mirror_name = f"dlrover_tpu_lock_{name}_{digest}"
        if create:
            # a segment left by a killed host is taken over, and reads
            # free from here on: the lock it mirrored died with it
            self._mirror = create_shared_memory(
                self._mirror_name, _MIRROR.size
            )
            self._publish(_FREE)
        super().__init__(name, create)

    # -- host side: the transitions, each with the mirror's write ------
    def _publish(self, state: int):
        if self._mirror is not None:
            _MIRROR.pack_into(self._mirror.buf, 0, state, os.getpid())

    def _do_acquire(self, blocking: bool, owner: str) -> bool:
        got = self._lock.acquire(blocking=blocking, timeout=30 if blocking else -1)
        if got:
            with self._transition:
                self._owner = owner
                # what the lock is now, not what this thread did: a
                # force_release may have come between
                self._publish(_HELD if self._lock.locked() else _FREE)
        return got

    def _let_go(self) -> bool:
        """Free the lock if it is held; the mirror goes first. Under
        ``_transition``."""
        held = self._lock.locked()
        self._publish(_FREE)
        if held:
            self._owner = None
            self._lock.release()
        return held

    def _do_release(self, owner: str) -> bool:
        with self._transition:
            return self._owner == owner and self._let_go()

    def _do_locked(self) -> bool:
        return self._lock.locked()

    def _do_force_release(self) -> bool:
        with self._transition:
            return self._let_go()

    def close(self):
        super().close()
        if self._create:
            # a client that still maps the segment must not read a lock
            # that is gone as held
            self._publish(_FREE)
        mirror, self._mirror = self._mirror, None
        if mirror is not None:
            if self._create:
                mirror.unlink()
            mirror.close()

    # -- client side ---------------------------------------------------
    def _mirror_reads_held(self) -> bool:
        """True only where the mirror says *held* and its host is alive."""
        if self._mirror is None:
            self._mirror = attach_shared_memory(self._mirror_name)
            if self._mirror is None:
                return False
        try:
            state, pid = _MIRROR.unpack_from(self._mirror.buf, 0)
            if state != _HELD:
                return False
            os.kill(pid, 0)
        except (struct.error, OSError):
            # too short to hold a state, or the host that wrote *held*
            # is gone (or not ours to see): ask
            return False
        return True

    def acquire(self, blocking: bool = True) -> bool:
        if blocking or self._create:
            return self._call("acquire", blocking, self._owner_id())
        if self._mirror_reads_held():
            self.local_answers += 1
            return False
        got = self._call("acquire", False, self._owner_id())
        if not got and self._mirror is not None:
            # the mirror did not say what the host said: a transition in
            # between, or the segment of a host that has closed since.
            # Look it up again next time
            self._mirror.close()
            self._mirror = None
        return got

    def release(self) -> bool:
        return self._call("release", self._owner_id())

    def force_release(self) -> bool:
        """Release regardless of owner — for lock-handoff protocols where a
        different process (or a dead owner's supervisor) must unlock."""
        return self._call("force_release")

    def locked(self) -> bool:
        return self._call("locked")

    def _owner_id(self) -> str:
        return f"{os.getpid()}-{threading.get_ident()}"


class SharedQueue(LocalSocketComm):
    """Named FIFO queue across processes (parity: multi_process.py:355)."""

    def __init__(self, name: str, create: bool = False, maxsize: int = 0):
        self._queue: Optional[queue.Queue] = (
            queue.Queue(maxsize) if create else None
        )
        super().__init__(name, create)

    def _do_put(self, obj, timeout: float):
        self._queue.put(obj, timeout=timeout)

    def _do_get(self, timeout: float):
        try:
            return self._queue.get(timeout=timeout)
        except queue.Empty:
            return _EMPTY

    def _do_qsize(self) -> int:
        return self._queue.qsize()

    def _do_empty(self) -> bool:
        return self._queue.empty()

    def put(self, obj, timeout: float = 60.0):
        self._call("put", obj, timeout)

    def get(self, timeout: float = 60.0):
        result = self._call("get", timeout, timeout=timeout + 10)
        if isinstance(result, _Empty):
            raise queue.Empty
        return result

    def qsize(self) -> int:
        return self._call("qsize")

    def empty(self) -> bool:
        return self._call("empty")


class _Empty:
    """Sentinel marking an empty-queue response."""

    def __eq__(self, other):
        return isinstance(other, _Empty)


_EMPTY = _Empty()


class SharedDict(LocalSocketComm):
    """Named dict across processes (parity: multi_process.py:462)."""

    def __init__(self, name: str, create: bool = False):
        self._dict: Optional[Dict] = {} if create else None
        self._dict_lock = threading.Lock() if create else None
        super().__init__(name, create)

    def _do_set(self, key, value):
        with self._dict_lock:
            self._dict[key] = value

    def _do_update(self, other: Dict):
        with self._dict_lock:
            self._dict.update(other)

    def _do_get(self, key, default):
        with self._dict_lock:
            return self._dict.get(key, default)

    def _do_dict(self) -> Dict:
        with self._dict_lock:
            return dict(self._dict)

    def _do_pop(self, key, default):
        with self._dict_lock:
            return self._dict.pop(key, default)

    def set(self, key, value):
        self._call("set", key, value)

    def update(self, other: Dict):
        self._call("update", other)

    def get(self, key, default=None):
        return self._call("get", key, default)

    def pop(self, key, default=None):
        return self._call("pop", key, default)

    def as_dict(self) -> Dict:
        return self._call("dict")


# ---------------------------------------------------------------------------
# resource-tracker-free POSIX shared memory
# ---------------------------------------------------------------------------

from multiprocessing import resource_tracker, shared_memory  # noqa: E402


class SharedMemory(shared_memory.SharedMemory):
    """POSIX shm whose lifetime is *not* tied to the creating process.

    Parity: multi_process.py:542 — the reference re-implements
    ``SharedMemory`` so the resource tracker does not unlink the segment
    when the training process dies; the checkpoint bytes must outlive it so
    the agent can persist them ("save at breakpoint"). We create through the
    stdlib then immediately unregister from the tracker, and make
    ``unlink()`` explicit-only.
    """

    def __init__(self, name: str, create: bool = False, size: int = 0):
        super().__init__(name=name, create=create, size=size)
        try:
            resource_tracker.unregister(self._name, "shared_memory")
        except Exception:  # pragma: no cover - tracker internals vary
            pass

    def unlink(self):
        """Unlink explicitly; never called implicitly by GC."""
        try:
            shared_memory._posixshmem.shm_unlink(self._name)
        except FileNotFoundError:
            pass


def create_shared_memory(name: str, size: int) -> Optional[SharedMemory]:
    """Create (or recreate with the right size) a named shm segment."""
    try:
        shm = SharedMemory(name=name, create=True, size=size)
    except FileExistsError:
        shm = SharedMemory(name=name)
        if shm.size < size:
            shm.close()
            shm.unlink()
            shm = SharedMemory(name=name, create=True, size=size)
    except Exception as e:  # pragma: no cover
        logger.error(f"cannot create shm {name}: {e!r}")
        return None
    return shm


def attach_shared_memory(name: str) -> Optional[SharedMemory]:
    try:
        return SharedMemory(name=name)
    except FileNotFoundError:
        return None
