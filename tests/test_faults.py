"""Deterministic chaos matrix: fault injection + checkpoint integrity.

The random-SIGKILL soak (test_chaos_soak.py, slow tier) only exercises
process death. This file is the deterministic tier-1 matrix for the
storage/RPC failure scenarios: every registered checkpoint fault point
is armed (torn write / bit flip / ENOSPC / IO error), and the contract
under test is always the same — corruption is DETECTED at load, restore
falls back to the newest *verified* step, training resumes from it, and
a corrupt newest step is never silently restored. Plus: degraded
(shm-only) checkpoint mode on persistent ENOSPC, saver fast-fail on a
dead shard thread, retry hardening of the master client, and the
prefetch/reshard fault sites.
"""

import os
import time

import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.common import faults
from dlrover_tpu.common.storage import PosixDiskStorage
from dlrover_tpu.ckpt import saver as saver_mod
from dlrover_tpu.ckpt.checkpointer import FlashCheckpointer, StorageType
from dlrover_tpu.ckpt.engine import CheckpointEngine
from dlrover_tpu.ckpt.saver import (
    AsyncCheckpointSaver,
    gc_checkpoints,
    quarantine_step_dir,
    read_history,
    read_tracker,
    resolve_verified_step,
    shard_file,
    step_dir,
    verify_step_dir,
)


@pytest.fixture(autouse=True)
def _disarm():
    """Every test starts and ends with no fault armed and zero tallies."""
    faults.reset()
    yield
    faults.reset()


@pytest.fixture
def saver(tmp_path):
    AsyncCheckpointSaver.reset()
    s = AsyncCheckpointSaver.start_async_saving_ckpt(local_shard_num=1)
    # keep the retry/backoff dance fast for tests
    s.persist_retries = 2
    s.persist_backoff_base = 0.01
    s.persist_backoff_cap = 0.02
    yield s
    AsyncCheckpointSaver.reset()


# ---------------------------------------------------------------------------
# fault framework
# ---------------------------------------------------------------------------
class TestFaultSpecs:
    def test_parse_full(self):
        s = faults.FaultSpec.parse("ckpt.shard_write:torn_write:0.5:42")
        assert s.site == "ckpt.shard_write"
        assert s.kind == "torn_write"
        assert s.prob == 0.5
        assert s.seed == 42

    def test_parse_derives_stable_seed(self):
        a = faults.FaultSpec.parse("ckpt.persist:enospc:1.0")
        b = faults.FaultSpec.parse("ckpt.persist:enospc:1.0")
        assert a.seed == b.seed

    @pytest.mark.parametrize(
        "raw",
        [
            "nope.site:enospc:1.0",  # unknown site
            "ckpt.persist:frobnicate:1.0",  # unknown kind
            "ckpt.persist:enospc:2.0",  # prob out of range
            "ckpt.persist:enospc",  # missing prob
            "ckpt.persist:enospc:xyz",  # unparsable prob
        ],
    )
    def test_parse_rejects(self, raw):
        with pytest.raises(ValueError):
            faults.FaultSpec.parse(raw)

    def test_seeded_triggering_is_deterministic(self):
        def run():
            inj = faults.FaultInjector()
            inj.configure("ckpt.persist:enospc:0.5:7")
            seq = []
            for _ in range(32):
                try:
                    inj.fire("ckpt.persist")
                    seq.append(0)
                except OSError:
                    seq.append(1)
            return seq

        a, b = run(), run()
        assert a == b, "same spec+seed must replay the same sequence"
        assert 0 < sum(a) < 32, "prob 0.5 should mix hits and misses"

    def test_env_activation_and_reload(self, monkeypatch):
        monkeypatch.setenv(faults.ENV_VAR, "ckpt.persist:enospc:1.0")
        faults.reload_from_env()
        with pytest.raises(OSError) as ei:
            faults.fire("ckpt.persist")
        import errno

        assert ei.value.errno == errno.ENOSPC
        monkeypatch.delenv(faults.ENV_VAR)
        faults.reload_from_env()
        faults.fire("ckpt.persist")  # disarmed: no-op

    def test_wildcard_site_and_tally(self):
        faults.configure("*:io_error:1.0")
        for site in ("rpc.send", "prefetch.pull"):
            with pytest.raises(OSError):
                faults.fire(site)
        t = faults.triggered()
        assert t[("rpc.send", "io_error")] == 1
        assert t[("prefetch.pull", "io_error")] == 1
        assert faults.triggered_total() == 2

    def test_triggered_counts_into_metrics_registry(self):
        from dlrover_tpu.obs.metrics import default_registry

        c = default_registry().counter(
            "dlrover_faults_triggered_total",
            "injected faults that fired, by site and kind",
            labelnames=("site", "kind"),
        )
        before = c.labels("ckpt.persist", "delay").value
        faults.configure("ckpt.persist:delay:1.0")
        faults.fire("ckpt.persist")
        assert c.labels("ckpt.persist", "delay").value == before + 1

    def test_corrupt_torn_write_truncates(self):
        faults.configure("ckpt.shard_write:torn_write:1.0:3")
        blob = bytes(range(256)) * 8
        out = faults.corrupt("ckpt.shard_write", blob)
        assert 0 < len(out) < len(blob)
        assert out == blob[: len(out)]

    def test_corrupt_bit_flip_changes_one_bit(self):
        faults.configure("ckpt.shard_write:bit_flip:1.0:3")
        blob = b"\x00" * 64
        out = faults.corrupt("ckpt.shard_write", blob)
        assert len(out) == len(blob)
        diff = [a ^ b for a, b in zip(blob, out)]
        assert sum(bin(d).count("1") for d in diff) == 1

    def test_corrupt_array_keeps_length(self):
        faults.configure("ckpt.shm_stage:bit_flip:1.0:5")
        arr = np.ones(16, np.float32)
        out = faults.corrupt_array("ckpt.shm_stage", arr)
        assert out.nbytes == arr.nbytes
        assert not np.array_equal(
            np.asarray(out).view(np.uint8),
            np.ascontiguousarray(arr).view(np.uint8),
        )

    def test_inactive_paths_are_noops(self):
        faults.fire("ckpt.persist")
        assert faults.corrupt("ckpt.shard_write", b"abc") == b"abc"
        arr = np.arange(4.0)
        assert faults.corrupt_array("ckpt.shm_stage", arr) is arr

    def test_corrupt_array_scale_is_finite_but_wrong(self):
        # the SDC kind: a deterministic slice multiplied by a large
        # factor — wrong numbers that every finite fence passes
        faults.configure("device.sdc:scale:1.0:7")
        arr = np.ones(64, np.float32)
        out = np.asarray(faults.corrupt_array("device.sdc", arr))
        assert out.shape == arr.shape
        assert np.all(np.isfinite(out))
        scaled = int(np.sum(out == np.float32(faults.SCALE_FACTOR)))
        assert scaled == 64 // 8  # an eighth of the elements
        assert int(np.sum(out == 1.0)) == 64 - scaled

    def test_corrupt_array_scale_is_seed_deterministic(self):
        arr = np.arange(1, 65, dtype=np.float32)
        faults.configure("device.sdc:scale:1.0:7")
        a = np.asarray(faults.corrupt_array("device.sdc", arr.copy()))
        faults.reset()
        faults.configure("device.sdc:scale:1.0:7")
        b = np.asarray(faults.corrupt_array("device.sdc", arr.copy()))
        assert np.array_equal(a, b)

    def test_corrupt_bytes_ignores_scale_kind(self):
        # bytes carry no dtype to scale: the data kind must act only at
        # array sites, never rot a byte stream it cannot interpret
        faults.configure("device.sdc:scale:1.0:7")
        blob = bytes(range(64))
        assert faults.corrupt("device.sdc", blob) == blob


# ---------------------------------------------------------------------------
# step-dir integrity primitives
# ---------------------------------------------------------------------------
def _write_step(storage, ckpt_dir, step, value=1.0):
    """One shard of a tiny state persisted through the production
    helpers (payload + crc + done file)."""
    from dlrover_tpu.ckpt.sharding import host_shard_records

    records = host_shard_records(
        {"w": np.full(8, value, np.float32), "step": step}
    )
    storage.safe_makedirs(
        os.path.join(step_dir(ckpt_dir, step), saver_mod.DONE_DIR)
    )
    payload = saver_mod.build_shard_payload(step, 0, 1, records, {})
    saver_mod.write_shard_and_done(storage, ckpt_dir, step, payload)
    saver_mod.commit_checkpoint(storage, ckpt_dir, step, 1, timeout=5)


class TestStepVerification:
    def test_clean_step_verifies(self, tmp_path):
        st = PosixDiskStorage()
        _write_step(st, str(tmp_path), 3)
        ok, reason = verify_step_dir(st, str(tmp_path), 3)
        assert ok, reason

    def test_torn_shard_detected(self, tmp_path):
        st = PosixDiskStorage()
        _write_step(st, str(tmp_path), 3)
        path = shard_file(str(tmp_path), 3, 0)
        blob = open(path, "rb").read()
        open(path, "wb").write(blob[: len(blob) // 2])
        ok, reason = verify_step_dir(st, str(tmp_path), 3)
        assert not ok and "torn" in reason

    def test_bit_flip_detected(self, tmp_path):
        st = PosixDiskStorage()
        _write_step(st, str(tmp_path), 3)
        path = shard_file(str(tmp_path), 3, 0)
        blob = bytearray(open(path, "rb").read())
        blob[len(blob) // 2] ^= 0x10
        open(path, "wb").write(bytes(blob))
        ok, reason = verify_step_dir(st, str(tmp_path), 3)
        assert not ok and "checksum" in reason

    def test_missing_done_file_detected(self, tmp_path):
        st = PosixDiskStorage()
        _write_step(st, str(tmp_path), 3)
        os.remove(
            os.path.join(
                step_dir(str(tmp_path), 3), saver_mod.DONE_DIR, "0.done"
            )
        )
        ok, reason = verify_step_dir(st, str(tmp_path), 3)
        assert not ok

    def test_missing_shard_of_advertised_set_detected(self, tmp_path):
        st = PosixDiskStorage()
        _write_step(st, str(tmp_path), 3)
        # done file advertises 2 global shards but only shard 0 exists
        done = os.path.join(
            step_dir(str(tmp_path), 3), saver_mod.DONE_DIR, "0.done"
        )
        meta = saver_mod.parse_done(open(done).read())
        meta["global_shard_num"] = 2
        import json

        open(done, "w").write(json.dumps(meta))
        ok, reason = verify_step_dir(st, str(tmp_path), 3)
        assert not ok and "partial" in reason

    def test_legacy_bare_int_done_file_still_verifies(self, tmp_path):
        st = PosixDiskStorage()
        _write_step(st, str(tmp_path), 3)
        done = os.path.join(
            step_dir(str(tmp_path), 3), saver_mod.DONE_DIR, "0.done"
        )
        open(done, "w").write("1")  # pre-checksum format: shard count
        ok, reason = verify_step_dir(st, str(tmp_path), 3)
        assert ok, reason

    def test_quarantine_moves_dir_out_of_restore_path(self, tmp_path):
        st = PosixDiskStorage()
        _write_step(st, str(tmp_path), 3)
        q = quarantine_step_dir(st, str(tmp_path), 3)
        assert q and q.endswith(".corrupt")
        assert not os.path.exists(step_dir(str(tmp_path), 3))
        assert os.path.exists(q)

    def test_rollback_to_newest_verified(self, tmp_path):
        st = PosixDiskStorage()
        for s in (1, 2, 3):
            _write_step(st, str(tmp_path), s)
        # corrupt the newest two
        for s in (2, 3):
            path = shard_file(str(tmp_path), s, 0)
            open(path, "ab").write(b"xx")  # length mismatch
        good = resolve_verified_step(st, str(tmp_path))
        assert good == 1
        assert read_tracker(st, str(tmp_path)) == 1
        assert read_history(st, str(tmp_path)) == [1]
        # both bad dirs quarantined
        names = os.listdir(tmp_path)
        assert sum(".corrupt" in n for n in names) == 2

    def test_no_verifiable_checkpoint_clears_tracker(self, tmp_path):
        st = PosixDiskStorage()
        _write_step(st, str(tmp_path), 1)
        open(shard_file(str(tmp_path), 1, 0), "wb").write(b"junk")
        assert resolve_verified_step(st, str(tmp_path)) == -1
        assert read_tracker(st, str(tmp_path)) == -1

    def test_repair_false_never_mutates(self, tmp_path):
        st = PosixDiskStorage()
        for s in (1, 2):
            _write_step(st, str(tmp_path), s)
        open(shard_file(str(tmp_path), 2, 0), "ab").write(b"x")
        assert resolve_verified_step(st, str(tmp_path), repair=False) == 1
        # non-repairing caller (shard id != 0) left everything in place
        assert read_tracker(st, str(tmp_path)) == 2
        assert os.path.exists(step_dir(str(tmp_path), 2))

    def test_history_is_bounded_and_gc_prunes(self, tmp_path):
        st = PosixDiskStorage()
        n = saver_mod.COMMIT_HISTORY_KEEP + 4
        for s in range(1, n + 1):
            _write_step(st, str(tmp_path), s)
        hist = read_history(st, str(tmp_path))
        assert len(hist) <= saver_mod.COMMIT_HISTORY_KEEP
        assert hist[-1] == n
        # commit-time GC dropped the dirs that fell out of the history
        dirs = [
            d for d in os.listdir(tmp_path) if d.startswith("step_")
        ]
        assert len(dirs) <= saver_mod.COMMIT_HISTORY_KEEP

    def test_gc_keeps_quarantine_budget(self, tmp_path):
        st = PosixDiskStorage()
        for s in (1, 2, 3, 4):
            _write_step(st, str(tmp_path), s)
        for s in (1, 2, 3):
            quarantine_step_dir(st, str(tmp_path), s)
        removed = gc_checkpoints(
            st, str(tmp_path), keep_quarantined=1
        )
        assert removed >= 2
        names = os.listdir(tmp_path)
        assert sum(".corrupt" in n for n in names) == 1

    def test_gc_never_touches_steps_newer_than_tracker(self, tmp_path):
        st = PosixDiskStorage()
        _write_step(st, str(tmp_path), 1)
        # an in-flight persist: dir exists, not yet committed
        st.safe_makedirs(step_dir(str(tmp_path), 9))
        gc_checkpoints(st, str(tmp_path), keep_steps=1)
        assert os.path.exists(step_dir(str(tmp_path), 9))

    def test_upgrade_from_tracker_only_keeps_fallback(self, tmp_path):
        """First commit after upgrading from the single-tracker protocol:
        pre-existing step dirs have no history file — GC must seed the
        history from them, not wipe every old step as 'untracked'."""
        st = PosixDiskStorage()
        for s in (1, 2, 3):
            _write_step(st, str(tmp_path), s)
        os.remove(os.path.join(str(tmp_path), saver_mod.HISTORY_FILE))
        _write_step(st, str(tmp_path), 4)  # first post-upgrade commit
        assert os.path.exists(step_dir(str(tmp_path), 3)), (
            "upgrade GC deleted the pre-history fallback step"
        )
        # the exact data-loss scenario: the new step is torn; restore
        # must fall back to a pre-history step, not to nothing
        path = shard_file(str(tmp_path), 4, 0)
        blob = open(path, "rb").read()
        open(path, "wb").write(blob[: len(blob) // 2])
        assert resolve_verified_step(st, str(tmp_path)) == 3

    def test_shallow_verify_lengths_only(self, tmp_path):
        """deep=False (non-repair ranks) checks completeness + lengths
        without reading blobs: torn writes caught, bit flips left to the
        repairing rank's one deep pass."""
        st = PosixDiskStorage()
        _write_step(st, str(tmp_path), 3)
        ok, reason = verify_step_dir(st, str(tmp_path), 3, deep=False)
        assert ok, reason
        path = shard_file(str(tmp_path), 3, 0)
        blob = bytearray(open(path, "rb").read())
        blob[len(blob) // 2] ^= 0x10
        open(path, "wb").write(bytes(blob))
        ok, _ = verify_step_dir(st, str(tmp_path), 3, deep=False)
        assert ok  # same length: shallow cannot see it...
        ok, _ = verify_step_dir(st, str(tmp_path), 3, deep=True)
        assert not ok  # ...the deep pass (repairing rank) does
        open(path, "wb").write(bytes(blob[: len(blob) // 2]))
        ok, reason = verify_step_dir(st, str(tmp_path), 3, deep=False)
        assert not ok and "torn" in reason


# ---------------------------------------------------------------------------
# chaos matrix: end-to-end detect -> rollback -> resume (sync engine path)
# ---------------------------------------------------------------------------
_TARGET = np.linspace(-1.0, 1.0, 8).astype(np.float32)


def _train(w, n):
    """Deterministic toy training (pure float32 SGD on a quadratic):
    bitwise-reproducible, so loss continuity can be asserted exactly."""
    losses = []
    for _ in range(n):
        w = (w - np.float32(0.1) * (w - _TARGET)).astype(np.float32)
        losses.append(float(np.square(w - _TARGET).sum()))
    return w, losses


class TestChaosMatrix:
    """One scenario per registered checkpoint fault point: the injected
    fault is detected, restore falls back to the newest verified step,
    and training resumed from it reproduces the clean run exactly."""

    @pytest.fixture(autouse=True)
    def _own_job(self, monkeypatch):
        """A job name nothing else serves: a launcher that another test
        file left winding down in this xdist worker still answers on the
        worker's checkpoint endpoints, and the engine would then take
        the agent path (seen once under six workers)."""
        monkeypatch.setenv(
            "DLROVER_TPU_JOB_NAME", f"chaosmatrix{os.getpid()}"
        )

    def _ckptr(self, tmp_path):
        AsyncCheckpointSaver.reset()  # force the sync (no-agent) path
        ckptr = FlashCheckpointer(str(tmp_path / "ckpt"))
        assert not ckptr.engine._agent_mode
        return ckptr

    def _save(self, ckptr, step, w):
        return ckptr.save_checkpoint(
            step, {"w": jnp.asarray(w), "step": step}, StorageType.DISK
        )

    def _run_scenario(self, tmp_path, arm_spec, save2_ok=None):
        """Clean save at step 4; faulted save at step 8; 'crash';
        restore must land on step 4 and retraining must reproduce the
        uninterrupted trajectory."""
        ckptr = self._ckptr(tmp_path)
        w0 = np.zeros(8, np.float32)
        w4, _ = _train(w0, 4)
        assert self._save(ckptr, 4, w4)
        w8_clean, losses_clean = _train(w4, 4)

        faults.configure(arm_spec)
        ok = self._save(ckptr, 8, w8_clean)
        if save2_ok is not None:
            assert ok is save2_ok
        faults.reset()
        assert faults.active() is False

        # "crash + restart": a fresh load must roll back to step 4 —
        # never silently restore a corrupt/unpublished step 8
        target = {"w": jnp.zeros(8, jnp.float32), "step": 0}
        step, state = ckptr.load_checkpoint(target)
        assert step == 4, f"expected rollback to 4, got {step}"
        np.testing.assert_array_equal(np.asarray(state["w"]), w4)

        # loss continuity: resume from the restored state
        _, losses_resumed = _train(
            np.asarray(state["w"], np.float32), 4
        )
        assert losses_resumed == losses_clean
        return ckptr

    def test_shard_write_torn(self, tmp_path):
        ckptr = self._run_scenario(
            tmp_path, "ckpt.shard_write:torn_write:1.0:11", save2_ok=True
        )
        assert faults.triggered() == {}  # reset cleared the tally
        # the corrupt step was quarantined, not deleted silently
        names = os.listdir(ckptr.checkpoint_dir)
        assert any(".corrupt" in n for n in names)

    def test_shard_write_bit_flip(self, tmp_path):
        self._run_scenario(
            tmp_path, "ckpt.shard_write:bit_flip:1.0:12", save2_ok=True
        )

    def test_done_write_io_error(self, tmp_path):
        # crash-between-shard-and-done: shard landed, done never did,
        # step never published -> restore ignores it
        ckptr = self._run_scenario(
            tmp_path, "ckpt.done_write:io_error:1.0", save2_ok=False
        )
        assert read_tracker(
            ckptr.engine.storage, ckptr.checkpoint_dir
        ) == 4

    def test_tracker_write_enospc(self, tmp_path):
        # crash-before-tracker: fully valid step dir, never published
        self._run_scenario(
            tmp_path, "ckpt.tracker_write:enospc:1.0", save2_ok=False
        )

    def test_persist_enospc_training_continues(self, tmp_path):
        # disk full before anything is written: save reports False (the
        # train loop keeps going), previous verified step stays live
        ckptr = self._run_scenario(
            tmp_path, "ckpt.persist:enospc:1.0", save2_ok=False
        )
        # metric visible in the registry
        from dlrover_tpu.obs.metrics import default_registry

        assert (
            default_registry()
            .counter("dlrover_ckpt_persist_failures_total")
            .value
            >= 1
        )
        # the failed save left nothing: a later healthy save commits
        w8, _ = _train(np.zeros(8, np.float32), 8)
        assert self._save(ckptr, 8, w8)
        assert ckptr.latest_verified_step() == 8

    def test_corrupt_newest_never_silently_restores(self, tmp_path):
        """Paranoia variant: BOTH saved steps corrupt -> load must say
        'no checkpoint', not hand back bad bytes."""
        ckptr = self._ckptr(tmp_path)
        faults.configure("ckpt.shard_write:bit_flip:1.0:13")
        for s in (4, 8):
            w, _ = _train(np.zeros(8, np.float32), s)
            assert self._save(ckptr, s, w)
        faults.reset()
        step, state = ckptr.load_checkpoint(
            {"w": jnp.zeros(8, jnp.float32), "step": 0}
        )
        assert step == -1 and state is None


# ---------------------------------------------------------------------------
# agent path: shm corruption, degraded mode, shard-thread fast-fail
# ---------------------------------------------------------------------------
def _wait(pred, timeout=30.0, interval=0.05):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if pred():
            return True
        time.sleep(interval)
    return False


class TestAgentFaults:
    def test_shm_stage_bit_flip_detected_and_storage_fallback(
        self, saver, tmp_path
    ):
        events = []
        saver.set_event_reporter(lambda ev, msg: events.append((ev, msg)))
        ckpt_dir = str(tmp_path / "ckpt")
        engine = CheckpointEngine()
        assert engine._agent_mode
        state = {"w": jnp.arange(16.0), "step": 1}
        # clean step 1 on storage
        assert engine.save_to_memory(1, state, ckpt_dir)
        assert _wait(lambda: engine.latest_step(ckpt_dir) == 1)

        # step 2 staged through a corrupting shm write: the writer's
        # crc is computed before the bytes rot, so the saver detects it
        faults.configure("ckpt.shm_stage:bit_flip:1.0:21")
        state2 = {"w": jnp.arange(16.0) * 2, "step": 2}
        assert engine.save_to_memory(2, state2, ckpt_dir)
        assert _wait(
            lambda: faults.triggered_total() > 0
            and ("ckpt.shm_stage", "bit_flip") in faults.triggered()
        )
        # corrupt shm must never reach storage
        assert _wait(lambda: not saver._persist_mutex.locked())
        faults.reset()
        assert not os.path.exists(shard_file(ckpt_dir, 2, 0))
        assert engine.latest_step(ckpt_dir) == 1
        # shm corruption is its own incident — NOT storage-degraded
        # mode (storage is healthy; shm is the bad copy)
        assert _wait(lambda: events)
        assert events[0][0] == "ckpt_shm_corrupt"
        assert not saver.degraded

        # restore: the shm proposal fails verification and downgrades
        # to the storage path -> step 1, original bytes
        step, restored = engine.load(state, ckpt_dir)
        assert step == 1
        np.testing.assert_array_equal(
            np.asarray(restored["w"]), np.arange(16.0)
        )

    def test_persistent_enospc_enters_degraded_mode(self, saver, tmp_path):
        from dlrover_tpu.obs.metrics import default_registry

        events = []
        saver.set_event_reporter(lambda ev, msg: events.append((ev, msg)))
        ckpt_dir = str(tmp_path / "ckpt")
        engine = CheckpointEngine()
        state = {"w": jnp.arange(8.0), "step": 1}

        faults.configure("ckpt.persist:enospc:1.0")
        assert engine.save_to_memory(1, state, ckpt_dir)
        assert _wait(lambda: saver.degraded), "never entered degraded mode"
        # visible in the metrics registry + as a master-bound node event
        gauge = default_registry().gauge("dlrover_ckpt_degraded_mode")
        assert gauge.value == 1.0
        assert events and events[0][0] == "ckpt_degraded"
        # nothing reached storage, commit never started
        assert engine.latest_step(ckpt_dir) == -1

        # training continues: shm-only saves still work while degraded
        faults.reset()
        state2 = {"w": jnp.arange(8.0) + 1, "step": 2}
        assert _wait(
            lambda: engine.save_to_memory(2, state2, ckpt_dir),
            timeout=30,
            interval=0.2,
        ), "save never accepted after degraded entry"
        # first healthy persist exits the mode and reports recovery
        assert _wait(lambda: not saver.degraded), "never recovered"
        assert gauge.value == 0.0
        assert ("ckpt_degraded_recovered" in {e for e, _ in events})
        assert _wait(lambda: engine.latest_step(ckpt_dir) == 2)

    def test_shard_thread_failure_fast_fails_commit(self, saver, tmp_path):
        """An exception in a per-shard persist thread must surface
        immediately — no commit thread waiting out a 600s timeout for a
        done file that will never arrive."""
        events = []
        saver.set_event_reporter(lambda ev, msg: events.append((ev, msg)))
        ckpt_dir = str(tmp_path / "ckpt")
        engine = CheckpointEngine()
        faults.configure("ckpt.shard_write:io_error:1.0")
        t0 = time.time()
        assert engine.save_to_memory(
            3, {"w": jnp.arange(4.0)}, ckpt_dir
        )
        assert _wait(lambda: len(events) > 0), "failure never surfaced"
        elapsed = time.time() - t0
        assert elapsed < 30, f"fast-fail took {elapsed:.1f}s"
        # the failure names the shard and no commit was attempted
        assert "shard 0" in events[0][1]
        assert not saver._commit_threads
        assert read_tracker(saver.storage, ckpt_dir) == -1
        faults.reset()

    def test_master_records_degraded_node_event(self):
        """run.py wires saver events to report_failure(level=warning);
        the master must surface that as a queryable node event, not a
        relaunch."""
        from dlrover_tpu.master.local_master import LocalJobMaster
        from dlrover_tpu.agent.master_client import MasterClient

        m = LocalJobMaster(port=0, node_num=1)
        m.prepare()
        try:
            c = MasterClient(m.addr, node_id=0)
            c.report_failure(
                "ckpt_degraded: step 7: shard 0: ENOSPC", level="warning"
            )
            assert _wait(
                lambda: m.job_manager.node_events("ckpt_degraded"),
                timeout=10,
            )
            ev = m.job_manager.node_events("ckpt_degraded")[0]
            assert ev["node_id"] == 0
            assert "ENOSPC" in ev["detail"]
            # a warning never marks the node broken
            node = m.job_manager.get_node("worker", 0)
            assert node is not None and not node.is_released
            c.close()
        finally:
            m.stop()


# ---------------------------------------------------------------------------
# chunked-stager crc: end-to-end shm integrity for the incremental path
# ---------------------------------------------------------------------------
class TestChunkedStagerIntegrity:
    def test_chunked_commit_publishes_record_crcs(self, saver, tmp_path):
        engine = CheckpointEngine()
        state = {"w": jnp.arange(4096.0), "b": jnp.ones(7)}
        stager = engine.begin_chunked_save(
            5, state, str(tmp_path / "ck"), chunk_bytes=1 << 10
        )
        assert stager is not None
        while stager.advance(budget_s=0.01):
            pass
        assert stager.commit()
        metas = saver._shm_handlers[0].metadata()["records"]
        assert metas and all(m.get("crc32") is not None for m in metas)
        # and the saver's verify accepts them
        step, records, _ = saver._shm_handlers[0].load_records(verify=True)
        assert step == 5

    def test_chunked_stage_corruption_detected(self, saver, tmp_path):
        engine = CheckpointEngine()
        ckpt_dir = str(tmp_path / "ck")
        faults.configure("ckpt.shm_stage:bit_flip:1.0:31")
        stager = engine.begin_chunked_save(
            6, {"w": jnp.arange(512.0)}, ckpt_dir, chunk_bytes=1 << 10
        )
        assert stager is not None
        assert stager.commit()
        faults.reset()
        with pytest.raises(ValueError, match="checksum"):
            saver._shm_handlers[0].load_records(verify=True)
        # and the saver refuses to persist the poisoned bytes
        assert _wait(lambda: not saver._persist_mutex.locked())
        assert _wait(
            lambda: not os.path.exists(shard_file(ckpt_dir, 6, 0)),
            timeout=5,
        )


# ---------------------------------------------------------------------------
# master-client retry hardening (satellite)
# ---------------------------------------------------------------------------
class TestMasterClientRetries:
    def _client(self):
        from dlrover_tpu.agent.master_client import MasterClient

        return MasterClient("localhost:1", node_id=0)

    def test_full_jitter_backoff(self, monkeypatch):
        import grpc

        c = self._client()
        bounds, sleeps = [], []
        monkeypatch.setattr(
            "dlrover_tpu.agent.master_client.random.uniform",
            lambda a, b: (bounds.append((a, b)) or 0.0),
        )
        monkeypatch.setattr(
            "dlrover_tpu.agent.master_client.time.sleep",
            lambda s: sleeps.append(s),
        )
        calls = []

        def rpc(payload, timeout=None):
            calls.append(1)
            raise grpc.RpcError("down")

        with pytest.raises(ConnectionError):
            c._call(rpc, "msg", retries=3)
        assert len(calls) == 3
        # full jitter: uniform over [0, 2**i] capped at 8
        assert bounds == [(0.0, 1.0), (0.0, 2.0)]
        c.close()

    def test_retry_budget_bounds_total_attempts(self, monkeypatch):
        import grpc

        c = self._client()
        calls = []

        def rpc(payload, timeout=None):
            calls.append(1)
            raise grpc.RpcError("down")

        with pytest.raises(ConnectionError):
            c._call(rpc, "msg", retries=5, retry_budget_s=0.0)
        assert len(calls) == 1, "exhausted budget must stop retrying"
        c.close()

    def test_non_idempotent_report_single_attempt(self):
        import grpc

        c = self._client()
        calls = []

        def rpc(payload, timeout=None):
            calls.append(1)
            raise grpc.RpcError("down")

        c._report_rpc = rpc
        with pytest.raises(ConnectionError):
            c.report("msg", retries=5, idempotent=False)
        assert len(calls) == 1
        c.close()

    def test_rpc_send_fault_point_rides_retry_path(self, monkeypatch):
        c = self._client()
        monkeypatch.setattr(
            "dlrover_tpu.agent.master_client.time.sleep", lambda s: None
        )
        served = []

        def rpc(payload, timeout=None):
            served.append(1)
            raise AssertionError("must not reach the wire")

        # every attempt's injected OSError is retried like a flaky net
        faults.configure("rpc.send:io_error:1.0")
        with pytest.raises(ConnectionError):
            c._call(rpc, "msg", retries=3)
        assert not served
        assert faults.triggered()[("rpc.send", "io_error")] == 3
        c.close()


# ---------------------------------------------------------------------------
# prefetch / reshard fault sites
# ---------------------------------------------------------------------------
class TestPipelineFaultSites:
    def test_prefetch_pull_fault_propagates_in_order(self):
        from dlrover_tpu.data.prefetch import DevicePrefetcher

        faults.configure("prefetch.pull:io_error:1.0")
        pf = DevicePrefetcher(iter([np.ones(2)]), placement=lambda x: x)
        try:
            with pytest.raises(OSError):
                for _ in pf:
                    pass
        finally:
            pf.close()
        assert ("prefetch.pull", "io_error") in faults.triggered()

    def test_reshard_gather_fault_raises(self):
        from dlrover_tpu.ckpt.reshard import reshard_state

        faults.configure("reshard.gather:io_error:1.0")
        state = {"w": np.ones(4, np.float32)}
        with pytest.raises(OSError):
            reshard_state(state, state)
