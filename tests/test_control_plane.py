"""Control-plane scale-out tests (ISSUE 14 tentpole a): the delta
telemetry codec, the batched AgentReportBatch dispatch, the agent
aggregation-tier daemon, channel hardening (keepalive + gzip), the
client-side RPC brownout counters, and the rpc_load harness."""

import json
import os
import sys
import time

import grpc
import numpy as np
import pytest

from dlrover_tpu.agent.aggregator import AgentReportBatcher
from dlrover_tpu.agent.master_client import MasterClient
from dlrover_tpu.agent.monitor import (
    read_worker_commands,
    report_runtime_metrics,
)
from dlrover_tpu.common import comm
from dlrover_tpu.common.telemetry_delta import DeltaDecoder, DeltaEncoder
from dlrover_tpu.master.servicer import MasterServicer, create_master_service

sys.path.insert(
    0, os.path.join(os.path.dirname(os.path.dirname(__file__)), "tools")
)


# ---------------------------------------------------------------------------
# delta codec
# ---------------------------------------------------------------------------
class TestDeltaCodec:
    def test_full_then_delta_roundtrip(self):
        enc = DeltaEncoder()
        dec = DeltaDecoder()
        s1 = {"a": 1.0, "b": 2.0}
        full, seq, d = enc.encode({0: s1})
        assert full and seq == 1
        out = dec.apply(7, enc.epoch, seq, full, d)
        assert out == {0: s1}
        enc.ack(seq)
        # change one key, add one, remove one
        s2 = {"a": 1.5, "c": 3.0}
        full, seq, d = enc.encode({0: s2})
        assert not full
        changed, removed = d[0]
        assert changed == {"a": 1.5, "c": 3.0}
        assert removed == ["b"]
        out = dec.apply(7, enc.epoch, seq, full, d)
        assert out == {0: s2}
        assert dec.snapshot(7) == {0: s2}

    def test_unchanged_keys_not_resent(self):
        enc = DeltaEncoder()
        snap = {f"k{i}": float(i) for i in range(50)}
        _, seq, _ = enc.encode({0: snap})
        enc.ack(seq)
        snap2 = dict(snap, k3=99.0)
        full, seq, d = enc.encode({0: snap2})
        assert not full
        assert d[0][0] == {"k3": 99.0}  # ONLY the changed key
        # no change at all → no entry for the proc
        enc.ack(seq)
        full, seq, d = enc.encode({0: snap2})
        assert d == {}

    def test_rollback_arms_full_snapshot(self):
        """A transport failure makes the next batch a full snapshot:
        whether or not the master applied the lost batch, a snapshot
        converges (re-encoding a delta could diverge)."""
        enc = DeltaEncoder()
        _, seq, _ = enc.encode({0: {"a": 1.0}})
        enc.ack(seq)
        _, seq, d = enc.encode({0: {"a": 2.0}})
        enc.rollback(seq)  # send failed
        full, seq2, d2 = enc.encode({0: {"a": 2.0, "b": 1.0}})
        assert full  # snapshot, not a recomputed delta
        assert d2[0][0] == {"a": 2.0, "b": 1.0}

    def test_rollback_converges_when_value_reverts(self):
        """The divergence the full-snapshot recovery exists for: the
        master APPLIED the lost batch, and the changed key reverted to
        its acked value before the resend. A recomputed delta would
        omit the key and strand the master at the stale value; the
        snapshot overwrites it."""
        enc = DeltaEncoder()
        dec = DeltaDecoder()
        full, seq, d = enc.encode({0: {"gauge": 0.0}})
        dec.apply(1, enc.epoch, seq, full, d)
        enc.ack(seq)
        # gauge flips to 1; master applies it but the response is lost
        full, seq, d = enc.encode({0: {"gauge": 1.0}})
        dec.apply(1, enc.epoch, seq, full, d)
        enc.rollback(seq)
        # gauge reverts to 0 before the retry
        full, seq, d = enc.encode({0: {"gauge": 0.0}})
        out = dec.apply(1, enc.epoch, seq, full, d)
        assert out == {0: {"gauge": 0.0}}  # master converged
        assert dec.snapshot(1) == {0: {"gauge": 0.0}}

    def test_same_seq_replay_is_idempotent(self):
        """A lost RESPONSE: the master applied seq N, the client
        resends N — the decoder re-applies without resync."""
        enc = DeltaEncoder()
        dec = DeltaDecoder()
        full, seq, d = enc.encode({0: {"a": 1.0}})
        dec.apply(1, enc.epoch, seq, full, d)
        enc.ack(seq)
        full, seq, d = enc.encode({0: {"a": 2.0}})
        assert dec.apply(1, enc.epoch, seq, full, d) == {0: {"a": 2.0}}
        # replay (response lost, client resent the same seq)
        assert dec.apply(1, enc.epoch, seq, full, d) == {0: {"a": 2.0}}
        assert dec.replays == 1
        assert dec.resyncs == 0

    def test_epoch_mismatch_and_gap_force_resync(self):
        dec = DeltaDecoder()
        enc = DeltaEncoder()
        full, seq, d = enc.encode({0: {"a": 1.0}})
        dec.apply(1, enc.epoch, seq, full, d)
        enc.ack(seq)
        # wrong epoch
        assert dec.apply(1, enc.epoch + 1, 2, False, {0: ({"a": 2.0}, [])}) is None
        # seq gap
        assert dec.apply(1, enc.epoch, 5, False, {0: ({"a": 2.0}, [])}) is None
        # unknown node
        assert dec.apply(9, enc.epoch, 2, False, {0: ({}, [])}) is None
        assert dec.resyncs == 3
        # resync converges: fresh epoch, full snapshot
        enc.force_resync()
        full, seq, d = enc.encode({0: {"a": 2.0}})
        assert full and seq == 1
        assert dec.apply(1, enc.epoch, seq, full, d) == {0: {"a": 2.0}}

    def test_vanished_proc_removes_all_keys(self):
        enc = DeltaEncoder()
        dec = DeltaDecoder()
        full, seq, d = enc.encode({0: {"a": 1.0}, 1: {"b": 2.0}})
        dec.apply(1, enc.epoch, seq, full, d)
        enc.ack(seq)
        full, seq, d = enc.encode({0: {"a": 1.0}})  # proc 1 gone
        assert d[1] == ({}, ["b"])
        out = dec.apply(1, enc.epoch, seq, full, d)
        assert out[1] == {}
        assert dec.snapshot(1) == {0: {"a": 1.0}}  # no ghost scalars

    def test_fresh_epochs_differ(self):
        assert DeltaEncoder().epoch != DeltaEncoder().epoch


# ---------------------------------------------------------------------------
# comm serialization round trips (every new message)
# ---------------------------------------------------------------------------
class TestCommRoundTrip:
    @pytest.mark.parametrize(
        "msg",
        [
            comm.ProcDelta(
                proc_id=2,
                worker_id=5,
                step=42,
                step_ts=1.5,
                step_advanced=True,
                changed={"loss": 0.5, 'g{c="x"}': 1.0},
                removed=["stale"],
                open_span="ckpt_commit",
                open_span_elapsed_s=3.25,
            ),
            comm.AgentReportBatch(
                node_id=3,
                epoch=12345,
                seq=7,
                full=True,
                procs=[comm.ProcDelta(proc_id=0, changed={"a": 1.0})],
                command_ack_id=9,
                paral_version=2,
                resource=comm.ResourceStats(
                    node_id=3, cpu_percent=51.0, used_memory_mb=2048
                ),
            ),
            comm.AgentBatchResponse(
                resync=True,
                commands=[
                    comm.WorkerCommand(id=1, kind="flight_dump", arg=3)
                ],
                paral_config=comm.ParallelConfig(),
            ),
        ],
        ids=lambda m: type(m).__name__,
    )
    def test_roundtrip(self, msg):
        assert comm.deserialize_message(comm.serialize_message(msg)) == msg


# ---------------------------------------------------------------------------
# servicer dispatch
# ---------------------------------------------------------------------------
class _Collector:
    def __init__(self):
        self.metrics = {}
        self.calls = 0

    def report_train_metrics(self, worker_id, step, metrics):
        self.metrics[worker_id] = (step, dict(metrics))
        self.calls += 1


class _Speed:
    def __init__(self):
        self.steps = []

    def collect_global_step(self, step, ts=None, node_id=0):
        self.steps.append((node_id, step, ts))


class _Telemetry:
    def __init__(self):
        self.observed = []

    def observe_metrics(
        self, worker_id, step, metrics, open_span="",
        open_span_elapsed_s=0.0,
    ):
        self.observed.append(
            (worker_id, step, dict(metrics), open_span)
        )


class _ParalService:
    def __init__(self, version=3):
        self.cfg = comm.ParallelConfig()
        self.cfg.dataloader.version = version
        self.cfg.dataloader.batch_size = 32

    def get_config(self, node_id):
        return self.cfg


def _dispatch(servicer, message, node_id=3, rpc="report"):
    req = comm.serialize_message(
        comm.BaseRequest(
            node_id=node_id,
            node_type="worker",
            data=comm.serialize_message(message),
        )
    )
    fn = servicer.report if rpc == "report" else servicer.get
    resp = comm.deserialize_message(fn(req))
    assert resp.success, resp.message
    return comm.deserialize_message(resp.data)


class TestServicerBatchDispatch:
    def _servicer(self, paral=None):
        self.collector = _Collector()
        self.speed = _Speed()
        self.telemetry = _Telemetry()
        return MasterServicer(
            metric_collector=self.collector,
            speed_monitor=self.speed,
            telemetry=self.telemetry,
            paral_config_service=paral,
        )

    def _batch(self, enc, scalars, step=10, advanced=True, node_id=3):
        full, seq, d = enc.encode({0: scalars})
        changed, removed = d.get(0, ({}, []))
        return comm.AgentReportBatch(
            node_id=node_id,
            epoch=enc.epoch,
            seq=seq,
            full=full,
            procs=[
                comm.ProcDelta(
                    proc_id=0,
                    step=step,
                    step_ts=float(step),
                    step_advanced=advanced,
                    changed=changed,
                    removed=removed,
                    open_span="compute",
                )
            ],
        )

    def test_batch_forwards_reconstructed_full_scalars(self):
        s = self._servicer()
        enc = DeltaEncoder()
        scalars = {"loss": 1.0, "lr": 0.1}
        resp = _dispatch(s, self._batch(enc, scalars))
        assert isinstance(resp, comm.AgentBatchResponse)
        assert not resp.resync
        enc.ack(enc.seq)
        assert self.collector.metrics[3] == (10, scalars)
        assert self.speed.steps == [(3, 10, 10.0)]
        # delta tick: master forwards the FULL reconstruction
        scalars2 = dict(scalars, loss=0.9)
        resp = _dispatch(s, self._batch(enc, scalars2, step=11))
        assert not resp.resync
        assert self.collector.metrics[3] == (11, scalars2)
        assert self.telemetry.observed[-1][2] == scalars2
        assert self.telemetry.observed[-1][3] == "compute"

    def test_step_advanced_gates_speed_monitor(self):
        s = self._servicer()
        enc = DeltaEncoder()
        _dispatch(s, self._batch(enc, {"a": 1.0}, step=5))
        enc.ack(enc.seq)
        n = len(self.speed.steps)
        _dispatch(
            s, self._batch(enc, {"a": 2.0}, step=5, advanced=False)
        )
        assert len(self.speed.steps) == n  # no re-report at same step

    def test_epoch_mismatch_forces_resync_and_converges(self):
        """The mixed-version/failover drill: a delta the master cannot
        reconstruct applies NOTHING, answers resync, and the client's
        full snapshot converges with no dropped scalars."""
        s = self._servicer()
        enc = DeltaEncoder()
        _dispatch(s, self._batch(enc, {"a": 1.0, "b": 2.0}))
        enc.ack(enc.seq)
        # master restarts: fresh decoder
        s._delta = DeltaDecoder()
        before = dict(self.collector.metrics[3][1])
        scalars = {"a": 1.5, "b": 2.0, "c": 3.0}
        resp = _dispatch(s, self._batch(enc, scalars, step=11))
        assert resp.resync
        # nothing applied from the unreconstructable delta
        assert self.collector.metrics[3][1] == before
        # client resyncs: full snapshot under a fresh epoch
        enc.force_resync()
        resp = _dispatch(s, self._batch(enc, scalars, step=11))
        assert not resp.resync
        assert self.collector.metrics[3] == (11, scalars)

    def test_old_format_reports_still_dispatch(self):
        """Mixed-version fleet: a legacy (non-batched, non-delta)
        client's reports hit the same sinks with full fidelity."""
        s = self._servicer()
        _dispatch(
            s,
            comm.TrainMetricsReport(
                node_id=4, step=7, metrics={"loss": 2.0}
            ),
            node_id=4,
        )
        _dispatch(
            s,
            comm.GlobalStepReport(node_id=4, step=7, timestamp=1.0),
            node_id=4,
        )
        assert self.collector.metrics[4] == (7, {"loss": 2.0})
        assert (4, 7, 1.0) in self.speed.steps
        # and a batched node coexists
        enc = DeltaEncoder()
        _dispatch(s, self._batch(enc, {"loss": 1.0}, node_id=5), node_id=5)
        assert self.collector.metrics[5] == (10, {"loss": 1.0})

    def test_command_leg_piggybacks_and_acks(self):
        s = self._servicer()
        enc = DeltaEncoder()
        cmd = s.queue_worker_command(3, "flight_dump", reason="test")
        resp = _dispatch(s, self._batch(enc, {"a": 1.0}))
        enc.ack(enc.seq)
        assert [c.id for c in resp.commands] == [cmd.id]
        # unacked → redelivered on the next batch
        b = self._batch(enc, {"a": 2.0})
        b.command_ack_id = 0
        resp = _dispatch(s, b)
        enc.ack(enc.seq)
        assert [c.id for c in resp.commands] == [cmd.id]
        # acked → cleared
        b = self._batch(enc, {"a": 3.0})
        b.command_ack_id = cmd.id
        resp = _dispatch(s, b)
        assert resp.commands == []
        assert 3 not in s._worker_commands

    def test_paral_config_leg_only_on_version_change(self):
        s = self._servicer(paral=_ParalService(version=3))
        enc = DeltaEncoder()
        b = self._batch(enc, {"a": 1.0})
        b.paral_version = 0  # stale
        resp = _dispatch(s, b)
        enc.ack(enc.seq)
        assert resp.paral_config is not None
        assert resp.paral_config.dataloader.version == 3
        b = self._batch(enc, {"a": 2.0})
        b.paral_version = 3  # current
        resp = _dispatch(s, b)
        assert resp.paral_config is None

    def test_resource_leg_forwards_to_job_manager(self):
        class _JM:
            def __init__(self):
                self.usage = None

            def update_node_resource_usage(self, t, nid, cpu, mem):
                self.usage = (t, nid, cpu, mem)

        jm = _JM()
        s = MasterServicer(job_manager=jm)
        enc = DeltaEncoder()
        full, seq, d = enc.encode({0: {}})
        b = comm.AgentReportBatch(
            node_id=3, epoch=enc.epoch, seq=seq, full=full,
            resource=comm.ResourceStats(
                node_id=3, cpu_percent=77.0, used_memory_mb=512
            ),
        )
        _dispatch(s, b)
        assert jm.usage == ("worker", 3, 77.0, 512)

    def test_rpc_metrics_recorded_per_message_type(self):
        s = self._servicer()
        _dispatch(
            s, comm.GlobalStepReport(node_id=1, step=1, timestamp=1.0)
        )
        c = s._rpc_obs.requests.labels("report", "GlobalStepReport")
        assert c.value >= 1
        h = s._rpc_obs.latency.labels("report", "GlobalStepReport")
        assert h.count >= 1 and h.sum > 0
        b = s._rpc_obs.bytes.labels("report", "GlobalStepReport", "in")
        assert b.value > 0


# ---------------------------------------------------------------------------
# agent aggregation tier (the batcher daemon)
# ---------------------------------------------------------------------------
class _LoopbackClient:
    """MasterClient stand-in that dispatches straight into a servicer
    (no gRPC): the batcher's protocol behavior, isolated."""

    def __init__(self, servicer, node_id=3):
        self._servicer = servicer
        self.node_id = node_id
        self.eviction_notices = []
        self.fail_next = 0

    def report_batch(self, batch):
        if self.fail_next > 0:
            self.fail_next -= 1
            raise ConnectionError("injected transport failure")
        resp = _dispatch(self._servicer, batch, node_id=self.node_id)
        return (
            resp
            if isinstance(resp, comm.AgentBatchResponse)
            else comm.AgentBatchResponse()
        )

    def report_eviction_notice(self, grace_s, drain_ms=0.0, reason=""):
        self.eviction_notices.append((grace_s, drain_ms, reason))


class TestAgentReportBatcher:
    def _setup(self, tmp_path, paral=None):
        self.collector = _Collector()
        self.speed = _Speed()
        self.telemetry = _Telemetry()
        self.servicer = MasterServicer(
            metric_collector=self.collector,
            speed_monitor=self.speed,
            telemetry=self.telemetry,
            paral_config_service=paral,
        )
        self.client = _LoopbackClient(self.servicer)
        self.mpath = str(tmp_path / "metrics.json")
        self.cpath = str(tmp_path / "commands.json")
        self.ppath = str(tmp_path / "paral.json")
        return AgentReportBatcher(
            self.client,
            procs=[(0, -1, self.mpath)],
            commands_path=self.cpath,
            paral_path=self.ppath,
        )

    def test_one_rpc_per_tick_with_delta(self, tmp_path):
        b = self._setup(tmp_path)
        report_runtime_metrics(5, path=self.mpath, loss=2.0, lr=0.1)
        b._tick()
        assert b.batches_sent == 1
        assert self.collector.metrics[3][1] == {"loss": 2.0, "lr": 0.1}
        assert self.speed.steps[-1][:2] == (3, 5)
        full_bytes = b.last_wire_bytes
        # one scalar changes: the delta tick is strictly smaller
        report_runtime_metrics(6, path=self.mpath, loss=1.5, lr=0.1)
        b._tick()
        assert b.batches_sent == 2
        assert b.last_wire_bytes < full_bytes
        assert self.collector.metrics[3][1] == {"loss": 1.5, "lr": 0.1}
        assert self.speed.steps[-1][:2] == (3, 6)
        # quiet tick: the batch still goes out (it IS the poll leg)
        # with no proc entries
        b._tick()
        assert b.batches_sent == 3
        assert self.collector.metrics[3][1] == {"loss": 1.5, "lr": 0.1}

    def test_resync_after_master_restart_converges(self, tmp_path):
        b = self._setup(tmp_path)
        report_runtime_metrics(5, path=self.mpath, loss=2.0)
        b._tick()
        self.servicer._delta = DeltaDecoder()  # master restart
        report_runtime_metrics(6, path=self.mpath, loss=1.0, acc=0.5)
        b._tick()  # delta rejected → resync armed
        assert b.resyncs == 1
        b._tick()  # full snapshot converges, even with no new advance
        assert self.collector.metrics[3][1] == {"loss": 1.0, "acc": 0.5}

    def test_transport_failure_rolls_back_and_resends(self, tmp_path):
        b = self._setup(tmp_path)
        report_runtime_metrics(5, path=self.mpath, loss=2.0)
        b._tick()
        report_runtime_metrics(6, path=self.mpath, loss=1.0)
        self.client.fail_next = 1
        b._tick()  # lost request: rolled back, nothing dropped
        b._tick()
        assert self.collector.metrics[3][1] == {"loss": 1.0}
        assert self.servicer._delta.resyncs == 0  # no gap, no resync

    def test_commands_ride_the_batch_into_the_file(self, tmp_path):
        b = self._setup(tmp_path)
        cmd = self.servicer.queue_worker_command(
            3, "profile", arg=12, reason="straggler"
        )
        report_runtime_metrics(5, path=self.mpath, loss=2.0)
        b._tick()
        cmds = read_worker_commands(self.cpath)
        assert [c["id"] for c in cmds] == [cmd.id]
        assert cmds[0]["kind"] == "profile" and cmds[0]["arg"] == 12
        # the ack watermark cleared it master-side on the next tick
        b._tick()
        assert 3 not in self.servicer._worker_commands

    def test_paral_config_rides_the_batch_into_the_file(self, tmp_path):
        """The batcher's DEFAULT paral_version (-1, 'I have nothing')
        must receive the config on its first tick — the legacy tuner's
        initial-write parity (regression: a -1 sentinel the servicer
        read as 'does not want' made the channel permanently dead)."""
        b = self._setup(tmp_path, paral=_ParalService(version=4))
        assert b._paral_version == -1
        report_runtime_metrics(5, path=self.mpath, loss=2.0)
        b._tick()
        with open(self.ppath) as f:
            cfg = json.load(f)
        assert cfg["dataloader"]["version"] == 4
        assert b._paral_version == 4

    def test_eviction_relayed_first_on_dedicated_rpc(self, tmp_path):
        b = self._setup(tmp_path)
        report_runtime_metrics(
            5, path=self.mpath, loss=2.0,
            eviction_pending=1.0, eviction_grace_s=30.0,
        )
        b._tick()
        assert self.client.eviction_notices == [(30.0, 0.0, "worker_drain")]
        b._tick()  # unchanged notice: not re-sent
        assert len(self.client.eviction_notices) == 1

    def test_eviction_memo_is_per_proc(self, tmp_path):
        """Two draining procs with different drain values must each be
        relayed ONCE — a shared memo would thrash and re-send both
        every tick."""
        servicer = MasterServicer()
        client = _LoopbackClient(servicer, node_id=2)
        p0 = str(tmp_path / "m0.json")
        p1 = str(tmp_path / "m1.json")
        b = AgentReportBatcher(
            client,
            procs=[(0, 20, p0), (1, 21, p1)],
            commands_path=str(tmp_path / "c.json"),
            paral_path=str(tmp_path / "p.json"),
        )
        for path, drain in ((p0, 120.0), (p1, 95.0)):
            report_runtime_metrics(
                5, path=path, eviction_pending=1.0,
                eviction_grace_s=30.0, eviction_drain_ms=drain,
            )
        b._tick()
        assert sorted(n[1] for n in client.eviction_notices) == [
            95.0, 120.0,
        ]
        b._tick()  # unchanged: nothing re-sent
        b._tick()
        assert len(client.eviction_notices) == 2

    def test_multi_proc_batch_attributes_per_worker(self, tmp_path):
        self.collector = _Collector()
        self.speed = _Speed()
        servicer = MasterServicer(
            metric_collector=self.collector, speed_monitor=self.speed
        )
        client = _LoopbackClient(servicer, node_id=2)
        p0 = str(tmp_path / "m0.json")
        p1 = str(tmp_path / "m1.json")
        b = AgentReportBatcher(
            client,
            procs=[(0, 20, p0), (1, 21, p1)],
            commands_path=str(tmp_path / "c.json"),
            paral_path=str(tmp_path / "p.json"),
        )
        report_runtime_metrics(5, path=p0, loss=1.0)
        report_runtime_metrics(7, path=p1, loss=3.0)
        b._tick()
        assert b.batches_sent == 1  # ONE rpc for both procs
        assert self.collector.metrics[20] == (5, {"loss": 1.0})
        assert self.collector.metrics[21] == (7, {"loss": 3.0})
        assert {(n, s) for n, s, _ in self.speed.steps} == {
            (20, 5), (21, 7),
        }


# ---------------------------------------------------------------------------
# channel hardening + client metrics (satellites)
# ---------------------------------------------------------------------------
class TestChannelHardening:
    def test_keepalive_options_present(self):
        opts = dict(MasterClient.KEEPALIVE_OPTIONS)
        assert opts["grpc.keepalive_time_ms"] > 0
        assert opts["grpc.keepalive_timeout_ms"] > 0
        assert opts["grpc.keepalive_permit_without_calls"] == 1

    def test_compression_flag(self):
        c = MasterClient("127.0.0.1:1", compression=True)
        assert c._compression == grpc.Compression.Gzip
        c.close()
        c = MasterClient("127.0.0.1:1", compression=False)
        assert c._compression == grpc.Compression.NoCompression
        c.close()

    def test_compression_env_default(self, monkeypatch):
        monkeypatch.setenv("DLROVER_TPU_RPC_COMPRESSION", "0")
        c = MasterClient("127.0.0.1:1")
        assert c._compression == grpc.Compression.NoCompression
        c.close()
        monkeypatch.delenv("DLROVER_TPU_RPC_COMPRESSION")
        c = MasterClient("127.0.0.1:1")
        assert c._compression == grpc.Compression.Gzip
        c.close()

    def test_large_telemetry_payload_roundtrips_compressed(self):
        """A big, compressible telemetry payload through a REAL gRPC
        channel with gzip on: the master receives every value intact
        (and the servicer's byte counters see the uncompressed payload
        — compression is transport-level)."""
        collector = _Collector()
        servicer = MasterServicer(metric_collector=collector)
        port = comm.find_free_port()
        server = create_master_service(port, servicer)
        client = MasterClient(
            f"127.0.0.1:{port}", node_id=1, compression=True
        )
        try:
            rng = np.random.default_rng(0)
            big = {
                f"dlrover_goodput_seconds{{category=\"cat_{i}\"}}":
                float(rng.random())
                for i in range(3000)
            }
            client.report_train_metrics(9, big)
            assert collector.metrics[1] == (9, big)
        finally:
            client.close()
            server.stop(grace=None)


class TestClientRpcMetrics:
    def test_unreachable_master_counts_attempts(self):
        from dlrover_tpu.agent.master_client import _ClientRpcObs

        obs = _ClientRpcObs.get()
        req0 = obs.requests.labels("GlobalStepReport").value
        retry0 = obs.retries.labels("GlobalStepReport").value
        unreach0 = obs.unreachable.labels("GlobalStepReport").value
        client = MasterClient("127.0.0.1:1", node_id=1, timeout=0.2)
        with pytest.raises(ConnectionError):
            client._call(
                client._report_rpc,
                comm.GlobalStepReport(node_id=1, step=1),
                retries=3,
                rpc_timeout=0.2,
                retry_budget_s=5.0,
            )
        client.close()
        assert obs.requests.labels("GlobalStepReport").value == req0 + 3
        assert obs.retries.labels("GlobalStepReport").value == retry0 + 2
        assert (
            obs.unreachable.labels("GlobalStepReport").value
            == unreach0 + 1
        )

    def test_bytes_counted_on_success(self):
        from dlrover_tpu.agent.master_client import _ClientRpcObs

        obs = _ClientRpcObs.get()
        out0 = obs.bytes.labels("out").value
        in0 = obs.bytes.labels("in").value
        servicer = MasterServicer()
        port = comm.find_free_port()
        server = create_master_service(port, servicer)
        client = MasterClient(f"127.0.0.1:{port}", node_id=1)
        try:
            client.report_global_step(3)
            assert obs.bytes.labels("out").value > out0
            assert obs.bytes.labels("in").value > in0
        finally:
            client.close()
            server.stop(grace=None)

    def test_brownout_counters_reach_flight_bundle_export(self):
        """The satellite's point: the counters live in the default
        registry, so the flight recorder's metrics.prom carries them."""
        from dlrover_tpu.obs.metrics import default_registry

        client = MasterClient("127.0.0.1:1", node_id=1, timeout=0.2)
        with pytest.raises(ConnectionError):
            client.report_global_step(1, )
        client.close()
        text = default_registry().prometheus_text()
        assert "dlrover_rpc_client_requests_total" in text
        assert "dlrover_rpc_client_unreachable_total" in text


# ---------------------------------------------------------------------------
# the load harness (small fleet; 10k is slow; tools/rpc_load.py runs any)
# ---------------------------------------------------------------------------
class TestRpcLoadHarness:
    def test_delta_fleet_steady_state(self):
        from rpc_load import run_load

        r = run_load(nodes=24, ticks=4, nscalars=40, churn=0.1,
                     mode="delta", pool=8)
        assert r["rpcs_per_node_per_tick"] == 1.0
        assert r["reconstructed_ok"], r
        assert r["resyncs"] == 0
        assert r["rpc_p99_ms"] > 0
        assert r["master_service_s_per_tick"] > 0

    def test_delta_beats_full_on_wire(self):
        from rpc_load import run_load

        kw = dict(nodes=16, ticks=6, nscalars=60, churn=0.1, pool=8)
        delta = run_load(mode="delta", **kw)
        full = run_load(mode="full", **kw)
        assert delta["reconstructed_ok"] and full["reconstructed_ok"]
        ratio = delta["wire_bytes_total"] / full["wire_bytes_total"]
        assert ratio < 0.6  # 0.4 and under at the 1k-node shape
        assert (
            delta["wire_bytes_steady_per_node_per_tick"]
            < full["wire_bytes_steady_per_node_per_tick"] * 0.4
        )

    def test_master_restart_drill_converges(self):
        from rpc_load import run_load

        r = run_load(nodes=16, ticks=4, nscalars=40, churn=0.1,
                     mode="delta", pool=8, master_restart_tick=2)
        assert r["resyncs"] == 16  # every node resynced exactly once
        assert r["reconstructed_ok"], r
        assert r["rpcs_per_node_per_tick"] <= 1.25

    def test_legacy_mode_measures_the_old_protocol(self):
        from rpc_load import run_load

        r = run_load(nodes=8, ticks=2, nscalars=20, churn=0.1,
                     mode="legacy", pool=8)
        assert r["rpcs_per_node_per_tick"] == 4.0
        assert r["reconstructed_ok"]

    @pytest.mark.slow
    def test_ten_k_fleet(self):
        """The 10k-worker tier: steady state must hold at scale."""
        from rpc_load import run_load

        r = run_load(nodes=10_000, ticks=2, nscalars=40, churn=0.1,
                     mode="delta", pool=32, verify_sample=64)
        assert r["rpcs_per_node_per_tick"] == 1.0
        assert r["reconstructed_ok"], r
