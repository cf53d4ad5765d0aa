"""Goodput ledger, crash flight recorder, worker-command channel, and
the cross-worker timeline merge (ISSUE 7).

Acceptance anchors:
- the ledger partitions wall time into the closed taxonomy with zero
  closure error on synthetic and live-span inputs (a running trainer's
  ledger against the ±1% gate: ``test_obs.py``);
- the fleet goodput number flows worker scalars → TelemetryAggregator
  → JobMetricCollector sample → Brain datastore (including schema
  migration of pre-goodput stores);
- an exception'd dump produces a complete bundle whose trace validates
  as Chrome JSON, and the hang watchdog dumps once per episode from
  its own thread;
- master-queued worker commands coalesce, drain exactly once, relay
  through the agent's command file, and execute idempotently in the
  trainer's poll;
- ``tools/merge_timeline.py`` re-bases per-worker traces onto one
  wall-clock axis and overlays master node events.
"""

import json
import os
import sqlite3
import threading
import time
from types import SimpleNamespace

import pytest

from dlrover_tpu.obs import flight_recorder as obs_flight
from dlrover_tpu.obs import goodput as obs_goodput
from dlrover_tpu.obs.flight_recorder import FlightRecorder, ProfilerCapture
from dlrover_tpu.obs.goodput import (
    CATEGORIES,
    GoodputLedger,
    GoodputReport,
    _merge,
    _subtract,
    compute_goodput_pct,
)
from dlrover_tpu.obs.metrics import MetricsRegistry
from dlrover_tpu.obs.trace import SpanTracer, validate_chrome_trace

MS = 1_000_000  # ns


def _put(tracer, name, start_ns, dur_ns, tid=1, depth=0):
    """Append one synthetic completed record (the drain/ledger input
    shape) without threading real sleeps through the hot path."""
    tracer._buf.append(
        (name, tid, start_ns, dur_ns, depth, None, next(tracer._seq))
    )
    tracer._appended += 1


class TestIntervalOps:
    def test_merge_sorts_and_coalesces(self):
        assert _merge([(5, 9), (0, 3), (2, 4), (9, 9)]) == [(0, 4), (5, 9)]

    def test_subtract_splits_and_clips(self):
        ivs = [(0, 10)]
        cover = [(2, 4), (6, 8)]
        assert _subtract(ivs, cover) == [(0, 2), (4, 6), (8, 10)]

    def test_subtract_total_cover(self):
        assert _subtract([(1, 5)], [(0, 10)]) == []

    def test_goodput_formula(self):
        assert compute_goodput_pct(30.0, 60.0) == 50.0
        assert compute_goodput_pct(1.0, 0.0) == 0.0
        assert compute_goodput_pct(-1.0, 10.0) == 0.0


class TestGoodputLedger:
    def _ledger(self, **kw):
        tr = SpanTracer(enabled=True)
        led = GoodputLedger(tracer=tr, **kw)
        # rewind the epoch 1s so synthetic records laid out "in the
        # past" fall inside the collectable window even when a test
        # snapshots with the real clock
        led._t0_ns -= 1_000 * MS
        led._last_ns -= 1_000 * MS
        t0 = led._last_ns
        return tr, led, t0

    def test_span_categories_attributed(self):
        tr, led, t0 = self._ledger()
        _put(tr, "compute", t0 + 10 * MS, 100 * MS)
        _put(tr, "data_wait", t0 + 120 * MS, 50 * MS)
        _put(tr, "ckpt_commit", t0 + 180 * MS, 40 * MS)
        rep = led.snapshot(now_ns=t0 + 300 * MS)
        assert rep.seconds["productive_compute"] == pytest.approx(0.100)
        assert rep.seconds["data_stall"] == pytest.approx(0.050)
        assert rep.seconds["ckpt_block"] == pytest.approx(0.040)
        assert rep.seconds["other"] == pytest.approx(0.110)
        assert rep.closure_error_pct == pytest.approx(0.0)

    def test_priority_makes_partition_disjoint(self):
        """ckpt_block outranks productive_compute: the overlapped part
        is claimed once, by the higher category."""
        tr, led, t0 = self._ledger()
        _put(tr, "compute", t0, 100 * MS)
        _put(tr, "ckpt_stage", t0 + 50 * MS, 100 * MS)  # overlaps 50ms
        rep = led.snapshot(now_ns=t0 + 200 * MS)
        assert rep.seconds["ckpt_block"] == pytest.approx(0.100)
        assert rep.seconds["productive_compute"] == pytest.approx(0.050)
        total = sum(rep.seconds.values())
        assert total == pytest.approx(rep.wall_s)

    def test_unknown_spans_land_in_other(self):
        tr, led, t0 = self._ledger()
        _put(tr, "eval", t0, 50 * MS)
        rep = led.snapshot(now_ns=t0 + 100 * MS)
        assert rep.seconds["other"] == pytest.approx(0.100)

    def test_tid_filter_ignores_other_threads(self):
        """The prefetcher's h2d overlaps compute by design — only the
        train thread's spans may claim wall time."""
        tr, led, t0 = self._ledger(tid_fn=lambda: 1)
        _put(tr, "compute", t0, 50 * MS, tid=1)
        _put(tr, "compute", t0, 80 * MS, tid=2)  # producer thread
        rep = led.snapshot(now_ns=t0 + 100 * MS)
        assert rep.seconds["productive_compute"] == pytest.approx(0.050)

    def test_incremental_collect_never_double_counts(self):
        tr, led, t0 = self._ledger()
        _put(tr, "compute", t0, 40 * MS)
        led.collect(now_ns=t0 + 50 * MS)
        led.collect(now_ns=t0 + 60 * MS)  # same records still in ring
        rep = led.snapshot(now_ns=t0 + 100 * MS)
        assert rep.seconds["productive_compute"] == pytest.approx(0.040)

    def test_span_straddling_two_windows_clipped(self):
        tr, led, t0 = self._ledger()
        led.collect(now_ns=t0 + 50 * MS)  # window 1 ends mid-span
        _put(tr, "compute", t0 + 30 * MS, 60 * MS)  # lands after
        rep = led.snapshot(now_ns=t0 + 100 * MS)
        # only the [50,90) part falls in an uncounted window
        assert rep.seconds["productive_compute"] == pytest.approx(0.040)

    def test_open_span_counted_live_then_not_double_counted(self):
        """A wedged ckpt_commit shows up WHILE stuck; when it finally
        completes, the already-claimed window is not recounted."""
        tr, led, t0 = self._ledger()
        sp = tr.span("ckpt_commit")
        time.sleep(0.04)
        led.collect()
        with led._lock:
            mid = led._seconds["ckpt_block"]
        assert mid >= 0.03
        time.sleep(0.02)
        sp.end()
        rep = led.snapshot()
        dur = rep.seconds["ckpt_block"]
        assert dur >= mid
        assert dur <= rep.wall_s
        assert rep.closure_error_pct == pytest.approx(0.0, abs=1e-6)

    def test_replay_and_degraded_episodes(self):
        _, led, _ = self._ledger()
        led.replay_begin()
        time.sleep(0.03)
        led.replay_end()
        led.degraded_enter()
        time.sleep(0.02)
        led.degraded_exit()
        rep = led.snapshot()
        assert rep.seconds["restart_replay"] >= 0.025
        assert rep.seconds["degraded"] >= 0.015
        assert rep.closure_error_pct == pytest.approx(0.0, abs=1e-6)

    def test_live_episode_counted_while_open(self):
        _, led, _ = self._ledger()
        led.degraded_enter()
        time.sleep(0.03)
        rep = led.snapshot()
        assert rep.seconds["degraded"] >= 0.025
        # still open: the NEXT window keeps accruing without recount
        time.sleep(0.02)
        rep2 = led.snapshot()
        assert rep2.seconds["degraded"] >= rep.seconds["degraded"] + 0.015
        led.degraded_exit()

    def test_mark_interval_validates_category(self):
        _, led, _ = self._ledger()
        time.sleep(0.02)
        # a fully-elapsed interval (future portions are clipped to
        # "now" and carried into the next window)
        t = time.monotonic_ns() - 15 * MS
        led.mark_interval("restart_replay", t, t + 10 * MS)
        with pytest.raises(ValueError):
            led.mark_interval("productive_compute", t, t + MS)
        rep = led.snapshot()
        assert rep.seconds["restart_replay"] == pytest.approx(0.010)

    def test_export_publishes_gauges(self):
        tr, led, t0 = self._ledger()
        _put(tr, "compute", t0, 50 * MS)
        reg = MetricsRegistry()
        led.export(reg)
        scalars = reg.scalars()
        assert "dlrover_goodput_pct" in scalars
        assert "dlrover_goodput_wall_seconds" in scalars
        key = 'dlrover_goodput_seconds_total{category="productive_compute"}'
        assert scalars[key] == pytest.approx(0.050, abs=0.02)
        for cat in CATEGORIES:
            assert (
                f'dlrover_goodput_seconds_total{{category="{cat}"}}'
                in scalars
            )

    def test_note_degraded_seam(self, monkeypatch):
        _, led, _ = self._ledger()
        monkeypatch.setattr(obs_goodput, "_default", None)
        obs_goodput.note_degraded(True)  # no ledger: must not raise
        obs_goodput.install_default_ledger(led)
        obs_goodput.note_degraded(True)
        time.sleep(0.02)
        obs_goodput.note_degraded(False)
        assert led.snapshot().seconds["degraded"] >= 0.015

    def test_saver_degraded_exit_closes_ledger_episode(self):
        """The recovery side of the PR-5 seam: leaving degraded mode
        must close the ledger episode, or every second after recovery
        books as 'degraded' forever."""
        from dlrover_tpu.ckpt.saver import AsyncCheckpointSaver
        from dlrover_tpu.obs.goodput import install_default_ledger

        AsyncCheckpointSaver.reset()
        saver = AsyncCheckpointSaver.start_async_saving_ckpt(
            local_shard_num=1
        )
        try:
            led = GoodputLedger(tracer=SpanTracer(enabled=True))
            install_default_ledger(led)
            saver._degraded = True
            led.degraded_enter()  # what the entry hook did
            time.sleep(0.02)
            saver._exit_degraded(5)
            assert led._degraded_since is None
            booked = led.snapshot().seconds["degraded"]
            assert booked >= 0.015
            time.sleep(0.02)  # recovered: no further accrual
            assert led.snapshot().seconds["degraded"] == pytest.approx(
                booked, abs=1e-6
            )
        finally:
            AsyncCheckpointSaver.reset()

    def test_report_shapes(self):
        rep = GoodputReport(
            wall_s=10.0, seconds={"productive_compute": 5.0, "other": 5.0}
        )
        assert rep.goodput_pct == 50.0
        d = rep.as_dict()
        assert d["wall_s"] == 10.0 and d["goodput_pct"] == 50.0


class TestDrainAndWraparound:
    def test_drain_cursor_chain(self):
        tr = SpanTracer(enabled=True)
        for i in range(5):
            _put(tr, "compute", i, 1)
        recs, cur, dropped = tr.drain(0)
        assert len(recs) == 5 and dropped == 0
        for i in range(3):
            _put(tr, "compute", 10 + i, 1)
        recs2, cur2, dropped2 = tr.drain(cur)
        assert len(recs2) == 3 and dropped2 == 0
        assert tr.drain(cur2) == ([], cur2, 0)

    def test_drain_reports_lapped_records(self):
        tr = SpanTracer(enabled=True, capacity=16)
        for i in range(4):
            _put(tr, "compute", i, 1)
        _, cur, _ = tr.drain(0)
        for i in range(40):  # laps the 16-slot ring
            _put(tr, "compute", 100 + i, 1)
        recs, _, dropped = tr.drain(cur)
        assert len(recs) == 16
        assert dropped == 40 - 16

    def test_concurrent_export_no_torn_or_duplicate_records(self):
        """The satellite: the hot path lapping the exporter mid-drain
        must never tear a record or deliver one twice — every drained
        seq is unique, in order, and records+dropped accounts for
        every append."""
        tr = SpanTracer(enabled=True, capacity=64)
        stop = threading.Event()
        # prime the cursor chain: a cursor of 0 means "fresh consumer,
        # history is a starting point, not a loss" — the accounting
        # below needs the chain to start before the producers do
        _put(tr, "compute", 0, 1)
        seen = []
        recs, cursor, _ = tr.drain(0)
        seen.extend(r[6] for r in recs)

        def hot_path():
            while not stop.is_set():
                sp = tr.span("compute")
                sp.end()

        producers = [
            threading.Thread(target=hot_path, daemon=True)
            for _ in range(2)
        ]
        for p in producers:
            p.start()
        dropped_total = 0
        deadline = time.time() + 0.5
        while time.time() < deadline:
            recs, cursor, dropped = tr.drain(cursor)
            dropped_total += dropped
            seen.extend(r[6] for r in recs)
            for r in recs:
                assert len(r) == 7 and r[0] == "compute"  # not torn
        stop.set()
        for p in producers:
            p.join(timeout=2)
        assert len(seen) == len(set(seen)), "duplicated records"
        assert seen == sorted(seen), "out-of-order delivery"
        # exactly-once accounting over the whole run: everything ever
        # appended was either delivered or reported dropped (modulo
        # the tail still sitting in the ring)
        recs, cursor, dropped = tr.drain(cursor)
        seen.extend(r[6] for r in recs)
        dropped_total += dropped
        assert len(seen) + dropped_total == cursor

    def test_open_span_records_raw_timestamps(self):
        tr = SpanTracer(enabled=True)
        sp = tr.span("ckpt_commit")
        try:
            recs = tr.open_span_records()
            assert len(recs) == 1
            name, tid, start_ns, depth = recs[0]
            assert name == "ckpt_commit"
            assert tid == threading.get_ident()
            assert start_ns <= time.monotonic_ns()
        finally:
            sp.end()
        assert tr.open_span_records() == []


class TestHangAttributionHeartbeat:
    """Satellite: hang attribution when the heartbeat file is missing
    or stale."""

    class _FakeClient:
        def __init__(self):
            self.steps = []
            self.metric_calls = []

        def report_global_step(self, step):
            self.steps.append(step)

        def report_train_metrics(self, step, metrics, **kw):
            self.metric_calls.append((step, dict(metrics), kw))

    def test_missing_heartbeat_file_reports_nothing(
        self, tmp_path, monkeypatch
    ):
        from dlrover_tpu.agent.monitor import (
            TrainingMonitor,
            read_runtime_metrics,
        )

        path = str(tmp_path / "nope" / "metrics.json")
        monkeypatch.setenv("DLROVER_TPU_RUNTIME_METRICS_PATH", path)
        assert read_runtime_metrics(path) == {}
        client = self._FakeClient()
        mon = TrainingMonitor(client, interval=999)
        mon._tick()  # must not raise, must not report
        assert client.steps == [] and client.metric_calls == []

    def test_stale_heartbeat_stops_forwarding(
        self, tmp_path, monkeypatch
    ):
        """An unchanged payload timestamp (trainer AND heartbeat dead)
        must not keep re-forwarding the last snapshot."""
        from dlrover_tpu.agent.monitor import (
            TrainingMonitor,
            report_runtime_metrics,
        )

        path = str(tmp_path / "metrics.json")
        monkeypatch.setenv("DLROVER_TPU_RUNTIME_METRICS_PATH", path)
        client = self._FakeClient()
        mon = TrainingMonitor(client, interval=999)
        report_runtime_metrics(4, loss=1.0, span_heartbeat_ts=123.0)
        mon._tick()
        assert len(client.metric_calls) == 1
        mon._tick()  # file untouched since: stale
        mon._tick()
        assert len(client.metric_calls) == 1

    def test_attribution_without_any_span_report(self):
        from dlrover_tpu.obs.aggregate import TelemetryAggregator

        agg = TelemetryAggregator()
        # the worker reports steps but its heartbeat never published an
        # open span (missing heartbeat file on that host)
        agg.observe_step_report(3, 7, 1000.0)
        assert agg.hang_attribution() == {3: "no open span reported"}
        assert "worker 3 no open span reported" in agg.describe_hang()

    def test_stale_open_span_elapsed_keeps_advancing(self):
        """A worker that reported 'stuck in ckpt_commit for 10s' and
        then went silent is MORE stuck now, not frozen at 10s."""
        from dlrover_tpu.obs.aggregate import TelemetryAggregator

        agg = TelemetryAggregator()
        agg.observe_metrics(
            1, 5, {}, open_span="ckpt_commit", open_span_elapsed_s=10.0
        )
        time.sleep(0.05)
        name, elapsed = agg.last_open_span(1)
        assert name == "ckpt_commit"
        assert elapsed > 10.0
        assert "stuck in ckpt_commit" in agg.describe_hang()

    def test_empty_aggregator_describe_hang(self):
        from dlrover_tpu.obs.aggregate import TelemetryAggregator

        assert (
            TelemetryAggregator().describe_hang()
            == "no per-worker telemetry"
        )


class TestFlightRecorder:
    def _recorder(self, tmp_path, **kw):
        tr = SpanTracer(enabled=True)
        with tr.span("compute"):
            pass
        reg = MetricsRegistry()
        reg.gauge("dlrover_test_gauge", "g").set(1.0)
        rec = FlightRecorder(
            base_dir=str(tmp_path), tracer=tr, registry=reg,
            identity={"node_id": 3}, **kw,
        )
        return tr, reg, rec

    def test_dump_writes_complete_bundle(self, tmp_path):
        tr, reg, rec = self._recorder(tmp_path)
        rec.note_event("fault", "injected enospc")
        bundle = rec.dump("crash", exc=ValueError("boom"))
        assert bundle is not None and os.path.isdir(bundle)
        files = set(os.listdir(bundle))
        assert files == {
            "manifest.json", "trace.json", "metrics.prom",
            "stacks.txt", "events.json",
        }
        with open(os.path.join(bundle, "manifest.json")) as f:
            manifest = json.load(f)
        assert manifest["reason"] == "crash"
        assert manifest["identity"]["node_id"] == 3
        assert manifest["exception"]["type"] == "ValueError"
        assert "boom" in manifest["exception"]["message"]
        with open(os.path.join(bundle, "trace.json")) as f:
            ok, reason = validate_chrome_trace(json.load(f))
        assert ok, reason
        with open(os.path.join(bundle, "events.json")) as f:
            events = json.load(f)
        assert events[-1]["kind"] == "fault"
        with open(os.path.join(bundle, "stacks.txt")) as f:
            stacks = f.read()
        assert "MainThread" in stacks
        with open(os.path.join(bundle, "metrics.prom")) as f:
            assert "dlrover_test_gauge" in f.read()
        assert rec.dumps == [bundle]

    def test_rate_limit_folds_double_triggers(self, tmp_path):
        _, _, rec = self._recorder(tmp_path)
        first = rec.dump("hang")
        assert first is not None
        assert rec.dump("crash") is None  # < MIN_DUMP_INTERVAL_S later
        forced = rec.dump("crash", force=True)
        assert forced is not None and forced != first

    def test_open_span_lands_in_manifest(self, tmp_path):
        tr, _, rec = self._recorder(tmp_path)
        sp = tr.span("ckpt_commit")
        try:
            bundle = rec.dump("hang")
        finally:
            sp.end()
        with open(os.path.join(bundle, "manifest.json")) as f:
            manifest = json.load(f)
        assert any(
            s["name"] == "ckpt_commit" for s in manifest["open_spans"]
        )

    def test_watchdog_dumps_once_per_episode(self, tmp_path):
        tr, _, rec = self._recorder(tmp_path)
        sp = tr.span("ckpt_commit")
        # fake a 200s-old wedge: the watchdog must fire on its own
        # daemon thread — the "train thread" is conceptually stuck
        sp.start_ns -= 200_000_000_000
        try:
            rec.start_watchdog(hang_dump_after_s=60, interval_s=0.02)
            deadline = time.time() + 2
            while time.time() < deadline and not rec.dumps:
                time.sleep(0.02)
            assert len(rec.dumps) == 1
            time.sleep(0.2)  # same episode: no second dump
            assert len(rec.dumps) == 1
            assert any(e["kind"] == "hang" for e in rec.events())
        finally:
            rec.stop_watchdog()
            sp.end()

    def test_watchdog_quiet_below_threshold(self, tmp_path):
        tr, _, rec = self._recorder(tmp_path)
        sp = tr.span("compute")
        try:
            rec.start_watchdog(hang_dump_after_s=60, interval_s=0.02)
            time.sleep(0.15)
            assert rec.dumps == []
        finally:
            rec.stop_watchdog()
            sp.end()

    def test_degraded_note_event_triggers_dump(
        self, tmp_path, monkeypatch
    ):
        _, _, rec = self._recorder(tmp_path)
        monkeypatch.setattr(obs_flight, "_default", rec)
        obs_flight.note_event("ckpt_degraded", "step 9: enospc")
        assert len(rec.dumps) == 1
        obs_flight.note_event("restart", "not a dump trigger")
        assert len(rec.dumps) == 1
        assert [e["kind"] for e in rec.events()] == [
            "ckpt_degraded", "restart",
        ]

    def test_flight_dir_env_resolved_per_dump(
        self, tmp_path, monkeypatch
    ):
        tr = SpanTracer(enabled=True)
        rec = FlightRecorder(tracer=tr, registry=MetricsRegistry())
        monkeypatch.setenv(
            obs_flight.ENV_FLIGHT_DIR, str(tmp_path / "redirected")
        )
        bundle = rec.dump("manual")
        assert bundle is not None
        assert bundle.startswith(str(tmp_path / "redirected"))


class TestProfilerCapture:
    def _patched(self, monkeypatch, tmp_path):
        import jax

        calls = []
        monkeypatch.setattr(
            jax.profiler, "start_trace", lambda d: calls.append(("start", d))
        )
        monkeypatch.setattr(
            jax.profiler, "stop_trace", lambda: calls.append(("stop",))
        )
        return calls, ProfilerCapture(out_root=str(tmp_path))

    def test_capture_spans_k_steps(self, monkeypatch, tmp_path):
        calls, cap = self._patched(monkeypatch, tmp_path)
        assert cap.request(2, reason="straggler")
        assert not cap.request(2)  # already pending
        cap.on_step_begin()
        assert cap.active
        assert calls[0][0] == "start"
        cap.on_step_end()
        assert cap.active  # 1 of 2 steps done
        cap.on_step_end()
        assert not cap.active
        assert calls[-1] == ("stop",)
        assert len(cap.artifacts) == 1
        assert "straggler" in cap.artifacts[0]

    def test_cooldown_refuses_rerequest(self, monkeypatch, tmp_path):
        calls, cap = self._patched(monkeypatch, tmp_path)
        cap._cooldown_s = 300.0
        assert cap.request(1)
        cap.on_step_begin()
        cap.on_step_end()
        assert not cap.request(1)  # cooling down
        cap._cooldown_s = 0.0
        assert cap.request(1)

    def test_bad_steps_refused(self, monkeypatch, tmp_path):
        _, cap = self._patched(monkeypatch, tmp_path)
        assert not cap.request(0)
        assert not cap.request(-3)

    def test_abort_stops_live_capture(self, monkeypatch, tmp_path):
        calls, cap = self._patched(monkeypatch, tmp_path)
        cap.request(5)
        cap.on_step_begin()
        cap.abort()
        assert not cap.active
        assert calls[-1] == ("stop",)
        assert cap.artifacts == []  # aborted ≠ delivered


class TestWorkerCommandChannel:
    def _servicer(self):
        from dlrover_tpu.master.servicer import MasterServicer

        return MasterServicer()

    def test_queue_assigns_monotonic_ids_and_coalesces(self):
        s = self._servicer()
        c1 = s.queue_worker_command(0, "flight_dump", reason="hang")
        c2 = s.queue_worker_command(0, "flight_dump", reason="hang")
        c3 = s.queue_worker_command(0, "profile", arg=3, reason="straggler")
        c4 = s.queue_worker_command(1, "flight_dump", reason="hang")
        assert c1.id == c2.id  # coalesced while pending
        assert c3.id > c1.id and c4.id > c3.id

    def test_coalesce_takes_newest_arg(self):
        s = self._servicer()
        s.queue_worker_command(0, "profile", arg=3, reason="straggler")
        c = s.queue_worker_command(0, "profile", arg=20, reason="straggler")
        assert c.arg == 20  # the 20-step request must not shrink to 3

    def test_dispatch_redelivers_until_acked(self):
        """A lost RESPONSE must not drop a command: delivery without an
        ack redelivers; the ack (the next poll's ack_id) clears."""
        from dlrover_tpu.common import comm

        s = self._servicer()
        cmd = s.queue_worker_command(2, "profile", arg=5, reason="straggler")
        req = comm.BaseRequest(node_id=2)
        got = s._dispatch_get(req, comm.WorkerCommandRequest())
        assert isinstance(got, comm.WorkerCommands)
        assert [c.kind for c in got.commands] == ["profile"]
        assert got.commands[0].arg == 5
        # un-acked re-poll (the agent never saw the response): SAME
        # command comes back instead of vanishing
        again = s._dispatch_get(req, comm.WorkerCommandRequest())
        assert [c.id for c in again.commands] == [cmd.id]
        # acked poll clears it, and re-queueing works afterwards
        acked = s._dispatch_get(
            req, comm.WorkerCommandRequest(ack_id=cmd.id)
        )
        assert acked.commands == []
        s.queue_worker_command(2, "profile", arg=5, reason="straggler")
        assert len(
            s._dispatch_get(
                req, comm.WorkerCommandRequest(ack_id=cmd.id)
            ).commands
        ) == 1

    def test_no_coalesce_into_delivered_command(self):
        """A request arriving after delivery (but before the ack) must
        get a FRESH id — the trainer dedups by id, so folding into the
        delivered command would silently drop the new request."""
        from dlrover_tpu.common import comm

        s = self._servicer()
        c1 = s.queue_worker_command(0, "profile", arg=3, reason="straggler")
        req = comm.BaseRequest(node_id=0)
        s._dispatch_get(req, comm.WorkerCommandRequest())  # delivered
        c2 = s.queue_worker_command(0, "profile", arg=3, reason="straggler")
        assert c2.id > c1.id
        # both ride the next (still un-acked) poll
        got = s._dispatch_get(req, comm.WorkerCommandRequest())
        assert [c.id for c in got.commands] == [c1.id, c2.id]

    def test_clear_worker_commands_purges_queue(self):
        """The pre-restart purge: a pending command targets the dying
        incarnation and must not reach its replacement."""
        from dlrover_tpu.common import comm

        s = self._servicer()
        s.queue_worker_command(0, "flight_dump", reason="hang")
        s.queue_worker_command(1, "flight_dump", reason="hang")
        s.clear_worker_commands(1)
        req1 = comm.BaseRequest(node_id=1)
        assert s._dispatch_get(req1, comm.WorkerCommandRequest()).commands == []
        s.clear_worker_commands()
        req0 = comm.BaseRequest(node_id=0)
        assert s._dispatch_get(req0, comm.WorkerCommandRequest()).commands == []
        # the channel still works after a purge
        s.queue_worker_command(0, "flight_dump", reason="hang")
        assert len(
            s._dispatch_get(req0, comm.WorkerCommandRequest()).commands
        ) == 1

    def test_dispatch_explicit_node_id_wins(self):
        from dlrover_tpu.common import comm

        s = self._servicer()
        s.queue_worker_command(7, "flight_dump", reason="hang")
        got = s._dispatch_get(
            comm.BaseRequest(node_id=0),
            comm.WorkerCommandRequest(node_id=7),
        )
        assert len(got.commands) == 1

    def test_relay_mirrors_commands_to_file(self, tmp_path, monkeypatch):
        from dlrover_tpu.agent.monitor import (
            WorkerCommandRelay,
            read_worker_commands,
        )
        from dlrover_tpu.common import comm

        path = str(tmp_path / "cmds.json")
        monkeypatch.setenv("DLROVER_TPU_WORKER_COMMANDS_PATH", path)

        class _Client:
            def __init__(self):
                self.acks = []
                self.queue = [
                    comm.WorkerCommand(
                        id=1, kind="flight_dump", reason="hang"
                    ),
                    comm.WorkerCommand(
                        id=2, kind="profile", arg=3, reason="straggler"
                    ),
                ]

            def poll_worker_commands(self, ack_id=0):
                self.acks.append(ack_id)
                return [c for c in self.queue if c.id > ack_id]

        client = _Client()
        relay = WorkerCommandRelay(client, interval=999, keep=3)
        relay._tick()
        cmds = read_worker_commands(path)
        assert [c["kind"] for c in cmds] == ["flight_dump", "profile"]
        relay._tick()  # everything acked: file untouched
        assert read_worker_commands(path) == cmds
        assert client.acks == [0, 2]  # the second poll acked id 2

    def test_relay_dedups_unacked_redelivery(
        self, tmp_path, monkeypatch
    ):
        """The master redelivers until acked; the relay must not write
        the same command into the file twice."""
        from dlrover_tpu.agent.monitor import (
            WorkerCommandRelay,
            read_worker_commands,
        )
        from dlrover_tpu.common import comm

        path = str(tmp_path / "cmds.json")

        class _Client:
            def poll_worker_commands(self, ack_id=0):
                # a master that never sees the ack: always redelivers
                return [comm.WorkerCommand(id=1, kind="flight_dump")]

        relay = WorkerCommandRelay(
            _Client(), interval=999, path=path, keep=8
        )
        relay._tick()
        relay._tick()
        assert [c["id"] for c in read_worker_commands(path)] == [1]

    def test_relay_keeps_bounded_tail(self, tmp_path):
        from dlrover_tpu.agent.monitor import (
            WorkerCommandRelay,
            read_worker_commands,
        )
        from dlrover_tpu.common import comm

        path = str(tmp_path / "cmds.json")

        class _Client:
            def __init__(self):
                self.n = 0

            def poll_worker_commands(self, ack_id=0):
                self.n += 1
                return [
                    comm.WorkerCommand(id=self.n, kind="flight_dump")
                ]

        relay = WorkerCommandRelay(
            _Client(), interval=999, path=path, keep=2
        )
        for _ in range(4):
            relay._tick()
        cmds = read_worker_commands(path)
        assert [c["id"] for c in cmds] == [3, 4]

    def test_read_worker_commands_missing_or_garbage(self, tmp_path):
        from dlrover_tpu.agent.monitor import read_worker_commands

        assert read_worker_commands(str(tmp_path / "nope.json")) == []
        bad = tmp_path / "bad.json"
        bad.write_text("{not json")
        assert read_worker_commands(str(bad)) == []

    def test_trainer_poll_executes_each_command_once(
        self, tmp_path, monkeypatch
    ):
        """The trainer-side executor, run against a stub: a flight_dump
        dumps, a profile arms the capture, and re-polling the same file
        is a no-op (master-monotonic ids)."""
        from dlrover_tpu.agent.monitor import atomic_write_json
        from dlrover_tpu.trainer.elastic.trainer import ElasticTrainer

        path = str(tmp_path / "cmds.json")
        monkeypatch.setenv("DLROVER_TPU_WORKER_COMMANDS_PATH", path)
        tr = SpanTracer(enabled=True)
        with tr.span("compute"):
            pass
        rec = FlightRecorder(
            base_dir=str(tmp_path / "flight"), tracer=tr,
            registry=MetricsRegistry(),
        )
        requested = []
        cap = SimpleNamespace(
            request=lambda steps, reason="": (
                requested.append((steps, reason)) or True
            )
        )
        stub = SimpleNamespace(
            _last_command_id=0, _flight=rec, _profiler_capture=cap
        )
        atomic_write_json(path, {"commands": [
            {"id": 1, "kind": "flight_dump", "arg": 0, "reason": "hang"},
            {"id": 2, "kind": "profile", "arg": 4, "reason": "straggler"},
            {"id": 3, "kind": "bogus", "arg": 0, "reason": ""},
        ]})
        ElasticTrainer._poll_worker_commands(stub)
        assert len(rec.dumps) == 1
        assert "request_hang" in rec.dumps[0]
        assert requested == [(4, "straggler")]
        assert stub._last_command_id == 3
        ElasticTrainer._poll_worker_commands(stub)  # same file again
        assert len(rec.dumps) == 1 and len(requested) == 1


class TestAggregatorGoodput:
    def _scalars(self, productive, wall, **extra):
        s = {
            "dlrover_goodput_wall_seconds": wall,
            'dlrover_goodput_seconds_total{category="productive_compute"}':
                productive,
        }
        for cat, v in extra.items():
            s[f'dlrover_goodput_seconds_total{{category="{cat}"}}'] = v
        return s

    def test_worker_goodput_from_metrics_report(self):
        from dlrover_tpu.obs.aggregate import TelemetryAggregator

        agg = TelemetryAggregator()
        agg.observe_metrics(
            0, 10, self._scalars(30.0, 60.0, data_stall=10.0)
        )
        rec = agg.worker_goodput(0)
        assert rec["goodput_pct"] == pytest.approx(50.0)
        assert rec["seconds"]["data_stall"] == 10.0
        assert agg.worker_goodput(99) is None

    def test_fleet_goodput_wall_weighted(self):
        from dlrover_tpu.obs.aggregate import TelemetryAggregator

        agg = TelemetryAggregator()
        assert agg.fleet_goodput() is None
        agg.observe_metrics(0, 10, self._scalars(90.0, 100.0))
        agg.observe_metrics(1, 10, self._scalars(10.0, 100.0))
        fleet = agg.fleet_goodput()
        assert fleet["goodput_pct"] == pytest.approx(50.0)
        assert fleet["workers"] == 2
        assert fleet["wall_s"] == pytest.approx(200.0)

    def test_departed_worker_leaves_fleet_number(self):
        from dlrover_tpu.obs.aggregate import TelemetryAggregator

        agg = TelemetryAggregator()
        agg.observe_metrics(0, 10, self._scalars(90.0, 100.0))
        agg.observe_metrics(1, 10, self._scalars(10.0, 100.0))
        agg.remove_worker(1)
        assert agg.fleet_goodput()["goodput_pct"] == pytest.approx(90.0)

    def test_export_publishes_and_prunes_gauges(self):
        from dlrover_tpu.obs.aggregate import TelemetryAggregator

        agg = TelemetryAggregator()
        agg.observe_metrics(0, 10, self._scalars(90.0, 100.0))
        agg.observe_metrics(1, 10, self._scalars(10.0, 100.0))
        reg = MetricsRegistry()
        agg.export(reg)
        scalars = reg.scalars()
        assert scalars["dlrover_goodput_fleet_pct"] == pytest.approx(50.0)
        assert scalars[
            'dlrover_goodput_worker_pct{worker="1"}'
        ] == pytest.approx(10.0)
        key = (
            'dlrover_goodput_fleet_seconds_total'
            '{category="productive_compute"}'
        )
        assert scalars[key] == pytest.approx(100.0)
        agg.remove_worker(1)
        agg.export(reg)
        scalars = reg.scalars()
        assert 'dlrover_goodput_worker_pct{worker="1"}' not in scalars
        assert scalars["dlrover_goodput_fleet_pct"] == pytest.approx(90.0)

    def test_malformed_goodput_keys_ignored(self):
        from dlrover_tpu.obs.aggregate import TelemetryAggregator

        agg = TelemetryAggregator()
        agg.observe_metrics(0, 10, {
            "dlrover_goodput_wall_seconds": 0.0,  # zero wall: dropped
            'dlrover_goodput_seconds_total{category="productive_compute"}':
                5.0,
        })
        agg.observe_metrics(1, 10, {
            'dlrover_goodput_seconds_total{category="not_a_category"}':
                5.0,
            "dlrover_goodput_wall_seconds": 10.0,
        })
        assert agg.worker_goodput(0) is None
        assert agg.worker_goodput(1) is None

    def test_straggler_triggers_one_profile_request_per_episode(self):
        from dlrover_tpu.obs.aggregate import TelemetryAggregator

        requested = []
        agg = TelemetryAggregator(straggler_ratio=2.0, min_samples=4)
        agg.set_profile_requester(requested.append)
        t0 = 1000.0
        for w in range(4):
            step_s = 0.3 if w == 3 else 0.1
            for i in range(8):
                agg.observe_step_report(w, i + 1, t0 + (i + 1) * step_s)
        assert agg.detect_stragglers() == [3]
        assert requested == [3]
        agg.detect_stragglers()  # still flagged: no re-request
        assert requested == [3]


class TestGoodputReachesBrain:
    def test_sample_carries_fleet_goodput(self):
        from dlrover_tpu.master.stats.collector import JobMetricCollector

        class _SM:
            completed_global_step = 5

            def running_speed(self):
                return 1.0

        class _Telemetry:
            def fleet_goodput(self):
                return {"goodput_pct": 87.5, "wall_s": 10.0,
                        "seconds": {}, "workers": 2}

        coll = JobMetricCollector(None, _SM(), telemetry=_Telemetry())
        sample = coll.collect()
        assert sample.goodput_pct == pytest.approx(87.5)

    def test_sample_defaults_without_telemetry(self):
        from dlrover_tpu.master.stats.collector import JobMetricCollector

        class _SM:
            completed_global_step = 5

            def running_speed(self):
                return 1.0

        assert JobMetricCollector(None, _SM()).collect().goodput_pct == 0.0

    def test_brain_persists_and_queries_goodput(self):
        from dlrover_tpu.brain.service import BrainServicer
        from dlrover_tpu.common import comm

        b = BrainServicer(db_path=":memory:")
        try:
            b.persist_metrics("job-g", comm.JobMetricsSample(
                timestamp=1.0, global_step=10, steps_per_sec=2.0,
                alive_nodes=4, goodput_pct=91.25,
            ))
            rows = b.job_metrics("job-g")
            assert rows[-1].goodput_pct == pytest.approx(91.25)
        finally:
            b.close()

    def test_brain_migrates_pre_goodput_store(self, tmp_path):
        """A datastore created before the goodput column existed must
        open cleanly (ALTER migration) and serve old rows as 0.0."""
        from dlrover_tpu.brain.service import BrainServicer
        from dlrover_tpu.common import comm

        db = str(tmp_path / "old.db")
        conn = sqlite3.connect(db)
        conn.execute(
            "CREATE TABLE job_metrics (job TEXT, ts REAL, "
            "global_step INTEGER, steps_per_sec REAL, "
            "alive_nodes INTEGER, total_cpu_percent REAL, "
            "total_memory_mb INTEGER)"
        )
        conn.execute(
            "INSERT INTO job_metrics VALUES "
            "('job-old', 1.0, 5, 1.0, 2, 0.0, 0)"
        )
        conn.commit()
        conn.close()
        b = BrainServicer(db_path=db)
        try:
            rows = b.job_metrics("job-old")
            assert rows[0].goodput_pct == 0.0
            b.persist_metrics("job-old", comm.JobMetricsSample(
                timestamp=2.0, global_step=6, goodput_pct=50.0,
            ))
            assert b.job_metrics("job-old")[-1].goodput_pct == 50.0
        finally:
            b.close()


class TestCardinalityGuard:
    def test_cap_refuses_growth_and_warns_once(self):
        reg = MetricsRegistry()
        g = reg.gauge("capped", "g", labelnames=("w",), max_label_sets=3)
        for i in range(3):
            g.labels(str(i)).set(float(i))
        assert g.label_set_count() == 3
        assert not g._overflow_warned
        g.labels("overflow-a").set(99.0)  # refused, warned
        g.labels("overflow-b").set(98.0)  # refused, silent
        assert g._overflow_warned
        assert g.label_set_count() == 3
        text = reg.prometheus_text()
        assert "overflow-a" not in text and "overflow-b" not in text
        assert 'capped{w="2"}' in text
        # existing label sets still writable past the cap
        g.labels("1").set(41.0)
        assert 'capped{w="1"} 41' in reg.prometheus_text()

    def test_overflow_child_is_usable_dead_end(self):
        reg = MetricsRegistry()
        c = reg.counter("cc", "c", labelnames=("w",), max_label_sets=1)
        c.labels("a").inc()
        c.labels("b").inc(5)  # overflow: works, never exported
        assert c.labels("a").value == 1.0
        assert 'cc{w="b"}' not in reg.prometheus_text()

    def test_env_configures_default_cap(self, monkeypatch):
        from dlrover_tpu.obs.metrics import ENV_MAX_LABEL_SETS

        monkeypatch.setenv(ENV_MAX_LABEL_SETS, "2")
        g = MetricsRegistry().gauge("envcap", "g", labelnames=("w",))
        assert g.max_label_sets == 2
        monkeypatch.setenv(ENV_MAX_LABEL_SETS, "not-a-number")
        g2 = MetricsRegistry().gauge("envcap2", "g", labelnames=("w",))
        assert g2.max_label_sets == 256

    def test_histogram_honors_cap(self):
        reg = MetricsRegistry()
        h = reg.histogram(
            "hh", "h", labelnames=("w",), max_label_sets=1
        )
        h.labels("a").observe(0.1)
        h.labels("b").observe(0.2)
        assert h.label_set_count() == 1


class TestMergeTimeline:
    def _trace(self, wall_t0, name="step", ts=0.0, dur=1000.0):
        return {
            "traceEvents": [{
                "ph": "X", "name": name, "ts": ts, "dur": dur,
                "pid": 1, "tid": 1, "args": {"depth": 0},
            }],
            "displayTimeUnit": "ms",
            "otherData": {"wall_t0_s": wall_t0, "pid": 123},
        }

    def test_aligns_on_shared_wall_clock(self):
        from tools.merge_timeline import merge_traces

        merged = merge_traces(
            [self._trace(100.0), self._trace(101.5)], ["w0", "w1"]
        )
        ok, reason = validate_chrome_trace(merged)
        assert ok, reason
        xs = [
            e for e in merged["traceEvents"] if e.get("ph") == "X"
        ]
        by_pid = {e["pid"]: e for e in xs}
        assert by_pid[1]["ts"] == pytest.approx(0.0)
        assert by_pid[2]["ts"] == pytest.approx(1.5e6)  # 1.5s later
        names = {
            e["pid"]: e["args"]["name"]
            for e in merged["traceEvents"]
            if e.get("name") == "process_name"
        }
        assert names[1] == "w0" and names[2] == "w1"

    def test_node_events_overlay_as_instants(self):
        from tools.merge_timeline import MASTER_PID, merge_traces

        events = [
            {"node_type": "worker", "node_id": 1, "event": "restart",
             "detail": "hang", "ts": 102.0},
            {"ts": 100.5, "kind": "ckpt_degraded", "detail": "enospc"},
        ]
        merged = merge_traces(
            [self._trace(100.0)], ["w0"], events=events
        )
        instants = [
            e for e in merged["traceEvents"] if e.get("ph") == "i"
        ]
        assert [e["name"] for e in instants] == [
            "ckpt_degraded", "restart",  # sorted by time
        ]
        assert all(e["pid"] == MASTER_PID for e in instants)
        assert instants[0]["ts"] == pytest.approx(0.5e6)
        assert instants[1]["ts"] == pytest.approx(2.0e6)
        assert instants[1]["args"]["node_id"] == 1

    def test_unanchored_trace_still_merges(self):
        from tools.merge_timeline import merge_traces

        legacy = {"traceEvents": [
            {"ph": "X", "name": "step", "ts": 5.0, "dur": 1.0,
             "pid": 9, "tid": 1},
        ]}
        merged = merge_traces(
            [self._trace(100.0), legacy], ["w0", "legacy"]
        )
        assert merged["otherData"]["unaligned"] == ["legacy"]
        legacy_evt = [
            e for e in merged["traceEvents"]
            if e.get("ph") == "X" and e["pid"] == 2
        ][0]
        assert legacy_evt["ts"] == pytest.approx(5.0)  # offset 0

    def test_empty_inputs_raise(self):
        from tools.merge_timeline import merge_traces

        with pytest.raises(ValueError):
            merge_traces([], [])

    def test_cli_round_trip(self, tmp_path):
        from tools.merge_timeline import main

        p0 = tmp_path / "w0.json"
        p1 = tmp_path / "w1.json"
        ev = tmp_path / "events.json"
        out = tmp_path / "merged.json"
        p0.write_text(json.dumps(self._trace(100.0)))
        p1.write_text(json.dumps(self._trace(103.0)))
        ev.write_text(json.dumps([
            {"ts": 101.0, "kind": "straggler", "detail": "worker 1"},
        ]))
        rc = main([
            str(p0), str(p1), "-o", str(out), "--events", str(ev),
        ])
        assert rc == 0
        with open(out) as f:
            merged = json.load(f)
        ok, reason = validate_chrome_trace(merged)
        assert ok, reason
        assert merged["otherData"]["sources"] == ["w0", "w1"]

    def test_real_tracer_dump_carries_anchor(self, tmp_path):
        """The producer side of the contract: SpanTracer.chrome_trace
        embeds the wall anchor merge_timeline aligns on."""
        before = time.time()
        tr = SpanTracer(enabled=True)
        with tr.span("compute"):
            pass
        trace = tr.chrome_trace()
        assert before <= trace["otherData"]["wall_t0_s"] <= time.time()
        from tools.merge_timeline import merge_traces

        merged = merge_traces([trace, self._trace(time.time())])
        ok, reason = validate_chrome_trace(merged)
        assert ok, reason
