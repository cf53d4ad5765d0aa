"""Unified telemetry (dlrover_tpu/obs/): span tracer, metrics registry,
master-side straggler/hang aggregation, the monitor satellites, and the
trace artifact of a real smoke training run.

Acceptance anchors (ISSUE 4):
- a smoke training run dumps Chrome-trace JSON whose step spans are
  ≥95% covered by phase children, loaded + validated here;
- with one worker's step times inflated 3x the master flags exactly
  that worker and the signal reaches Brain ingestion;
- hang reports carry last-open-span attribution;
- every PipelineStats dataclass field must appear in as_dict() and the
  registry export (the drift tripwire).
"""

import dataclasses
import json
import os
import threading
import time

import numpy as np
import pytest

from dlrover_tpu.common import faults
from dlrover_tpu.obs import flight_recorder as obs_flight
from dlrover_tpu.obs import trace as obs_trace
from dlrover_tpu.obs.aggregate import TelemetryAggregator
from dlrover_tpu.obs.metrics import (
    PIPELINE_PREFIX,
    MetricsRegistry,
    fold_pipeline_stats,
)
from dlrover_tpu.obs.trace import (
    SpanHeartbeat,
    SpanTracer,
    step_coverage,
    validate_chrome_trace,
)


class TestSpanTracer:
    def test_records_span_with_duration(self):
        t = SpanTracer(enabled=True)
        with t.span("work"):
            time.sleep(0.005)
        assert len(t) == 1
        name, tid, start_ns, dur_ns, depth, attrs, _seq = list(t._buf)[0]
        assert name == "work"
        assert tid == threading.get_ident()
        assert dur_ns >= 4_000_000  # slept 5ms
        assert depth == 0

    def test_nesting_depth_recorded(self):
        t = SpanTracer(enabled=True)
        with t.span("outer"):
            with t.span("inner"):
                pass
        by_name = {r[0]: r for r in t._buf}
        assert by_name["outer"][4] == 0
        assert by_name["inner"][4] == 1

    def test_ring_buffer_bounds_memory(self):
        t = SpanTracer(capacity=16, enabled=True)
        for _ in range(100):
            with t.span("s"):
                pass
        assert len(t) == 16
        assert t.dropped == 84

    def test_disabled_is_noop(self):
        t = SpanTracer(enabled=False)
        sp = t.span("x")
        assert sp is t.span("y")  # shared singleton, no allocation
        with sp:
            pass
        assert len(t) == 0

    def test_cancel_discards(self):
        t = SpanTracer(enabled=True)
        sp = t.span("aborted")
        sp.cancel()
        assert len(t) == 0
        assert t.open_spans() == []

    def test_double_end_is_idempotent(self):
        t = SpanTracer(enabled=True)
        sp = t.span("once")
        sp.end()
        sp.end()
        assert len(t) == 1

    def test_attrs_and_set(self):
        t = SpanTracer(enabled=True)
        with t.span("resize_compile", mesh="dp4") as sp:
            sp.set(cache_hit=True)
        rec = list(t._buf)[0]
        assert rec[5] == {"mesh": "dp4", "cache_hit": True}

    def test_decorator(self):
        t = SpanTracer(enabled=True)

        # the decorator form went (no caller): a function that wants a
        # span around its body opens one
        def f(x):
            with t.span("named"):
                return x + 1

        assert f(1) == 2
        assert list(t._buf)[0][0] == "named"

    def test_chrome_export_valid_and_dump_roundtrips(self, tmp_path):
        t = SpanTracer(enabled=True)
        with t.span("step"):
            with t.span("compute"):
                pass
        path = str(tmp_path / "sub" / "trace.json")
        t.dump(path)
        loaded = json.load(open(path))
        ok, reason = validate_chrome_trace(loaded)
        assert ok, reason
        xs = [e for e in loaded["traceEvents"] if e["ph"] == "X"]
        assert {e["name"] for e in xs} == {"step", "compute"}
        # depth rides in args so coverage is recomputable offline
        assert all("depth" in e["args"] for e in xs)

    def test_validate_rejects_garbage(self):
        assert validate_chrome_trace({"nope": 1})[0] is False
        assert validate_chrome_trace({"traceEvents": []})[0] is False
        assert validate_chrome_trace(
            {"traceEvents": [{"ph": "X", "name": "a"}]}
        )[0] is False

    def test_open_spans_visible_cross_thread(self):
        t = SpanTracer(enabled=True)
        entered = threading.Event()
        release = threading.Event()

        def worker():
            with t.span("ckpt_commit"):
                entered.set()
                release.wait(5.0)

        th = threading.Thread(target=worker, daemon=True)
        th.start()
        assert entered.wait(5.0)
        time.sleep(0.02)
        last = t.last_open_span()
        assert last is not None
        assert last[0] == "ckpt_commit"
        assert last[1] > 0
        release.set()
        th.join(5.0)
        assert t.last_open_span() is None

    def test_last_open_span_tid_filter(self):
        t = SpanTracer(enabled=True)
        entered = threading.Event()
        release = threading.Event()

        def parked_producer():
            with t.span("prefetch_pull"):
                entered.set()
                release.wait(5.0)

        th = threading.Thread(target=parked_producer, daemon=True)
        th.start()
        assert entered.wait(5.0)
        sp = t.span("compute")
        try:
            my_tid = threading.get_ident()
            # unfiltered may pick the producer; filtered must not
            assert t.last_open_span(tid=my_tid)[0] == "compute"
        finally:
            sp.end()
            release.set()
            th.join(5.0)

    def test_threaded_recording_is_safe(self):
        t = SpanTracer(capacity=10_000, enabled=True)

        def burst():
            for _ in range(200):
                with t.span("s"):
                    pass

        threads = [
            threading.Thread(target=burst) for _ in range(4)
        ]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        assert len(t) == 800

    def test_reset_clears_records(self):
        t = SpanTracer(enabled=True)
        with t.span("a"):
            pass
        t.reset()
        assert len(t) == 0
        assert t.dropped == 0


class TestStepCoverage:
    def _ev(self, name, ts, dur, depth, tid=1):
        return {
            "name": name, "ph": "X", "tid": tid, "ts": ts, "dur": dur,
            "args": {"depth": depth},
        }

    def test_full_coverage(self):
        events = [
            self._ev("step", 0, 100, 0),
            self._ev("data_wait", 0, 40, 1),
            self._ev("compute", 40, 58, 1),
        ]
        assert step_coverage(events) == pytest.approx(0.98)

    def test_gap_detected(self):
        events = [
            self._ev("step", 0, 100, 0),
            self._ev("compute", 0, 50, 1),
        ]
        assert step_coverage(events) == pytest.approx(0.5)

    def test_overlapping_children_not_double_counted(self):
        events = [
            self._ev("step", 0, 100, 0),
            self._ev("a", 0, 60, 1),
            self._ev("b", 40, 60, 1),
        ]
        assert step_coverage(events) == pytest.approx(1.0)

    def test_deeper_descendants_ignored(self):
        # grandchildren don't count twice and other tids don't leak in
        events = [
            self._ev("step", 0, 100, 0),
            self._ev("compute", 0, 90, 1),
            self._ev("inner", 0, 90, 2),
            self._ev("h2d", 0, 100, 1, tid=2),
        ]
        assert step_coverage(events) == pytest.approx(0.9)

    def test_no_parents_returns_none(self):
        assert step_coverage([self._ev("x", 0, 1, 0)]) is None


class TestMetricsRegistry:
    def test_counter(self):
        reg = MetricsRegistry()
        c = reg.counter("req_total", "requests")
        c.inc()
        c.inc(2)
        assert c.value == 3
        with pytest.raises(ValueError):
            c.inc(-1)

    def test_gauge_and_labels(self):
        reg = MetricsRegistry()
        g = reg.gauge("temp", "t", labelnames=("zone",))
        g.labels("a").set(1.5)
        g.labels(zone="b").inc(2.0)
        assert g.labels("a").value == 1.5
        assert g.labels("b").value == 2.0
        with pytest.raises(ValueError):
            g.set(9.0)  # labeled metric requires .labels(...)

    def test_histogram(self):
        reg = MetricsRegistry()
        h = reg.histogram("lat", "l", buckets=(0.1, 1.0))
        for v in (0.05, 0.5, 5.0):
            h.observe(v)
        assert h.count == 3
        assert h.sum == pytest.approx(5.55)
        cum = h._default_child().cumulative()
        assert cum[0] == (0.1, 1)
        assert cum[1] == (1.0, 2)
        assert cum[-1][1] == 3
        assert h.quantile(0.5) == 1.0

    def test_get_or_create_and_kind_mismatch(self):
        reg = MetricsRegistry()
        a = reg.counter("x", "first help")
        b = reg.counter("x")
        assert a is b
        with pytest.raises(ValueError):
            reg.gauge("x")

    def test_prometheus_text_format(self):
        reg = MetricsRegistry()
        reg.counter("n_total", "count of n").inc(4)
        reg.gauge("g", "gg", labelnames=("w",)).labels("3").set(1.5)
        reg.histogram("lat_seconds", "lat", buckets=(0.5,)).observe(0.2)
        text = reg.prometheus_text()
        assert "# HELP n_total count of n" in text
        assert "# TYPE n_total counter" in text
        assert "n_total 4" in text
        assert 'g{w="3"} 1.5' in text
        assert 'lat_seconds_bucket{le="0.5"} 1' in text
        assert 'lat_seconds_bucket{le="+Inf"} 1' in text
        assert "lat_seconds_count 1" in text

    def test_scalars_flat_export(self):
        reg = MetricsRegistry()
        reg.counter("c").inc()
        reg.histogram("h", buckets=(1.0,)).observe(0.5)
        s = reg.scalars()
        assert s["c"] == 1.0
        assert s["h_sum"] == 0.5
        assert s["h_count"] == 1.0


class TestPipelineStatsTripwire:
    """Every PipelineStats dataclass field MUST appear in as_dict() AND
    in the registry export — fields silently missing from telemetry is
    exactly the drift mode PR 3 hit (new fields needed manual as_dict
    edits)."""

    def _stats_all_set(self):
        from dlrover_tpu.accel.profiler import PipelineStats

        stats = PipelineStats()
        for i, f in enumerate(dataclasses.fields(PipelineStats)):
            setattr(stats, f.name, float(i + 1))
        return stats

    def test_every_field_in_as_dict(self):
        from dlrover_tpu.accel.profiler import PipelineStats

        stats = self._stats_all_set()
        d = stats.as_dict()
        missing = [
            f.name
            for f in dataclasses.fields(PipelineStats)
            if f.name not in d
        ]
        assert not missing, (
            f"PipelineStats fields missing from as_dict(): {missing} — "
            f"add them or telemetry silently loses them"
        )

    def test_every_field_reaches_registry_export(self):
        from dlrover_tpu.accel.profiler import PipelineStats

        stats = self._stats_all_set()
        reg = MetricsRegistry()
        fold_pipeline_stats(stats, reg)
        scalars = reg.scalars()
        missing = [
            f.name
            for f in dataclasses.fields(PipelineStats)
            if PIPELINE_PREFIX + f.name not in scalars
        ]
        assert not missing, (
            f"PipelineStats fields missing from the registry export: "
            f"{missing}"
        )

    def test_none_fields_still_export(self):
        from dlrover_tpu.accel.profiler import PipelineStats

        reg = MetricsRegistry()
        fold_pipeline_stats(PipelineStats(), reg)  # defaults incl. None
        assert PIPELINE_PREFIX + "comm_overlap_pct" in reg.scalars()


class TestTelemetryAggregator:
    def _feed_steady(self, agg, worker, step_s, n=8, t0=1000.0):
        for i in range(n):
            agg.observe_step_report(worker, i + 1, t0 + (i + 1) * step_s)

    def test_derived_step_times(self):
        agg = TelemetryAggregator(min_samples=4)
        self._feed_steady(agg, 0, 0.1)
        assert agg.worker_p50(0) == pytest.approx(0.1, rel=0.01)

    def test_explicit_step_time_preferred(self):
        agg = TelemetryAggregator(min_samples=2)
        self._feed_steady(agg, 0, 5.0)  # coarse derived samples
        for _ in range(4):
            agg.observe_metrics(0, 10, {"step_time_ms": 100.0})
        # the explicit channel replaced the derived history entirely
        assert agg.worker_p50(0) == pytest.approx(0.1)

    def test_straggler_flags_exactly_the_inflated_worker(self):
        """One worker 3x slower than the fleet → exactly that worker is
        flagged and the brain reporter fires once."""
        reports = []
        agg = TelemetryAggregator(
            straggler_ratio=2.0,
            min_samples=4,
            brain_reporter=lambda w, p50, med: reports.append(w),
        )
        for w in range(4):
            self._feed_steady(agg, w, 0.3 if w == 3 else 0.1)
        assert agg.detect_stragglers() == [3]
        assert agg.stragglers == [3]
        assert reports == [3]
        # re-detection does not re-report while still flagged
        agg.detect_stragglers()
        assert reports == [3]

    def test_straggler_signal_reaches_brain_ingestion(self):
        """The acceptance path: detector → straggler_sink → Brain
        datastore node_events rows (event='straggler')."""
        from dlrover_tpu.brain.ingestion import straggler_sink
        from dlrover_tpu.brain.service import BrainServicer

        brain = BrainServicer(db_path=":memory:")
        try:
            agg = TelemetryAggregator(
                straggler_ratio=2.0,
                min_samples=4,
                brain_reporter=straggler_sink(brain, "job-a"),
            )
            for w in range(4):
                self._feed_steady(agg, w, 0.3 if w == 3 else 0.1)
            assert agg.detect_stragglers() == [3]
            rows = brain.node_events(job="job-a", event="straggler")
            assert [r.node_id for r in rows] == [3]
        finally:
            brain.close()

    def test_straggler_recovery_unflags_and_can_reflag(self):
        reports = []
        agg = TelemetryAggregator(
            straggler_ratio=2.0,
            min_samples=4,
            window=8,
            brain_reporter=lambda w, p50, med: reports.append(w),
        )
        for w in range(4):
            self._feed_steady(agg, w, 0.3 if w == 3 else 0.1)
        assert agg.detect_stragglers() == [3]
        # worker 3 recovers: fresh fast samples displace the window
        self._feed_steady(agg, 3, 0.1, n=8, t0=5000.0)
        assert agg.detect_stragglers() == []
        assert agg.stragglers == []
        # relapse reports again
        self._feed_steady(agg, 3, 0.3, n=8, t0=9000.0)
        assert agg.detect_stragglers() == [3]
        assert reports == [3, 3]

    def test_no_flag_below_min_samples_or_single_worker(self):
        agg = TelemetryAggregator(min_samples=4)
        self._feed_steady(agg, 0, 0.1, n=2)
        assert agg.detect_stragglers() == []
        agg2 = TelemetryAggregator(min_samples=4)
        self._feed_steady(agg2, 0, 0.3)
        assert agg2.detect_stragglers() == []  # no fleet to compare

    def test_hang_attribution_carries_last_open_span(self):
        agg = TelemetryAggregator()
        agg.observe_metrics(
            3, 50, {}, open_span="ckpt_commit", open_span_elapsed_s=42.0
        )
        name, elapsed = agg.last_open_span(3)
        assert name == "ckpt_commit"
        assert elapsed >= 42.0
        att = agg.hang_attribution()
        assert "stuck in ckpt_commit for 42" in att[3]
        assert "ckpt_commit" in agg.describe_hang()

    def test_empty_open_span_clears_attribution(self):
        agg = TelemetryAggregator()
        agg.observe_metrics(1, 5, {}, open_span="eval",
                            open_span_elapsed_s=1.0)
        agg.observe_metrics(1, 6, {}, open_span="")
        assert agg.last_open_span(1) is None

    def test_remove_worker_drops_history(self):
        agg = TelemetryAggregator(min_samples=4)
        self._feed_steady(agg, 0, 0.1)
        agg.remove_worker(0)
        assert agg.worker_p50(0) is None
        assert agg.workers() == []

    def test_export_to_registry(self):
        agg = TelemetryAggregator(min_samples=4)
        self._feed_steady(agg, 0, 0.1)
        self._feed_steady(agg, 1, 0.1)
        reg = MetricsRegistry()
        agg.export(reg)
        s = reg.scalars()
        assert s['dlrover_worker_step_time_p50_seconds{worker="0"}'] == (
            pytest.approx(0.1, rel=0.01)
        )
        assert "dlrover_fleet_step_time_median_seconds" in s
        assert s["dlrover_straggler_count"] == 0.0

    def test_export_prunes_departed_workers(self):
        """A scaled-away worker's labeled gauge child must not keep
        exposing its last p50 as a frozen ghost series."""
        agg = TelemetryAggregator(min_samples=4)
        self._feed_steady(agg, 0, 0.1)
        self._feed_steady(agg, 5, 0.1)
        reg = MetricsRegistry()
        agg.export(reg)
        assert 'dlrover_worker_step_time_p50_seconds{worker="5"}' in (
            reg.scalars()
        )
        agg.remove_worker(5)
        agg.export(reg)
        s = reg.scalars()
        assert 'dlrover_worker_step_time_p50_seconds{worker="5"}' not in s
        assert 'dlrover_worker_step_time_p50_seconds{worker="0"}' in s


class TestMasterTelemetryWiring:
    """The hooks: GlobalStepReport → SpeedMonitor(node_id) → aggregator;
    TrainMetricsReport → aggregator; auto-scaler surfaces the flags."""

    def _servicer(self):
        from dlrover_tpu.master.monitor.speed_monitor import SpeedMonitor
        from dlrover_tpu.master.servicer import MasterServicer

        agg = TelemetryAggregator(straggler_ratio=2.0, min_samples=4)
        sm = SpeedMonitor(telemetry=agg)
        servicer = MasterServicer(speed_monitor=sm, telemetry=agg)
        return servicer, sm, agg

    def _report(self, servicer, message, node_id=0):
        from dlrover_tpu.common import comm

        req = comm.BaseRequest(
            node_id=node_id, data=comm.serialize_message(message)
        )
        resp = comm.deserialize_message(
            servicer.report(comm.serialize_message(req))
        )
        assert resp.success, resp.message

    def test_step_reports_feed_per_worker_samples(self):
        from dlrover_tpu.common import comm

        servicer, sm, agg = self._servicer()
        t0 = 1000.0
        for w in range(2):
            step_s = 0.3 if w == 1 else 0.1
            for i in range(8):
                self._report(
                    servicer,
                    comm.GlobalStepReport(
                        node_id=w, step=i + 1,
                        timestamp=t0 + (i + 1) * step_s,
                    ),
                    node_id=w,
                )
        assert agg.worker_p50(0) == pytest.approx(0.1, rel=0.01)
        assert agg.worker_p50(1) == pytest.approx(0.3, rel=0.01)
        # the fleet-max channel still works
        assert sm.completed_global_step == 8

    def test_train_metrics_report_carries_open_span(self):
        from dlrover_tpu.common import comm

        servicer, _, agg = self._servicer()
        self._report(
            servicer,
            comm.TrainMetricsReport(
                node_id=3, step=7, metrics={"loss": 1.0},
                open_span="ckpt_commit", open_span_elapsed_s=42.0,
            ),
            node_id=3,
        )
        assert agg.last_open_span(3)[0] == "ckpt_commit"

    def test_master_flags_3x_straggler_and_scaler_surfaces_it(self):
        """Acceptance: 4 workers report steps through the real master
        wiring, worker 2's step times inflated 3x → the auto-scaler's
        detection pass flags exactly worker 2."""
        from dlrover_tpu.common import comm
        from dlrover_tpu.master.local_master import LocalJobMaster

        master = LocalJobMaster(node_num=4)
        try:
            master.telemetry.straggler_ratio = 2.0
            t0 = 1000.0
            for w in range(4):
                step_s = 0.3 if w == 2 else 0.1
                for i in range(8):
                    master.speed_monitor.collect_global_step(
                        i + 1, t0 + (i + 1) * step_s, node_id=w
                    )
            assert master.auto_scaler.check_stragglers() == [2]
            assert master.auto_scaler.stragglers == [2]
            # hang report names the per-worker state
            master.telemetry.observe_metrics(
                2, 8, {}, open_span="grad_sync_probe",
                open_span_elapsed_s=30.0,
            )
            desc = master.telemetry.describe_hang()
            assert "worker 2 stuck in grad_sync_probe" in desc
            assert "stragglers=[2]" in desc
        finally:
            master.stop()


class TestMonitorSatellites:
    def test_report_runtime_metrics_bare_filename(
        self, tmp_path, monkeypatch
    ):
        """os.makedirs(os.path.dirname('metrics.json')) used to raise
        FileNotFoundError on the empty dirname."""
        from dlrover_tpu.agent.monitor import (
            read_runtime_metrics,
            report_runtime_metrics,
        )

        monkeypatch.chdir(tmp_path)
        report_runtime_metrics(3, path="metrics.json", loss=1.25)
        got = read_runtime_metrics("metrics.json")
        assert got["global_step"] == 3
        assert got["loss"] == 1.25

    def test_speed_monitor_honors_explicit_zero_timestamp(self):
        """`timestamp or time.time()` treated an explicit 0.0 as 'not
        provided'; the contract is `is None`."""
        from dlrover_tpu.master.monitor.speed_monitor import SpeedMonitor

        sm = SpeedMonitor(window=8)
        sm.collect_global_step(5, timestamp=0.0)
        assert sm.first_step_time == 0.0
        assert list(sm._samples) == [(0.0, 5)]
        # None still means "stamp now"
        sm2 = SpeedMonitor(window=8)
        before = time.time()
        sm2.collect_global_step(5)
        assert sm2.first_step_time >= before

    class _FakeClient:
        def __init__(self):
            self.steps = []
            self.metric_calls = []

        def report_global_step(self, step):
            self.steps.append(step)

        def report_train_metrics(
            self, step, metrics, open_span="", open_span_elapsed_s=0.0
        ):
            self.metric_calls.append(
                (step, dict(metrics), open_span, open_span_elapsed_s)
            )

    def test_training_monitor_forwards_updated_scalars_same_step(
        self, tmp_path, monkeypatch
    ):
        """A fresh loss at an UNCHANGED global step (post-restore
        refresh) must still reach the master: forwarding is gated on
        the payload timestamp, not the step."""
        from dlrover_tpu.agent.monitor import (
            TrainingMonitor,
            report_runtime_metrics,
        )

        path = str(tmp_path / "metrics.json")
        monkeypatch.setenv("DLROVER_TPU_RUNTIME_METRICS_PATH", path)
        client = self._FakeClient()
        mon = TrainingMonitor(client, interval=999)

        report_runtime_metrics(5, loss=2.0)
        mon._tick()
        assert client.steps == [5]
        assert client.metric_calls[-1][1]["loss"] == 2.0

        time.sleep(0.01)  # a distinct payload timestamp
        report_runtime_metrics(5, loss=1.5)  # same step, fresh loss
        mon._tick()
        assert client.steps == [5]  # step channel fires once
        assert client.metric_calls[-1][1]["loss"] == 1.5

        mon._tick()  # no new payload → no forward
        assert len(client.metric_calls) == 2

    def test_training_monitor_forwards_span_heartbeat_while_stuck(
        self, tmp_path, monkeypatch
    ):
        """The wedged-step path: the step stops advancing, the trainer
        stops writing — the SpanHeartbeat's file updates must still
        flow to the master (this is what makes hang reports
        attributable)."""
        from dlrover_tpu.agent.monitor import (
            TrainingMonitor,
            report_runtime_metrics,
        )

        path = str(tmp_path / "metrics.json")
        monkeypatch.setenv("DLROVER_TPU_RUNTIME_METRICS_PATH", path)
        client = self._FakeClient()
        mon = TrainingMonitor(client, interval=999)
        report_runtime_metrics(7, loss=1.0)
        mon._tick()

        tracer = SpanTracer(enabled=True)
        hb = SpanHeartbeat(tracer=tracer, path=path)
        sp = tracer.span("ckpt_commit")  # the loop "wedges" here
        try:
            time.sleep(0.01)
            hb.publish_once()
        finally:
            sp.end()
        mon._tick()
        step, metrics, open_span, elapsed = client.metric_calls[-1]
        assert step == 7
        assert open_span == "ckpt_commit"
        assert elapsed > 0


@pytest.fixture(scope="class")
def traced_smoke_run(tmp_path_factory):
    """One tiny training run with tracing on: the Chrome-trace artifact
    + the runtime-metrics payload the class below validates."""
    import jax
    import optax

    from dlrover_tpu.accel.strategy import Strategy
    from dlrover_tpu.models import tiny
    from dlrover_tpu.parallel.mesh import MeshConfig
    from dlrover_tpu.trainer.elastic.trainer import (
        ElasticTrainer,
        TrainerConfig,
    )

    tmp = tmp_path_factory.mktemp("traced_run")
    metrics_path = str(tmp / "runtime_metrics.json")
    old_env = os.environ.get("DLROVER_TPU_RUNTIME_METRICS_PATH")
    os.environ["DLROVER_TPU_RUNTIME_METRICS_PATH"] = metrics_path
    old_flight = os.environ.get(obs_flight.ENV_FLIGHT_DIR)

    class _Tokens:
        def __init__(self, n=256, seq=32, vocab=256):
            rng = np.random.default_rng(3)
            self.data = rng.integers(
                0, vocab, (n, seq + 1), dtype=np.int32
            )

        def __len__(self):
            return len(self.data)

        def __getitem__(self, i):
            return {"x": self.data[i][:-1], "y": self.data[i][1:]}

    tracer = obs_trace.get_tracer()
    was_enabled = tracer.enabled
    tracer.enabled = True
    trainer = ElasticTrainer(
        model_cfg=tiny(num_layers=1),
        tx=optax.adamw(1e-2),
        dataset=_Tokens(),
        trainer_cfg=TrainerConfig(
            batch_size=8,
            seq_len=32,
            report_metrics=True,
            log_interval=4,
            prefetch=2,
            donation_aware=False,
            speculative_compile=False,
            ckpt_dir=str(tmp / "ckpt"),
            save_memory_interval=6,
            save_storage_interval=10_000,
        ),
        strategy=Strategy(mesh=MeshConfig(dp=1), dtype="float32"),
        devices=list(jax.devices())[:1],
    )
    try:
        trainer.train(num_steps=2)  # compile outside the traced window
        tracer.reset()
        trainer.train(num_steps=14)
        trace_path = str(tmp / "trace.json")
        tracer.dump(trace_path)
        goodput = trainer._goodput.snapshot()
        # then a crash: the feed fails under the loop, which leaves its
        # black box behind before the exception reaches the caller
        os.environ[obs_flight.ENV_FLIGHT_DIR] = str(tmp / "flight")
        faults.configure("prefetch.pull:io_error:1.0")
        try:
            trainer.train(num_steps=trainer.global_step + 8)
            crashed = None
        except OSError as e:
            crashed = e
        yield {
            "trace_path": trace_path,
            "metrics_path": metrics_path,
            "stats": trainer.pipeline_stats,
            "goodput": goodput,
            "crashed": crashed,
            "flight_dir": str(tmp / "flight"),
        }
    finally:
        faults.reset()
        if old_flight is None:
            os.environ.pop(obs_flight.ENV_FLIGHT_DIR, None)
        else:
            os.environ[obs_flight.ENV_FLIGHT_DIR] = old_flight
        trainer.close()
        tracer.enabled = was_enabled
        if old_env is None:
            os.environ.pop("DLROVER_TPU_RUNTIME_METRICS_PATH", None)
        else:
            os.environ["DLROVER_TPU_RUNTIME_METRICS_PATH"] = old_env


class TestTrainerTraceArtifact:
    """Acceptance: a smoke training run dumps Chrome-trace JSON whose
    step spans are >= 95% explained by phase children; the registry
    scalars reach the runtime-metrics file."""

    def test_artifact_is_valid_chrome_trace(self, traced_smoke_run):
        loaded = json.load(open(traced_smoke_run["trace_path"]))
        ok, reason = validate_chrome_trace(loaded)
        assert ok, reason

    def test_step_spans_cover_95_pct(self, traced_smoke_run):
        loaded = json.load(open(traced_smoke_run["trace_path"]))
        cov = step_coverage(loaded)
        assert cov is not None
        assert cov >= 0.95, f"step phase coverage {cov:.1%} < 95%"

    def test_expected_phases_present(self, traced_smoke_run):
        loaded = json.load(open(traced_smoke_run["trace_path"]))
        names = {
            e["name"]
            for e in loaded["traceEvents"]
            if e["ph"] == "X"
        }
        for expected in (
            "step", "data_wait", "compute", "host_sync", "ckpt_save",
            "prefetch_pull", "h2d",
        ):
            assert expected in names, f"missing span {expected}"

    def test_the_live_ledger_closes_on_wall_time(self, traced_smoke_run):
        """The goodput categories of a running trainer sum back to its
        wall time within the gate, and spans did flow into them."""
        from dlrover_tpu.obs.goodput import CLOSURE_GATE_PCT

        report = traced_smoke_run["goodput"]
        assert report.closure_error_pct <= CLOSURE_GATE_PCT
        assert report.goodput_pct > 0
        assert report.seconds.get("productive_compute", 0.0) > 0

    def test_a_crash_leaves_a_bundle_with_a_valid_trace(
        self, traced_smoke_run
    ):
        assert isinstance(traced_smoke_run["crashed"], OSError)
        root = traced_smoke_run["flight_dir"]
        bundles = sorted(
            d for d in os.listdir(root) if d.split("_")[1:2] == ["crash"]
        )
        assert bundles
        with open(os.path.join(root, bundles[-1], "trace.json")) as f:
            ok, reason = validate_chrome_trace(json.load(f))
        assert ok, reason
        with open(os.path.join(root, bundles[-1], "manifest.json")) as f:
            assert json.load(f)["exception"]["type"] == "OSError"

    def test_registry_scalars_reach_metrics_file(self, traced_smoke_run):
        payload = json.load(open(traced_smoke_run["metrics_path"]))
        assert payload["global_step"] >= 12
        assert payload["step_time_ms"] > 0
        assert "loss" in payload
        # the PipelineStats fold rides the same export
        assert PIPELINE_PREFIX + "prefetch_hits" in payload
        assert "dlrover_step_time_seconds_count" in payload
