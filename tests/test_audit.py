"""Step-budget reconciliation (ISSUE 19): priced-vs-observed
attribution, drift-vs-regression classification, calibration
persistence, and the fleet leg of the attribution.

Acceptance anchors:
- observed component seconds come from the span stream's step windows
  (same clipping rule as ``step_coverage``): a span straddling a mesh
  rebuild contributes only its inside portion to each step bucket —
  never double-counted into a neighbor step;
- a mispriced component (within the drift gate) folds into the
  per-component EWMA and raises NO regression alarm; a genuinely
  regressed component trips the CUSUM latch, names itself, and fires
  ``on_alarm`` once per episode;
- the drift snapshot persists beside ``railrates-<fp>.json`` with the
  same fingerprint-reject discipline, and the dry-runner reprices
  per component (``reprice_report``) instead of one scalar calib;
- the aggregator upgrades a straggler flag with the component-level
  *why*, and ``merge_timeline`` renders alarms as named instant
  markers.
"""

import json
import os

import pytest

from dlrover_tpu.obs import audit as obs_audit
from dlrover_tpu.obs.audit import (
    COMPONENTS,
    CUSUM_H,
    CUSUM_K,
    WARMUP_STEPS,
    AuditCalibration,
    ComponentDrift,
    CusumDetector,
    StepAuditor,
    StepBudget,
    current_drift_factors,
    install_default_auditor,
    load_audit_calibration,
    reset_default_auditor,
    save_audit_calibration,
    seed_default_drift,
)
from dlrover_tpu.obs.metrics import MetricsRegistry
from dlrover_tpu.obs.trace import SpanTracer, step_coverage

MS = 1_000_000  # ns


@pytest.fixture(autouse=True)
def _isolated_default_auditor(tmp_path, monkeypatch):
    # hermetic: the user cache may hold a real auditcal-<fp>.json from
    # any prior trainer run on this machine — current_drift_factors()
    # overlays it by design, so point the topology cache elsewhere
    monkeypatch.setenv(
        "DLROVER_TPU_TOPOLOGY_CACHE", str(tmp_path / "topocache")
    )
    reset_default_auditor()
    yield
    reset_default_auditor()


def _put(tracer, name, start_ns, dur_ns, tid=1, depth=0):
    """Append one synthetic completed record (drain input shape)."""
    tracer._buf.append(
        (name, tid, start_ns, dur_ns, depth, None, next(tracer._seq))
    )
    tracer._appended += 1


def _emit_step(tracer, t0_ns, *, compute_ms=80.0, data_wait_ms=5.0,
               host_sync_ms=0.0, tid=1):
    """One complete step: children first, then the parent ``step``
    record — the stack-discipline drain order the auditor sees live.
    Returns the step's end time in ns."""
    t = t0_ns
    if data_wait_ms:
        _put(tracer, "data_wait", t, int(data_wait_ms * MS), tid, depth=1)
        t += int(data_wait_ms * MS)
    if compute_ms:
        _put(tracer, "compute", t, int(compute_ms * MS), tid, depth=1)
        t += int(compute_ms * MS)
    if host_sync_ms:
        _put(tracer, "host_sync", t, int(host_sync_ms * MS), tid, depth=1)
        t += int(host_sync_ms * MS)
    _put(tracer, "step", t0_ns, t - t0_ns, tid, depth=0)
    return t


def _budget(compute_ms=80.0, data_wait_ms=5.0, **kw):
    b = StepBudget()
    b.set_component("compute", compute_ms / 1e3, "priced")
    b.set_component("data_wait", data_wait_ms / 1e3, "priced")
    for c, ms in kw.items():
        b.set_component(c, ms / 1e3, "priced")
    return b


def _auditor(budget=None, **kw):
    tr = SpanTracer(enabled=True)
    aud = StepAuditor(tracer=tr, budget=budget, **kw)
    return tr, aud


def _run_warmup(tr, aud, t0=0, **step_kw):
    """Drive the auditor past its baseline window on on-budget steps."""
    t = t0
    for _ in range(WARMUP_STEPS):
        t = _emit_step(tr, t, **step_kw)
    aud.collect()
    return t


class TestStepBudget:
    def test_component_roundtrip_and_total(self):
        b = StepBudget()
        for i, c in enumerate(COMPONENTS):
            b.set_component(c, 0.01 * (i + 1), "priced")
        assert b.component("dcn_sync") == pytest.approx(0.03)
        assert b.total_s() == pytest.approx(sum(
            0.01 * (i + 1) for i in range(len(COMPONENTS))
        ))
        d = b.as_dict()
        assert d["source"]["compute"] == "priced"
        assert set(d) == {c + "_s" for c in COMPONENTS} | {"source"}

    def test_negative_clamps_to_zero(self):
        b = StepBudget()
        b.set_component("compute", -1.0)
        assert b.compute_s == 0.0


class TestComponentDrift:
    def test_seed_is_first_measurement_only(self):
        d = ComponentDrift()
        d.seed(1.8)
        assert d.factor == pytest.approx(1.8)
        d.seed(5.0)  # no-op once seeded
        assert d.factor == pytest.approx(1.8)

    def test_fold_ewma_converges(self):
        d = ComponentDrift()
        for _ in range(60):
            d.fold(1.5)
        assert d.factor == pytest.approx(1.5, rel=1e-3)

    def test_nonpositive_ratio_ignored(self):
        d = ComponentDrift()
        d.fold(0.0)
        d.seed(-2.0)
        assert d.factor == 1.0 and d.samples == 0


class TestCusumDetector:
    def test_sustained_positive_fires_and_resets(self):
        det = CusumDetector()
        fired = [det.update(2.0) for _ in range(5)]
        assert any(fired)
        # the accumulator reset on fire: re-alarming needs
        # re-accumulation (refire hysteresis)
        assert det.pos < CUSUM_H

    def test_noise_below_allowance_never_fires(self):
        det = CusumDetector()
        for r in (0.1, -0.2, 0.2, -0.1) * 50:
            assert not det.update(r)

    def test_fast_side_tracked_but_silent(self):
        det = CusumDetector()
        for _ in range(10):
            assert not det.update(-2.0)
        assert det.neg > 0.0


class TestAuditorObservation:
    def test_on_budget_steps_no_alarm(self):
        tr, aud = _auditor(_budget())
        t = _run_warmup(tr, aud)
        for _ in range(5):
            t = _emit_step(tr, t)
        results = aud.collect()
        assert len(results) == 5
        assert aud.steps_audited == WARMUP_STEPS + 5
        assert aud.alarm_components() == []
        last = aud.last_result()
        assert last.observed["compute"] == pytest.approx(0.08, rel=1e-6)
        assert abs(last.residual["compute"]) < 1e-6

    def test_children_of_inflight_step_are_held(self):
        tr, aud = _auditor(_budget())
        _put(tr, "compute", 0, 80 * MS, depth=1)  # step not closed yet
        assert aud.collect() == []
        _put(tr, "step", 0, 85 * MS, depth=0)
        res = aud.collect()
        assert len(res) == 1
        assert res[0].observed["compute"] == pytest.approx(0.08)

    def test_other_tid_records_ignored(self):
        tr, aud = _auditor(_budget(), tid_fn=lambda: 1)
        _emit_step(tr, 0, tid=2)
        assert aud.collect() == []

    def test_measured_sync_deducted_from_compute(self):
        b = _budget(ici_sync=0.0)
        b.set_component("ici_sync", 0.01, "priced")
        tr, aud = _auditor(b)
        aud.set_measured("ici_sync", 0.01)
        _emit_step(tr, 0, compute_ms=90.0)  # sync runs inside compute
        res = aud.collect()[0]
        assert res.observed["ici_sync"] == pytest.approx(0.01)
        assert res.observed["compute"] == pytest.approx(0.08)

    def test_unknown_component_rejected(self):
        _tr, aud = _auditor()
        with pytest.raises(ValueError):
            aud.set_measured("gpu_burn", 1.0)
        with pytest.raises(ValueError):
            aud.seed_drift("gpu_burn", 1.0)


class TestDriftVsRegression:
    def test_mispricing_within_gate_folds_no_alarm(self):
        # compute consistently 1.6x its price: drift, not regression
        tr, aud = _auditor(_budget(compute_ms=50.0))
        alarms = []
        aud._on_alarm = lambda c, r, d: alarms.append(c)
        t = 0
        for _ in range(WARMUP_STEPS + 15):
            t = _emit_step(tr, t, compute_ms=80.0)
        aud.collect()
        assert alarms == []
        assert aud.alarm_components() == []
        assert aud.drift_factors()["compute"] == pytest.approx(1.6, abs=0.05)

    def test_regression_beyond_gate_alarms_right_component(self):
        tr, aud = _auditor(_budget())
        fired = []
        aud._on_alarm = lambda c, r, d: fired.append((c, r, d))
        t = _run_warmup(tr, aud)
        # data_wait blows past the 2x drift gate; compute stays on-price
        for _ in range(10):
            t = _emit_step(tr, t, data_wait_ms=25.0)
        aud.collect()
        assert [c for c, _, _ in fired] == ["data_wait"]
        assert "data_wait" in aud.alarm_components()
        assert "compute" not in aud.alarm_components()
        c, ratio, detail = fired[0]
        assert ratio > 2.0
        assert detail.startswith("data_wait ")
        assert aud.alarms_total()["data_wait"] >= 1

    def test_alarm_fires_once_per_episode_and_clears(self):
        tr, aud = _auditor(_budget())
        fired = []
        aud._on_alarm = lambda c, r, d: fired.append(c)
        t = _run_warmup(tr, aud)
        for _ in range(12):
            t = _emit_step(tr, t, data_wait_ms=25.0)
        aud.collect()
        assert fired.count("data_wait") == 1  # latched, not per-step
        # recovery: sustained on-budget steps clear the latch
        for _ in range(6):
            t = _emit_step(tr, t)
        aud.collect()
        assert aud.alarm_components() == []

    def test_warmup_window_never_alarms(self):
        tr, aud = _auditor(_budget())
        fired = []
        aud._on_alarm = lambda c, r, d: fired.append(c)
        t = 0
        for _ in range(WARMUP_STEPS):
            t = _emit_step(tr, t, data_wait_ms=50.0)
        aud.collect()
        assert fired == []

    def test_observed_seeded_budget_for_unpriced_component(self):
        # data_wait is not priced: its warmup mean becomes the budget
        b = _budget(data_wait_ms=0.0)
        tr, aud = _auditor(b)
        _run_warmup(tr, aud, data_wait_ms=8.0)
        assert aud.budget().data_wait_s == pytest.approx(0.008, rel=1e-6)
        assert aud.budget().source["data_wait"] == "observed"


class TestResizeNoDoubleCount:
    """The satellite regression test: spans spanning a mesh rebuild
    must not be double-counted into the next step's component
    buckets."""

    def test_straddling_span_clipped_per_window(self):
        # one compute span [0, 100ms) straddles two step windows:
        # step A [0, 60ms), step B [60ms, 120ms). Each bucket gets
        # only its inside portion — summed, never more than the span.
        tr, aud = _auditor(_budget())
        _put(tr, "compute", 0, 100 * MS, depth=1)
        _put(tr, "step", 0, 60 * MS, depth=0)
        _put(tr, "step", 60 * MS, 60 * MS, depth=0)
        res = aud.collect()
        assert len(res) == 2
        a, b = res
        assert a.observed["compute"] == pytest.approx(0.060)
        assert b.observed["compute"] == pytest.approx(0.040)
        total = a.observed["compute"] + b.observed["compute"]
        assert total == pytest.approx(0.100)

    def test_skip_to_now_drops_pre_resize_records(self):
        tr, aud = _auditor(_budget())
        t = _run_warmup(tr, aud)
        # records buffered but not collected when the resize lands
        _put(tr, "compute", t, 500 * MS, depth=1)
        _put(tr, "step", t, 505 * MS, depth=0)
        aud.skip_to_now()  # the resize boundary
        aud.set_budget(_budget(compute_ms=40.0))
        audited_before = aud.steps_audited
        assert aud.collect() == []  # old incarnation fully dropped
        t2 = t + 600 * MS
        for _ in range(WARMUP_STEPS + 1):
            t2 = _emit_step(tr, t2, compute_ms=40.0)
        res = aud.collect()
        assert aud.steps_audited == audited_before + WARMUP_STEPS + 1
        # the post-resize buckets hold only post-resize observation
        assert res[-1].observed["compute"] == pytest.approx(0.040)
        assert aud.alarm_components() == []

    def test_step_coverage_consistent_under_straddle(self):
        # the step_coverage acceptance number stays <= 1 when a child
        # leaks past its parent window (the rebuild-straddle shape):
        # the same clipping rule the auditor buckets use
        tr = SpanTracer(enabled=True)
        _put(tr, "compute", 0, 100 * MS, depth=1)
        _put(tr, "step", 0, 60 * MS, depth=0)
        _put(tr, "step", 60 * MS, 60 * MS, depth=0)
        cov = step_coverage(tr)
        assert cov is not None
        assert cov <= 1.0 + 1e-9


class TestCalibrationPersistence:
    def test_roundtrip_and_fingerprint_reject(self, tmp_path):
        cal = AuditCalibration(
            fingerprint="fp-a",
            factors={"compute": 1.3, "dcn_sync": 2.0},
            samples={"compute": 10, "dcn_sync": 4},
            updated_at=123.0,
        )
        path = save_audit_calibration(cal, dir_override=str(tmp_path))
        assert path and os.path.exists(path)
        back = load_audit_calibration("fp-a", dir_override=str(tmp_path))
        assert back.factors == pytest.approx(cal.factors)
        assert back.samples == cal.samples
        # a cache copied across worlds is rejected, not misapplied
        payload = json.load(open(path))
        payload["fingerprint"] = "fp-b"
        wrong = tmp_path / "auditcal-fp-c.json"
        wrong.write_text(json.dumps(payload))
        assert load_audit_calibration(
            "fp-c", dir_override=str(tmp_path)
        ) is None

    def test_auditor_persist_rate_limited(self, tmp_path):
        tr, aud = _auditor(_budget(compute_ms=50.0))
        t = 0
        for _ in range(WARMUP_STEPS + 5):
            t = _emit_step(tr, t, compute_ms=80.0)  # folds drift
        aud.collect()
        p1 = aud.persist("fp-x", dir_override=str(tmp_path))
        assert p1 is not None
        # no new samples + inside the min interval: both gates hold
        assert aud.persist("fp-x", dir_override=str(tmp_path)) is None
        assert aud.persist(
            "fp-x", dir_override=str(tmp_path), force=True
        ) is not None

    def test_apply_calibration_respects_live_samples(self):
        _tr, aud = _auditor()
        aud.seed_drift("compute", 1.4)  # live evidence
        cal = AuditCalibration(
            fingerprint="fp",
            factors={"compute": 9.0, "dcn_sync": 1.7},
            samples={"compute": 5, "dcn_sync": 5},
        )
        aud.apply_calibration(cal)
        f = aud.drift_factors()
        assert f["compute"] == pytest.approx(1.4)  # disk never outranks
        assert f["dcn_sync"] == pytest.approx(1.7)


class TestDefaultSeams:
    def test_seed_before_install_is_first_wins(self):
        seed_default_drift("compute", 2.0)
        seed_default_drift("compute", 9.0)
        assert current_drift_factors()["compute"] == pytest.approx(2.0)
        _tr, aud = _auditor()
        install_default_auditor(aud)
        # queued seeds transferred into the installed auditor
        assert aud.drift_factors()["compute"] == pytest.approx(2.0)
        assert current_drift_factors()["compute"] == pytest.approx(2.0)

    def test_current_factors_default_to_unity(self):
        f = current_drift_factors()
        assert set(f) == set(COMPONENTS)
        assert all(v == 1.0 for v in f.values())


class TestExportAndIngestion:
    def test_export_publishes_all_series(self):
        tr, aud = _auditor(_budget())
        _run_warmup(tr, aud)
        _emit_step(tr, 10_000 * MS)
        reg = MetricsRegistry()
        assert aud.export(reg) is not None
        scalars = reg.scalars()
        for series in (
            "residual_seconds", "observed_seconds", "budget_seconds",
            "drift_factor", "budget_ratio", "alarm",
        ):
            for c in COMPONENTS:
                key = (
                    f'dlrover_audit_{series}{{component="{c}"}}'
                )
                assert key in scalars, key
        assert scalars["dlrover_audit_steps_total"] == float(
            WARMUP_STEPS + 1
        )

    def test_aggregator_upgrades_straggler_why(self):
        from dlrover_tpu.obs.aggregate import TelemetryAggregator

        agg = TelemetryAggregator()
        agg.observe_metrics(3, 50, metrics={
            'dlrover_audit_budget_ratio{component="dcn_sync"}': 2.4,
            'dlrover_audit_budget_ratio{component="compute"}': 1.01,
            'dlrover_audit_alarm{component="dcn_sync"}': 1.0,
            'dlrover_audit_alarm{component="compute"}': 0.0,
        })
        why = agg.audit_attribution(3)
        assert "dcn_sync is 2.4x its budget" in why
        assert "compute" in why and "on-price" in why
        assert agg.audit_alarms() == {3: ["dcn_sync"]}
        assert agg.audit_attribution(99) == ""
        agg.remove_worker(3)
        assert agg.worker_audit(3) is None

    def test_brain_sink_carries_detail(self):
        from dlrover_tpu.brain.ingestion import straggler_sink
        from dlrover_tpu.brain.service import BrainServicer

        brain = BrainServicer(db_path=":memory:")
        report = straggler_sink(brain, "job-a")
        report(3, 0.5, 0.2, "dcn_sync is 2.4x its budget")
        rows = brain.node_events("job-a")
        assert rows and rows[0].event == "straggler"
        assert "dcn_sync" in rows[0].detail

    def test_an_alarm_leaves_a_bundle_that_names_the_component(
        self, tmp_path
    ):
        """The trainer's side of an alarm (``_on_audit_alarm``, the
        auditor's ``on_alarm``): the recorder's event log carries the
        attribution and a bundle is captured at the moment it fires."""
        import types

        from dlrover_tpu.obs.flight_recorder import FlightRecorder
        from dlrover_tpu.trainer.elastic.trainer import ElasticTrainer

        rec = FlightRecorder(
            base_dir=str(tmp_path), tracer=SpanTracer(enabled=True),
            registry=MetricsRegistry(),
        )
        ElasticTrainer._on_audit_alarm(
            types.SimpleNamespace(_flight=rec), "data_wait", 3.14159,
            "data_wait is 3.1x its budget",
        )
        assert [e["kind"] for e in rec.events()] == ["audit_regression"]
        (bundle,) = [
            d for d in os.listdir(tmp_path) if "audit_regression" in d
        ]
        with open(tmp_path / bundle / "manifest.json") as f:
            manifest = json.load(f)
        assert manifest["reason"] == "audit_regression"
        assert manifest["extra"] == {
            "component": "data_wait", "ratio": 3.142,
            "detail": "data_wait is 3.1x its budget",
        }

    def test_merge_timeline_names_alarm_component(self):
        import sys

        sys.path.insert(
            0, os.path.join(os.path.dirname(__file__), "..", "tools")
        )
        try:
            from merge_timeline import merge_traces
        finally:
            sys.path.pop(0)
        trace = {
            "otherData": {"wall_t0_s": 100.0},
            "traceEvents": [{
                "ph": "X", "name": "step", "pid": 9, "tid": 1,
                "ts": 0, "dur": 5,
            }],
        }
        events = [{
            "ts": 100.5, "kind": "audit_regression",
            "detail": "dcn_sync observed 12.0ms vs budget 5.0ms "
            "(2.40x, source=priced)",
        }]
        merged = merge_traces([trace], ["w0"], events)
        markers = [
            e for e in merged["traceEvents"] if e.get("ph") == "i"
        ]
        assert markers[0]["name"] == "audit_regression:dcn_sync"
        assert markers[0]["args"]["component"] == "dcn_sync"


class TestDryRunnerRepricing:
    def test_reprice_report_per_component(self):
        from dlrover_tpu.accel.dry_runner import (
            DryRunReport,
            reprice_report,
        )

        r = DryRunReport(
            strategy=None,
            ok=True,
            est_step_s=1.0,
            comm_exposed_s=0.3,
            host_exposed_s=0.1,
            comm_ici_s=0.2,
            comm_dcn_s=0.1,
        )
        # compute share is 1.0 - 0.3 - 0.1 = 0.6
        out = reprice_report(r, {
            "compute": 1.0, "ici_sync": 1.0,
            "dcn_sync": 3.0, "host_xfer": 1.0,
        })
        assert out == pytest.approx(0.6 + 0.2 + 0.3 + 0.1)
        # only the drifted leg moved; a scalar calib would have
        # scaled all four
        out2 = reprice_report(r, {"compute": 2.0})
        assert out2 == pytest.approx(1.2 + 0.2 + 0.1 + 0.1)
