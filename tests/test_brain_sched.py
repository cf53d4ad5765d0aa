"""Brain cluster scheduler: curve fitting, allocation, the plan table's
redeliver/ack/expire accounting, the master-side executor, the unified
algorithm verdicts, BrainClient retry treatment, and brain_ctl."""

import time

import pytest

from dlrover_tpu.brain.plan_exec import PlanExecutor
from dlrover_tpu.brain.scheduler import (
    DEFAULT_EXPONENT,
    ClusterScheduler,
    JobState,
    ScalingCurve,
    fit_scaling_curve,
    plan_signature,
    solve_allocation,
)
from dlrover_tpu.brain.service import (
    BrainClient,
    BrainServicer,
    start_brain_service,
)
from dlrover_tpu.common import comm


def _sample(nodes, sps, goodput=0.0, ts=None):
    return comm.JobMetricsSample(
        timestamp=time.time() if ts is None else ts,
        alive_nodes=nodes,
        steps_per_sec=sps,
        goodput_pct=goodput,
    )


def _feed(servicer, job, sizes_speeds, goodput=99.0, ts=None):
    base = time.time() if ts is None else ts
    for i, (n, sps) in enumerate(sizes_speeds):
        servicer.persist_metrics(
            job, _sample(n, sps, goodput=goodput, ts=base + i * 0.001)
        )


def _scheduler(servicer, **kw):
    kw.setdefault("total_chips", 12)
    kw.setdefault("min_dwell_s", 0.0)
    kw.setdefault("hysteresis_frac", 0.0)
    return ClusterScheduler(servicer, **kw)


class TestScalingCurve:
    def test_power_law_fit_recovers_exponent(self):
        true = lambda n: 3.0 * n**0.8  # noqa: E731
        c = fit_scaling_curve({n: true(n) for n in (2, 4, 8, 16)})
        assert abs(c.b - 0.8) < 1e-6
        assert abs(c.a - 3.0) < 1e-6
        assert abs(c.predict(32) - true(32)) < 1e-3

    def test_single_point_uses_default_exponent(self):
        c = fit_scaling_curve({4: 20.0})
        assert c.b == DEFAULT_EXPONENT
        assert abs(c.predict(4) - 20.0) < 1e-9

    def test_exponent_clamped_to_concave(self):
        # superlinear observations (cache effects, noise) must not
        # produce a convex curve that breaks greedy optimality
        c = fit_scaling_curve({2: 10.0, 4: 50.0})
        assert c.b == 1.0
        # and "more chips slower" noise must not go negative
        c2 = fit_scaling_curve({2: 10.0, 4: 5.0})
        assert c2.b == 0.0

    def test_empty_and_junk_points(self):
        assert fit_scaling_curve({}) is None
        assert fit_scaling_curve({0: 5.0, 3: 0.0}) is None


class TestSolveAllocation:
    def _job(self, name, b, current=4, **kw):
        return JobState(
            job=name,
            curve=ScalingCurve(a=10.0, b=b),
            current=current,
            **kw,
        )

    def test_linear_job_wins_chips_over_flat(self):
        jobs = [self._job("lin", 0.95), self._job("flat", 0.2)]
        alloc = solve_allocation(jobs, total_chips=8, node_unit=1)
        assert alloc["lin"] > alloc["flat"]
        assert sum(alloc.values()) <= 8
        assert alloc["flat"] >= 1  # starvation floor

    def test_respects_node_unit(self):
        jobs = [self._job("a", 0.9), self._job("b", 0.5)]
        alloc = solve_allocation(jobs, total_chips=16, node_unit=4)
        assert all(n % 4 == 0 for n in alloc.values())
        assert sum(alloc.values()) <= 16

    def test_frozen_job_is_pinned(self):
        jobs = [
            self._job("lin", 0.95),
            self._job("flat", 0.2, current=6, frozen=True),
        ]
        alloc = solve_allocation(jobs, total_chips=12, node_unit=1)
        assert alloc["flat"] == 6  # dwell pin holds its chips
        assert alloc["lin"] <= 6

    def test_flat_curves_leave_chips_idle(self):
        # zero-exponent curves: n^0 is constant, marginal gain 0 —
        # chips must not be burned on jobs they cannot speed up
        jobs = [self._job("a", 0.0), self._job("b", 0.0)]
        alloc = solve_allocation(jobs, total_chips=100, node_unit=1)
        assert sum(alloc.values()) == 2  # floors only

    def test_goodput_weighting_shifts_chips(self):
        # identical curves, one job at half goodput: its chips yield
        # half the productive throughput -> the healthy job wins ties
        sick = self._job("sick", 0.7, goodput_pct=40.0)
        well = self._job("well", 0.7, goodput_pct=95.0)
        alloc = solve_allocation([sick, well], 9, node_unit=1)
        assert alloc["well"] > alloc["sick"]

    def test_oversubscribed_keeps_current(self):
        jobs = [
            self._job("a", 0.9, current=8, frozen=True),
            self._job("b", 0.9, current=8, frozen=True),
        ]
        alloc = solve_allocation(jobs, total_chips=4, node_unit=1)
        assert alloc == {"a": 8, "b": 8}


class TestPlanTable:
    def test_emit_poll_ack_lifecycle(self):
        s = BrainServicer()
        try:
            v = s.next_plan_version()
            s.record_cluster_plan(
                v,
                [{"job": "j1", "worker_count": 6, "prev_count": 4}],
                time.time(),
            )
            sl = s.cluster_plan_slice("j1")
            assert sl is not None and sl.worker_count == 6
            assert sl.sig == plan_signature(v, "j1", 6, sl.issued_ts)
            # an unacked poll redelivers the same slice
            again = s.cluster_plan_slice("j1")
            assert again is not None and again.version == v
            # the ack clears it
            assert s.cluster_plan_slice("j1", ack_version=v) is None
            assert s.plan_status_counts() == {"acked": 1}
            assert s.last_planned_count("j1") == 6
        finally:
            s.close()

    def test_outcome_report_is_the_sign_off(self):
        s = BrainServicer()
        try:
            v = s.next_plan_version()
            s.record_cluster_plan(
                v, [{"job": "j1", "worker_count": 2}], time.time()
            )
            s.record_plan_outcome(
                comm.PlanOutcomeReport(
                    job_name="j1",
                    version=v,
                    worker_count=2,
                    decision_to_resized_ms=42.0,
                    realized_goodput_pct=97.5,
                )
            )
            assert s.plan_status_counts() == {"acked": 1}
            assert s.latest_outcome_latencies() == {"j1": 42.0}
            hist = s.plan_history("j1")
            assert hist[0]["realized_goodput_pct"] == 97.5
            # replay (the retried idempotent report) is a no-op
            s.record_plan_outcome(
                comm.PlanOutcomeReport(
                    job_name="j1", version=v, worker_count=2,
                    decision_to_resized_ms=42.0,
                )
            )
            assert len(s.plan_history("j1")) == 1
        finally:
            s.close()

    def test_new_version_supersedes_pending(self):
        s = BrainServicer()
        try:
            s.record_cluster_plan(
                1, [{"job": "j1", "worker_count": 2}], time.time()
            )
            s.record_cluster_plan(
                2, [{"job": "j1", "worker_count": 8}], time.time()
            )
            sl = s.cluster_plan_slice("j1")
            assert sl.version == 2 and sl.worker_count == 8
            assert s.plan_status_counts() == {
                "pending": 1,
                "superseded": 1,
            }
        finally:
            s.close()

    def test_unacked_plans_expire_not_vanish(self):
        s = BrainServicer()
        try:
            s.record_cluster_plan(
                1, [{"job": "dead", "worker_count": 2}], time.time() - 100
            )
            assert s.expire_stale_plans(time.time() - 50) == 1
            assert s.plan_status_counts() == {"expired": 1}
            assert s.cluster_plan_slice("dead") is None
            # an expired plan is NOT the current allocation
            assert s.last_planned_count("dead") == 0
        finally:
            s.close()

    def test_active_jobs_windows_and_job_end(self):
        s = BrainServicer()
        try:
            now = time.time()
            _feed(s, "live", [(2, 5.0)], ts=now)
            _feed(s, "stale", [(2, 5.0)], ts=now - 1000)
            _feed(s, "done", [(2, 5.0)], ts=now)
            s.record_job_end(
                comm.BrainJobEndReport(job_name="done")
            )
            assert s.active_jobs(now - 300) == ["live"]
            # a resubmitted job (fresh rows after its end) is active
            _feed(s, "done", [(2, 6.0)], ts=now + 10)
            assert s.active_jobs(now - 300) == ["done", "live"]
        finally:
            s.close()


class TestSchedulerPass:
    def test_pass_reallocates_toward_better_scaler(self):
        s = BrainServicer()
        try:
            sched = _scheduler(s, total_chips=8)
            _feed(s, "lin", [(4, 10 * 4**0.95)])
            _feed(s, "flat", [(4, 10 * 4**0.2)])
            v = sched.run_pass()
            assert v is not None
            lin = s.cluster_plan_slice("lin")
            flat = s.cluster_plan_slice("flat")
            assert lin is not None and lin.worker_count > 4
            assert flat is not None and flat.worker_count < 4
            assert flat.worker_count >= 1  # starvation floor
        finally:
            s.close()

    def test_hysteresis_holds_marginal_gains(self):
        s = BrainServicer()
        try:
            # identical jobs at the optimum: any move is churn
            sched = _scheduler(s, total_chips=8, hysteresis_frac=0.05)
            _feed(s, "a", [(4, 20.0)])
            _feed(s, "b", [(4, 20.0)])
            assert sched.run_pass() is None
            assert s.plan_status_counts() == {}
        finally:
            s.close()

    def test_min_dwell_pins_recently_resized(self):
        s = BrainServicer()
        try:
            sched = _scheduler(s, total_chips=8, min_dwell_s=3600.0)
            _feed(s, "lin", [(4, 10 * 4**0.95)])
            _feed(s, "flat", [(4, 10 * 4**0.2)])
            v1 = sched.run_pass()
            assert v1 is not None
            # both jobs just changed: the very next pass pins them
            assert sched.run_pass() is None
        finally:
            s.close()

    def test_goodput_rows_drive_the_objective(self):
        """The PR-7 goodput_pct column (the fleet_goodput number the
        collector persists) is consumed as the utility weight — same
        curves, the low-goodput job loses chips."""
        s = BrainServicer()
        try:
            sched = _scheduler(s, total_chips=9)
            _feed(s, "sick", [(4, 20.0)], goodput=40.0)
            _feed(s, "well", [(4, 20.0)], goodput=95.0)
            assert sched.run_pass() is not None
            well = s.cluster_plan_slice("well")
            sick = s.cluster_plan_slice("sick")
            got = {
                "well": well.worker_count if well else 4,
                "sick": sick.worker_count if sick else 4,
            }
            assert got["well"] > got["sick"]
        finally:
            s.close()

    def test_feedback_row_closes_the_loop(self):
        """The scheduler's next pass sees the outcome of its last one:
        the acked plan's count becomes the job's current allocation."""
        s = BrainServicer()
        try:
            sched = _scheduler(s, total_chips=8)
            _feed(s, "lin", [(4, 10 * 4**0.95)])
            _feed(s, "flat", [(4, 10 * 4**0.2)])
            v = sched.run_pass()
            lin = s.cluster_plan_slice("lin")
            s.record_plan_outcome(
                comm.PlanOutcomeReport(
                    job_name="lin", version=v,
                    worker_count=lin.worker_count,
                    decision_to_resized_ms=9.0,
                )
            )
            st = sched.job_state("lin", time.time())
            assert st.current == lin.worker_count
        finally:
            s.close()

    def test_underperformance_verdict_lands_in_node_events(self):
        """Satellite: run_algorithms verdicts feed the scheduler pass
        and are persisted as node_events rows, once per episode."""
        s = BrainServicer()
        try:
            # fleet history: someone completed at 4 nodes, 20 steps/s
            _feed(s, "hist", [(4, 20.0)])
            s.record_job_end(
                comm.BrainJobEndReport(
                    job_name="hist", exit_reason="completed"
                )
            )
            sched = _scheduler(s, total_chips=8)
            _feed(s, "slow", [(4, 5.0)])  # 25% of fleet best
            sched.run_pass()
            events = s.node_events(job="slow", event="underperformance")
            assert len(events) == 1
            sched.run_pass()  # same episode: no re-fire
            assert (
                len(s.node_events(job="slow", event="underperformance"))
                == 1
            )
        finally:
            s.close()

    def test_hot_verdict_raises_floor(self):
        s = BrainServicer()
        try:
            sched = _scheduler(s, total_chips=8)
            _feed(s, "hot", [(2, 10.0)] * 6)
            for nid, host in ((0, "h0"), (1, "h1")):
                s.record_node_event(
                    comm.BrainNodeEventReport(
                        job_name="hot", node_id=nid, hostname=host,
                        event="hot", cpu_percent=96.0,
                    )
                )
            st = sched.job_state("hot", time.time())
            assert "hot" in st.verdicts
            assert st.floor >= 3  # current 2 + one unit
        finally:
            s.close()

    def test_bad_node_exclusion_rides_the_slice(self):
        s = BrainServicer()
        try:
            for job in ("j1", "j2"):
                s.record_node_event(
                    comm.BrainNodeEventReport(
                        job_name=job, node_id=0, hostname="cursed",
                        event="failed",
                    )
                )
            sched = _scheduler(s, total_chips=8)
            _feed(s, "lin", [(4, 10 * 4**0.95)])
            _feed(s, "flat", [(4, 10 * 4**0.2)])
            assert sched.run_pass() is not None
            sl = s.cluster_plan_slice("lin")
            assert sl.exclude_hosts == ["cursed"]
        finally:
            s.close()

    def test_gauges_exported(self):
        from dlrover_tpu.obs.metrics import MetricsRegistry

        reg = MetricsRegistry()
        s = BrainServicer()
        try:
            sched = _scheduler(s, total_chips=8, registry=reg)
            _feed(s, "lin", [(4, 10 * 4**0.95)])
            _feed(s, "flat", [(4, 10 * 4**0.2)])
            sched.run_pass()
            text = reg.prometheus_text()
            assert 'dlrover_brain_allocation{job="lin"}' in text
            assert "dlrover_brain_plan_version 1" in text
            assert 'dlrover_brain_plans{status="pending"} 2' in text
            assert "dlrover_brain_plans_emitted 2" in text
        finally:
            s.close()

    def test_scheduler_survives_brain_restart(self, tmp_path):
        """Dwell bookkeeping and plan versions are seeded from the
        store: a restarted Brain neither replays version 1 nor
        immediately re-resizes a job inside its dwell window."""
        db = str(tmp_path / "brain.db")
        s = BrainServicer(db_path=db)
        sched = _scheduler(s, total_chips=8)
        _feed(s, "lin", [(4, 10 * 4**0.95)])
        _feed(s, "flat", [(4, 10 * 4**0.2)])
        v1 = sched.run_pass()
        assert v1 == 1
        s.close()

        s2 = BrainServicer(db_path=db)
        try:
            sched2 = _scheduler(s2, total_chips=8, min_dwell_s=3600.0)
            assert s2.next_plan_version() == 2
            # both jobs changed moments ago: dwell pins them
            _feed(s2, "lin", [(4, 10 * 4**0.95)])
            assert sched2.run_pass() is None
        finally:
            s2.close()


class _Exec:
    """One simulated job master: auto-scaler on the local backend."""

    def __init__(self, addr, job, start_n=4, goodput_fn=None):
        from dlrover_tpu.master.job_auto_scaler import JobAutoScaler
        from dlrover_tpu.master.job_manager import JobManager
        from dlrover_tpu.master.scaler import CallbackScaler

        self.jm = JobManager()
        self.jm.create_initial_nodes(start_n)
        self.scaler = CallbackScaler(lambda plan: None)
        self.auto = JobAutoScaler(
            self.jm, scaler=self.scaler, target_nodes=start_n
        )
        self.client = BrainClient(addr, job)
        self.executor = PlanExecutor(
            self.client, self.auto, goodput_fn=goodput_fn
        )

    def close(self):
        self.client.close()


@pytest.fixture()
def brain_sched():
    server, servicer, addr = start_brain_service(
        scheduler=True, total_chips=8
    )
    servicer.scheduler.stop()  # tests drive passes manually
    servicer.scheduler.min_dwell_s = 0.0
    servicer.scheduler.hysteresis_frac = 0.0
    yield servicer, addr
    server.stop(grace=1)
    servicer.close()


class TestPlanExecutor:
    def test_closed_loop_over_grpc(self, brain_sched):
        servicer, addr = brain_sched
        lin = _Exec(addr, "lin", goodput_fn=lambda: 88.0)
        flat = _Exec(addr, "flat")
        try:
            lin.client.persist_metrics(_sample(4, 10 * 4**0.95))
            flat.client.persist_metrics(_sample(4, 10 * 4**0.2))
            v = servicer.scheduler.run_pass()
            assert v is not None
            assert lin.executor.poll_once() == v
            assert flat.executor.poll_once() == v
            assert lin.auto.target > 4 > flat.auto.target
            # outcome feedback landed, with the goodput the master saw
            hist = servicer.plan_history("lin")
            assert hist[0]["status"] == "acked"
            assert hist[0]["decision_to_resized_ms"] is not None
            assert hist[0]["realized_goodput_pct"] == 88.0
            # nothing pending -> the next poll is a no-op
            assert lin.executor.poll_once() is None
        finally:
            lin.close()
            flat.close()

    def test_redelivers_until_acked(self, brain_sched):
        """A lost outcome report leaves ack unadvanced: the slice is
        redelivered and re-executing scale_to is idempotent."""
        servicer, addr = brain_sched
        ex = _Exec(addr, "lin")
        try:
            ex.client.persist_metrics(_sample(4, 10 * 4**0.95))
            v = servicer.scheduler.run_pass()
            orig = ex.client.report_plan_outcome
            ex.client.report_plan_outcome = lambda *a, **k: (
                (_ for _ in ()).throw(ConnectionError("brain down"))
            )
            assert ex.executor.poll_once() == v
            assert ex.executor.acked_version == 0  # NOT acked
            assert servicer.plan_status_counts().get("pending") == 1
            ex.client.report_plan_outcome = orig
            assert ex.executor.poll_once() == v  # redelivered
            assert ex.executor.acked_version == v
            assert servicer.plan_status_counts() == {"acked": 1}
            assert len(ex.executor.executed) == 2
            assert ex.executor.executed[0][1] == ex.executor.executed[1][1]
        finally:
            ex.close()

    def test_bad_signature_rejected_not_executed(self, brain_sched):
        servicer, addr = brain_sched
        ex = _Exec(addr, "lin")
        try:
            ex.client.persist_metrics(_sample(4, 10 * 4**0.95))
            v = servicer.scheduler.run_pass()
            with servicer._lock:
                servicer._conn.execute(
                    "UPDATE cluster_plans SET worker_count = 999 "
                    "WHERE job='lin'"
                )
                servicer._conn.commit()
            assert ex.executor.poll_once() is None
            assert ex.auto.target == 4  # tampered plan not executed
            assert ex.executor.acked_version == v  # but not poison-looped
        finally:
            ex.close()

    def test_nonpositive_count_rejected(self, brain_sched):
        """The signature proves integrity, not sanity: a signed slice
        asking for <= 0 workers must be refused (eviction is the
        operator's call), not executed or redelivery-looped."""
        servicer, addr = brain_sched
        ex = _Exec(addr, "lin")
        try:
            servicer.record_cluster_plan(
                1,
                [{"job": "lin", "worker_count": 0, "prev_count": 4}],
                time.time(),
            )
            assert ex.executor.poll_once() is None
            assert ex.auto.target == 4
            assert ex.executor.acked_version == 1  # no poison loop
        finally:
            ex.close()

    def test_exclude_hosts_reach_the_scaler(self, brain_sched):
        servicer, addr = brain_sched
        seen = []

        class _Scaler:
            def scale(self, plan):
                pass

            def set_exclude_hosts(self, hosts):
                seen.append(tuple(hosts))

        ex = _Exec(addr, "lin")
        ex.auto._scaler = _Scaler()
        try:
            for job in ("j1", "j2"):
                servicer.record_node_event(
                    comm.BrainNodeEventReport(
                        job_name=job, hostname="cursed", event="oom"
                    )
                )
            ex.client.persist_metrics(_sample(4, 10 * 4**0.95))
            servicer.scheduler.run_pass()
            ex.executor.poll_once()
            assert ("cursed",) in seen
        finally:
            ex.close()


def test_master_env_wiring_runs_the_execution_leg(monkeypatch):
    """DLROVER_TPU_BRAIN_ADDR + a platform scaler wires the whole
    execution leg into LocalJobMaster with zero explicit plumbing: the
    PlanExecutor polls the job's slice and drives scale_to."""
    from dlrover_tpu.master.local_master import LocalJobMaster
    from dlrover_tpu.master.scaler import CallbackScaler

    server, servicer, addr = start_brain_service(
        scheduler=True, total_chips=8
    )
    servicer.scheduler.stop()
    servicer.scheduler.min_dwell_s = 0.0
    servicer.scheduler.hysteresis_frac = 0.0
    monkeypatch.setenv("DLROVER_TPU_BRAIN_ADDR", addr)
    monkeypatch.setenv("DLROVER_TPU_JOB_NAME", "env-exec")
    m = LocalJobMaster(
        node_num=4, scaler=CallbackScaler(lambda plan: None)
    )
    m.prepare()
    try:
        assert m.plan_executor is not None
        _feed(servicer, "env-exec", [(4, 10 * 4**0.95)])
        _feed(servicer, "env-other", [(4, 10 * 4**0.2)])
        v = servicer.scheduler.run_pass()
        assert v is not None
        # the daemon is running on its own cadence; drive one poll
        # deterministically instead of sleeping through an interval
        assert m.plan_executor.poll_once() in (v, None)
        assert m.auto_scaler.target > 4
        assert servicer.plan_history("env-exec")[0]["status"] == "acked"
    finally:
        m.stop()
        server.stop(grace=1)
        servicer.close()


class TestBrainClientRetries:
    """Satellite: the PR-5 retry treatment on the Brain link — jittered
    retries with a budget on the series/decision legs, single-attempt
    fire-and-forget on the mirror/event legs."""

    def _client(self, monkeypatch, fail_times=99):
        import dlrover_tpu.agent.master_client as mc

        c = BrainClient("127.0.0.1:1", "j", retries=3, retry_budget_s=30.0)
        calls = {"n": 0}

        def rpc(payload, timeout=None):
            calls["n"] += 1
            if calls["n"] <= fail_times:
                raise OSError("link down")
            return comm.serialize_message(comm.BaseResponse())

        monkeypatch.setattr(c._client, "_get_rpc", rpc)
        monkeypatch.setattr(c._client, "_report_rpc", rpc)
        monkeypatch.setattr(mc.random, "uniform", lambda a, b: 0.0)
        return c, calls

    def test_persist_metrics_retries_with_backoff(self, monkeypatch):
        c, calls = self._client(monkeypatch)
        with pytest.raises(ConnectionError):
            c.persist_metrics(_sample(2, 5.0))
        assert calls["n"] == 3

    def test_flaky_link_recovers_mid_call(self, monkeypatch):
        c, calls = self._client(monkeypatch, fail_times=1)
        c.persist_metrics(_sample(2, 5.0))  # 2nd attempt lands
        assert calls["n"] == 2
        c.poll_cluster_plan()  # the plan channel gets the same leg
        assert calls["n"] == 3  # healthy link: one attempt

    def test_event_legs_are_single_attempt(self, monkeypatch):
        c, calls = self._client(monkeypatch)
        with pytest.raises(ConnectionError):
            c.report_node_event(0, "h", "oom")
        assert calls["n"] == 1
        calls["n"] = 0
        with pytest.raises(ConnectionError):
            c.report_job_end("failed")
        assert calls["n"] == 1

    def test_retry_budget_bounds_the_tail(self, monkeypatch):
        import dlrover_tpu.agent.master_client as mc

        c = BrainClient(
            "127.0.0.1:1", "j", retries=10, retry_budget_s=0.0
        )
        calls = {"n": 0}

        def rpc(payload, timeout=None):
            calls["n"] += 1
            raise OSError("down")

        monkeypatch.setattr(c._client, "_get_rpc", rpc)
        monkeypatch.setattr(mc.random, "uniform", lambda a, b: 1.0)
        with pytest.raises(ConnectionError):
            c.optimize()
        assert calls["n"] == 1  # budget exhausted before any backoff


class TestScaleRequestEntry:
    def test_servicer_scale_request_drives_scale_to(self):
        from dlrover_tpu.agent.master_client import MasterClient
        from dlrover_tpu.master.local_master import LocalJobMaster
        from dlrover_tpu.master.scaler import CallbackScaler

        m = LocalJobMaster(
            node_num=2, scaler=CallbackScaler(lambda plan: None)
        )
        m.prepare()
        c = MasterClient(m.addr, node_id=0)
        try:
            assert c.request_scale(4) is True
            assert m.auto_scaler.target == 4
        finally:
            c.close()
            m.stop()

    def test_scalerless_master_refuses_scale_request(self):
        """No platform scaler -> executing scale_to would fabricate
        ghost node entries nothing launches; the request is refused."""
        from dlrover_tpu.agent.master_client import MasterClient
        from dlrover_tpu.master.local_master import LocalJobMaster

        m = LocalJobMaster(node_num=2)
        m.prepare()
        c = MasterClient(m.addr, node_id=0)
        try:
            assert c.request_scale(4) is False
            assert m.auto_scaler.target == 2
        finally:
            c.close()
            m.stop()


class TestBrainCtl:
    def _store(self, tmp_path):
        db = str(tmp_path / "brain.db")
        s = BrainServicer(db_path=db)
        _feed(s, "lin", [(2, 10 * 2**0.9), (4, 10 * 4**0.9)])
        sched = _scheduler(s, total_chips=8)
        v = sched.run_pass()
        sl = s.cluster_plan_slice("lin")
        s.record_plan_outcome(
            comm.PlanOutcomeReport(
                job_name="lin", version=v,
                worker_count=sl.worker_count,
                decision_to_resized_ms=17.5,
                realized_goodput_pct=96.0,
            )
        )
        s.record_node_event(
            comm.BrainNodeEventReport(
                job_name="lin", hostname="h1", event="straggler"
            )
        )
        s.close()
        return db

    def test_jobs_and_curves(self, tmp_path, capsys):
        from tools.brain_ctl import main

        db = self._store(tmp_path)
        assert main([db, "jobs"]) == 0
        out = capsys.readouterr().out
        assert "lin" in out and "goodput_pct" in out
        assert main([db, "curves", "--json"]) == 0
        import json

        rows = json.loads(capsys.readouterr().out)
        assert rows[0]["job"] == "lin"
        assert abs(rows[0]["b"] - 0.9) < 0.01
        assert rows[0]["points"]["4"] > rows[0]["points"]["2"]

    def test_plans_show_realized_outcome(self, tmp_path, capsys):
        """Acceptance: the realized-outcome feedback row is visible in
        brain_ctl output."""
        from tools.brain_ctl import main

        db = self._store(tmp_path)
        assert main([db, "plans", "--json"]) == 0
        import json

        rows = json.loads(capsys.readouterr().out)
        acked = [r for r in rows if r["status"] == "acked"]
        assert acked and acked[0]["decision_to_resized_ms"] == 17.5
        assert acked[0]["realized_goodput_pct"] == 96.0

    def test_events_and_missing_store(self, tmp_path, capsys):
        from tools.brain_ctl import main

        db = self._store(tmp_path)
        assert main([db, "events"]) == 0
        assert "straggler" in capsys.readouterr().out
        assert main([str(tmp_path / "nope.db"), "jobs"]) == 1


def test_the_closed_loop_beats_the_equal_split_and_drops_no_plan():
    """Three simulated jobs with unequal scaling curves (near-linear,
    knee, flat) under one Brain with the ClusterScheduler over real gRPC,
    each job's ``PlanExecutor`` driving a real ``JobAutoScaler``: the
    loop's allocation of the same 12 chips runs faster in aggregate than
    four each, the decision -> resized latency is recorded, and every
    emitted plan slice ends acked or expired, never silently dropped (one
    job goes dark for the first rounds, one dies before ever polling)."""
    from dlrover_tpu.brain.plan_exec import PlanExecutor
    from dlrover_tpu.brain.service import BrainClient, start_brain_service
    from dlrover_tpu.master.job_auto_scaler import JobAutoScaler
    from dlrover_tpu.master.job_manager import JobManager
    from dlrover_tpu.master.scaler import CallbackScaler

    total_chips, start_n = 12, 4
    curves = {"sim-lin": 0.95, "sim-knee": 0.55, "sim-flat": 0.20}

    def true_speed(job, n):
        return 10.0 * max(0, n) ** curves[job]

    server, servicer, addr = start_brain_service(
        scheduler=True, total_chips=total_chips
    )
    sched = servicer.scheduler
    sched.stop()  # passes are driven by hand: deterministic rounds
    sched.min_dwell_s = 0.0
    sched.hysteresis_frac = 0.01
    jobs = {}
    try:
        for job in curves:
            jm = JobManager()
            jm.create_initial_nodes(start_n)
            auto = JobAutoScaler(
                jm, scaler=CallbackScaler(lambda plan: None),
                target_nodes=start_n,
            )
            cli = BrainClient(addr, job)
            jobs[job] = (auto, cli, PlanExecutor(cli, auto))
        for rnd in range(8):
            for job, (auto, cli, _ex) in jobs.items():
                cli.persist_metrics(comm.JobMetricsSample(
                    timestamp=time.time(), alive_nodes=auto.target,
                    steps_per_sec=true_speed(job, auto.target),
                    goodput_pct=99.0,
                ))
            sched.run_pass()
            for job, (_auto, _cli, ex) in jobs.items():
                if job == "sim-flat" and rnd < 2:
                    continue  # dark: its slices must expire, visibly
                ex.poll_once()
        servicer.record_cluster_plan(
            servicer.next_plan_version(),
            [{
                "job": "sim-zombie", "worker_count": 2, "prev_count": 4,
                "reason": "master died before ack",
            }],
            time.time(),
        )
        with servicer._lock:
            servicer._conn.execute(
                "UPDATE cluster_plans SET ts = ts - ? "
                "WHERE status='pending'",
                (sched.plan_ttl_s + 1,),
            )
            servicer._conn.commit()
        servicer.expire_stale_plans(time.time() - sched.plan_ttl_s)

        alloc = {job: auto.target for job, (auto, _c, _e) in jobs.items()}
        assert sum(alloc.values()) <= total_chips
        assert sum(true_speed(j, n) for j, n in alloc.items()) > sum(
            true_speed(j, start_n) for j in curves
        )
        assert any(ex.executed for _a, _c, ex in jobs.values())
        counts = servicer.plan_status_counts()
        assert counts.get("pending", 0) == 0
        assert counts.get("acked", 0) > 0
        assert counts.get("expired", 0) > 0
        assert any(
            r["decision_to_resized_ms"] is not None
            for r in servicer.plan_history()
        )
    finally:
        for _auto, cli, _ex in jobs.values():
            cli.close()
        server.stop(grace=1)
        servicer.close()
