"""Brain service: persist/optimize/query over real gRPC + sqlite."""

import time

import pytest

from dlrover_tpu.brain import BrainClient, start_brain_service
from dlrover_tpu.common import comm
from dlrover_tpu.master.resource.optimizer import JobResourceOptimizer


def _sample(nodes, sps, mem=1000, ts=None):
    return comm.JobMetricsSample(
        timestamp=ts or time.time(),
        alive_nodes=nodes,
        steps_per_sec=sps,
        total_memory_mb=mem,
    )


@pytest.fixture()
def brain():
    server, servicer, addr = start_brain_service()
    yield addr
    server.stop(grace=1)
    servicer.close()


class TestBrain:
    def test_persist_and_query_isolated_per_job(self, brain):
        a = BrainClient(brain, "job-a")
        b = BrainClient(brain, "job-b")
        try:
            a.persist_metrics(_sample(4, 10.0, ts=1.0))
            a.persist_metrics(_sample(4, 12.0, ts=2.0))
            b.persist_metrics(_sample(2, 5.0, ts=1.5))
            assert len(a.get_job_metrics()) == 2
            got_b = b.get_job_metrics()
            assert len(got_b) == 1 and got_b[0].alive_nodes == 2
        finally:
            a.close()
            b.close()

    def test_optimize_recommends_scale_down(self, brain):
        c = BrainClient(brain, "job-c")
        try:
            c.persist_metrics(_sample(4, 10.0, ts=1.0))
            c.persist_metrics(_sample(8, 11.0, ts=2.0))  # bad scaling
            plan = c.optimize()
            assert plan.worker_count == 4
            assert "recommend 4" in plan.reason
        finally:
            c.close()

    def test_master_optimizer_uses_brain(self, brain):
        """The JobResourceOptimizer brain seam end to end over RPC."""
        c = BrainClient(brain, "job-d")
        try:
            c.persist_metrics(_sample(4, 10.0, ts=1.0))
            c.persist_metrics(_sample(8, 11.0, ts=2.0))
            opt = JobResourceOptimizer(brain=c.optimizer())
            plan = opt.generate_plan()
            assert plan.worker_count == 4
        finally:
            c.close()

    def test_reporter_seam_feeds_brain(self, brain):
        from dlrover_tpu.master.stats.collector import JobMetricCollector

        c = BrainClient(brain, "job-e")

        class _SM:
            completed_global_step = 9

            def running_speed(self):
                return 2.0

        try:
            coll = JobMetricCollector(None, _SM(), reporter=c.reporter())
            coll.collect()
            coll.flush_reports()  # reporting is fire-and-forget
            samples = c.get_job_metrics()
            assert len(samples) == 1 and samples[0].global_step == 9
        finally:
            c.close()

    def test_persistence_across_restart(self, tmp_path):
        db = str(tmp_path / "brain.db")
        server, servicer, addr = start_brain_service(db_path=db)
        c = BrainClient(addr, "job-f")
        c.persist_metrics(_sample(3, 7.0, ts=1.0))
        c.close()
        server.stop(grace=1)
        servicer.close()

        server2, servicer2, addr2 = start_brain_service(db_path=db)
        c2 = BrainClient(addr2, "job-f")
        try:
            samples = c2.get_job_metrics()
            assert len(samples) == 1 and samples[0].steps_per_sec == 7.0
        finally:
            c2.close()
            server2.stop(grace=1)
            servicer2.close()


class TestClusterAlgorithms:
    """The cluster-level algorithms a job-local optimizer provably
    cannot reproduce (they need OTHER jobs' data)."""

    def test_cold_start_from_other_jobs_histories(self, brain):
        """Two completed jobs' histories produce a plan for a brand-new
        third job; the job-local optimizer with the same (empty) view of
        that job returns nothing."""
        a = BrainClient(brain, "hist-a")
        b = BrainClient(brain, "hist-b")
        new = BrainClient(brain, "fresh-job")
        try:
            # hist-a scaled 2->4 efficiently (1.9x), peak 500 MB/worker
            a.persist_metrics(_sample(2, 10.0, mem=800, ts=1.0))
            a.persist_metrics(_sample(4, 19.0, mem=2000, ts=2.0))
            a.report_job_end("completed", worker_count=4)
            # hist-b pushed 4->8 for only 1.2x: past the knee
            b.persist_metrics(_sample(4, 19.0, mem=2000, ts=1.0))
            b.persist_metrics(_sample(8, 23.0, mem=4000, ts=2.0))
            b.report_job_end("completed", worker_count=8)

            plan = new.optimize()
            # fit: scale to 4 (worth it), stop before 8 (1.2x < 0.6-rule)
            assert plan.worker_count == 4, plan
            # memory: fleet peak/worker = 500 MB * 1.2 margin
            assert plan.worker_memory_mb == 600, plan
            assert "cold-start" in plan.reason

            # the job-local optimizer cannot: zero samples -> empty plan
            local = JobResourceOptimizer().plan_from_samples(
                new.get_job_metrics()
            )
            assert local.empty()
        finally:
            a.close(); b.close(); new.close()

    def test_oom_adjust_beats_cold_start(self, brain):
        c = BrainClient(brain, "oomy")
        try:
            c.persist_metrics(_sample(2, 5.0, mem=3000, ts=1.0))
            c.report_node_event(0, "host-1", "oom", memory_mb=1800)
            plan = c.optimize()
            # 2x of max(incident 1800, observed 1500/worker)
            assert plan.worker_memory_mb == 3600, plan
            assert "oom adjust" in plan.reason
        finally:
            c.close()

    def test_cross_job_bad_node_exclusion(self, brain):
        """A hostname misbehaving across >= 2 DIFFERENT jobs lands on
        every new plan's exclude list — one job's events alone do not."""
        a = BrainClient(brain, "ex-a")
        b = BrainClient(brain, "ex-b")
        c = BrainClient(brain, "ex-c")
        try:
            a.report_node_event(3, "node-bad", "oom", memory_mb=900)
            plan = c.optimize()
            assert "node-bad" not in plan.exclude_nodes  # 1 job only
            b.report_node_event(5, "node-bad", "failed")
            plan = c.optimize()
            assert plan.exclude_nodes == ("node-bad",), plan
        finally:
            a.close(); b.close(); c.close()

    def test_hot_node_exclusion(self, brain):
        a = BrainClient(brain, "hot-a")
        c = BrainClient(brain, "hot-c")
        try:
            for _ in range(3):
                a.report_node_event(1, "node-hot", "hot", cpu_percent=97.0)
            a.report_node_event(2, "node-warm", "hot", cpu_percent=50.0)
            plan = c.optimize()
            assert plan.exclude_nodes == ("node-hot",), plan
        finally:
            a.close(); c.close()

    def test_init_adjust_right_sizes_early(self, brain):
        """A job with only FIRST samples (too few for the windowed
        optimizer) gets memory right-sized from its own readings + 50%
        (ref optimize_job_ps_init_adjust_resource.go)."""
        c = BrainClient(brain, "young")
        try:
            c.persist_metrics(_sample(2, 5.0, mem=2000, ts=1.0))
            c.persist_metrics(_sample(2, 5.1, mem=2400, ts=2.0))
            plan = c.optimize()
            # peak 1200 MB/worker x 2.0 init headroom (the steady-state
            # rule would give only x1.5 of an underestimating reading)
            assert plan.worker_memory_mb == 2400, plan
            assert "init adjust" in plan.reason
        finally:
            c.close()

    def test_hot_job_scales_out(self, brain):
        """A MAJORITY of one job's nodes running sustained-hot grows
        the worker group by a node-unit (ref
        optimize_job_hot_ps_resource.go) — while a single hot host in
        one job does NOT (that is bad_node_exclusion territory and
        needs cross-job evidence)."""
        c = BrainClient(brain, "hotjob")
        try:
            for i in range(10):
                c.persist_metrics(
                    _sample(4, 9.9 + 0.01 * i, mem=1000, ts=float(i + 1))
                )
            c.report_node_event(0, "h0", "hot", cpu_percent=95.0)
            plan = c.optimize()
            assert (plan.worker_count or 0) <= 4, plan  # 1/4 hot: no
            for nid, host in ((1, "h1"), (2, "h2")):
                c.report_node_event(nid, host, "hot", cpu_percent=96.0)
            plan = c.optimize()
            assert plan.worker_count == 5, plan  # 3/4 hot: scale out
            assert "hot nodes" in plan.reason
        finally:
            c.close()

    def test_profile_rollup_survives_series_eviction(self):
        """Completed jobs' raw series evict after the post-mortem
        window; the cold-start fit still works from the job_profile
        rollup (the MySQL retention-policy analog)."""
        import dlrover_tpu.brain.service as svc

        s = svc.BrainServicer()
        try:
            s.persist_metrics("old", _sample(2, 10.0, mem=800, ts=1.0))
            s.persist_metrics("old", _sample(4, 19.0, mem=2000, ts=2.0))
            s.record_job_end(
                comm.BrainJobEndReport(
                    job_name="old", exit_reason="completed",
                    worker_count=4, worker_memory_mb=0,
                )
            )
            # age the job-end stamp past the retention window, then
            # trigger eviction via another job's end
            s._conn.execute(
                "UPDATE job_end SET end_ts = end_ts - ? WHERE job = 'old'",
                (svc._SERIES_RETENTION_S + 10,),
            )
            s.record_job_end(
                comm.BrainJobEndReport(
                    job_name="other", exit_reason="failed",
                    worker_count=0, worker_memory_mb=0,
                )
            )
            assert s.job_metrics("old") == []  # raw series gone
            speed, peak, n_jobs = s.fleet_size_curve()
            assert n_jobs == 1
            assert speed == {2: 10.0, 4: 19.0}  # rollup intact
            assert peak == 500.0
        finally:
            s.close()

    def test_prune_is_batched_but_bounded(self):
        from dlrover_tpu.brain.service import BrainServicer, _PRUNE_EVERY

        s = BrainServicer(max_rows_per_job=100)
        try:
            n = 100 + 2 * _PRUNE_EVERY
            for i in range(n):
                s.persist_metrics("j", _sample(1, 1.0, ts=float(i + 1)))
            rows = s.job_metrics("j")
            # bounded within one prune batch of slack, and the retained
            # rows are the newest
            assert len(rows) <= 100 + _PRUNE_EVERY
            assert rows[-1].timestamp == float(n)
        finally:
            s.close()


def test_job_manager_feeds_brain_node_events(brain):
    """OOM/failure incidents flow master -> Brain through the
    brain_reporter seam, and surface in another job's exclude list once
    a second job condemns the same host."""
    from dlrover_tpu.common.constants import NodeEventType
    from dlrover_tpu.common.node import Node, NodeExitReason, NodeStatus
    from dlrover_tpu.master.job_manager import JobManager, NodeEvent

    a = BrainClient(brain, "jm-a")
    b = BrainClient(brain, "jm-b")
    c = BrainClient(brain, "jm-c")
    try:
        for cli in (a, b):
            jm = JobManager(
                brain_reporter=lambda nid, host, ev, mem, detail="", _c=cli: (
                    _c.report_node_event(
                        nid, host, ev, memory_mb=mem, detail=detail
                    )
                )
            )
            n = Node("worker", 0)
            n.update_status(NodeStatus.RUNNING)
            jm.add_node(n)
            failed = Node("worker", 0)
            # the PHYSICAL host (pod spec.nodeName), carried by the
            # watcher's event node — logical "worker-0" must never be
            # what condemns a host cluster-wide
            failed.hostname = "flaky-host"
            failed.exit_reason = NodeExitReason.OOM
            failed.update_status(NodeStatus.FAILED)
            jm.process_event(NodeEvent(NodeEventType.MODIFIED, failed))
        # the reporter is fire-and-forget on a daemon thread (it must
        # never block relaunch) — poll for delivery
        deadline = time.time() + 10
        plan = c.optimize()
        while plan.exclude_nodes != ("flaky-host",) and time.time() < deadline:
            time.sleep(0.1)
            plan = c.optimize()
        assert plan.exclude_nodes == ("flaky-host",), plan
    finally:
        a.close(); b.close(); c.close()


def test_exclusion_enforced_via_pod_anti_affinity(brain):
    """The full enforcement chain: Brain condemns a host -> auto-scaler
    pushes the exclude list into the scaler -> every launched pod
    carries hostname NotIn anti-affinity."""
    from dlrover_tpu.common.node import Node, NodeResource
    from dlrover_tpu.k8s.client import FakeK8sApi
    from dlrover_tpu.k8s.scaler import PodScaler
    from dlrover_tpu.master.job_auto_scaler import JobAutoScaler
    from dlrover_tpu.master.job_manager import JobManager
    from dlrover_tpu.master.resource.optimizer import JobResourceOptimizer
    from dlrover_tpu.master.scaler import ScalePlan

    a = BrainClient(brain, "aff-a")
    b = BrainClient(brain, "aff-b")
    c = BrainClient(brain, "aff-c")
    try:
        a.report_node_event(0, "cursed-host", "oom", memory_mb=512)
        b.report_node_event(0, "cursed-host", "failed")

        api = FakeK8sApi()
        scaler = PodScaler(api, "aff-job")
        opt = JobResourceOptimizer(brain=c.optimizer())
        auto = JobAutoScaler(
            JobManager(), scaler=scaler, resource_optimizer=opt
        )
        auto.run_optimization_pass()
        scaler.scale(
            ScalePlan(launch_nodes=[Node("worker", 0, rank_index=0)])
        )
        pod = api.pods["aff-job-worker-0"]
        expr = pod["spec"]["affinity"]["nodeAffinity"][
            "requiredDuringSchedulingIgnoredDuringExecution"
        ]["nodeSelectorTerms"][0]["matchExpressions"][0]
        assert expr["operator"] == "NotIn"
        assert expr["values"] == ["cursed-host"]
    finally:
        a.close(); b.close(); c.close()


def test_brain_outage_keeps_standing_exclusions():
    """A Brain outage falls back to the job-local optimizer, whose plan
    carries exclude_nodes=None ("no statement") — standing anti-affinity
    must survive; only an authoritative empty tuple clears it."""
    from dlrover_tpu.master.job_auto_scaler import JobAutoScaler
    from dlrover_tpu.master.job_manager import JobManager
    from dlrover_tpu.master.resource.optimizer import (
        JobResourceOptimizer, ResourcePlan,
    )
    from dlrover_tpu.master.scaler import CallbackScaler

    calls = []

    class _Scaler(CallbackScaler):
        def set_exclude_hosts(self, hosts):
            calls.append(tuple(hosts))

    scaler = _Scaler(lambda plan: None)
    auto = JobAutoScaler(JobManager(), scaler=scaler)

    def _down(samples):
        raise ConnectionError("brain down")

    auto._optimizer = JobResourceOptimizer(brain=_down)
    auto.run_optimization_pass()
    assert calls == [], "outage fallback must not touch exclusions"

    auto._optimizer = JobResourceOptimizer(
        brain=lambda s: ResourcePlan(exclude_nodes=())
    )
    auto.run_optimization_pass()
    assert calls == [()], "authoritative empty tuple clears exclusions"


def test_underperformance_flagged_against_fleet(brain):
    """A running job far below the FLEET's best throughput at the same
    size gets a diagnostic its own history cannot produce."""
    hist = BrainClient(brain, "fast-hist")
    sick = BrainClient(brain, "slow-job")
    healthy = BrainClient(brain, "ok-job")
    try:
        hist.persist_metrics(_sample(4, 20.0, ts=1.0))
        hist.report_job_end("completed", worker_count=4)
        # same size, 25% of fleet best -> flagged
        sick.persist_metrics(_sample(4, 5.0, ts=1.0))
        plan = sick.optimize()
        assert "underperforming vs fleet" in plan.reason, plan
        # 80% of fleet best -> healthy, no flag
        healthy.persist_metrics(_sample(4, 16.0, ts=1.0))
        plan = healthy.optimize()
        assert "underperforming" not in plan.reason, plan
    finally:
        hist.close(); sick.close(); healthy.close()


def test_master_env_wiring_reports_job_end(brain, monkeypatch):
    """DLROVER_TPU_BRAIN_ADDR on the master wires the whole loop with
    zero explicit plumbing: metrics reporter, node events, optimizer
    seam, and the terminal job-end summary that future cold-starts fit
    from."""
    from dlrover_tpu.agent.master_client import MasterClient
    from dlrover_tpu.master.local_master import LocalJobMaster

    monkeypatch.setenv("DLROVER_TPU_BRAIN_ADDR", brain)
    monkeypatch.setenv("DLROVER_TPU_JOB_NAME", "env-wired")
    m = LocalJobMaster(port=0, node_num=1)
    m.prepare()
    c = MasterClient(m.addr, node_id=0)
    try:
        c.report_dataset_shard_params(
            comm.DatasetShardParams(
                batch_size=4, num_minibatches_per_shard=1,
                dataset_size=8, num_epochs=1, dataset_name="ds",
            )
        )
        while True:
            task = c.get_task("ds")
            if task.is_empty:
                break
            c.report_task_result("ds", task.task_id)
        rc = m.run()
        assert rc == "succeeded"
    finally:
        c.close()
        m.stop()  # joins the job-end thread before closing the client

    fresh = BrainClient(brain, "fresh-after-env")
    try:
        # env-wired's completed row exists -> cold start has history
        plan = fresh.optimize()
        assert "cold-start" in plan.reason, plan
    finally:
        fresh.close()


class TestBrainIngestion:
    """review r4 #7: the Brain watches node events ITSELF (ref
    brain/pkg/server/server.go:176 watch manager -> mysql.go:339 sink)
    — raw pod lifecycle drives the datastore and cross-job
    bad-node exclusion with NO job master involved."""

    def _pod(self, api, name, job, node_id, host):
        api.create_pod(
            "default",
            {
                "metadata": {
                    "name": name,
                    "labels": {
                        "elastic.dlrover-tpu.org/job": job,
                        "elastic.dlrover-tpu.org/node-id": str(node_id),
                    },
                },
                "spec": {"nodeName": host},
            },
        )

    def test_raw_pod_failures_drive_exclusion_without_master(self):
        from dlrover_tpu.brain.algorithms import bad_node_exclusion
        from dlrover_tpu.brain.ingestion import BrainNodeWatcher
        from dlrover_tpu.brain.service import BrainServicer
        from dlrover_tpu.k8s.client import FakeK8sApi

        api = FakeK8sApi()
        servicer = BrainServicer()
        watcher = BrainNodeWatcher(api, servicer)

        # the same physical host eats failures in TWO distinct jobs
        self._pod(api, "j1-w0", "job1", 0, "host-bad")
        self._pod(api, "j2-w0", "job2", 0, "host-bad")
        self._pod(api, "j1-w1", "job1", 1, "host-ok")
        watcher._tick()  # records identities, no incidents yet
        assert servicer.node_events() == []

        api.set_pod_phase("j1-w0", "Failed")
        api.set_pod_phase("j2-w0", "Failed")
        watcher._tick()
        events = servicer.node_events()
        assert {(e.job_name, e.event) for e in events} == {
            ("job1", "failed"),
            ("job2", "failed"),
        }
        assert bad_node_exclusion(servicer) == ("host-bad",)

    def test_oom_detected_from_container_status(self):
        from dlrover_tpu.brain.ingestion import BrainNodeWatcher
        from dlrover_tpu.brain.service import BrainServicer
        from dlrover_tpu.k8s.client import FakeK8sApi

        api = FakeK8sApi()
        servicer = BrainServicer()
        watcher = BrainNodeWatcher(api, servicer)
        self._pod(api, "jo-w0", "jobo", 0, "host-x")
        watcher._tick()
        with api._lock:
            pod = api.pods["jo-w0"]
            pod["status"]["phase"] = "Failed"
            pod["status"]["containerStatuses"] = [
                {
                    "state": {
                        "terminated": {
                            "reason": "OOMKilled",
                            "exitCode": 137,
                            "memoryMB": 12345,
                        }
                    }
                }
            ]
        watcher._tick()
        events = servicer.node_events()
        # kubelet terminated-state carries no memory reading: the event
        # classifies as oom, sizing falls to oom_adjust's fallback path
        assert [(e.event, e.memory_mb) for e in events] == [("oom", 0)]

    def test_stale_failed_pods_not_reingested_at_startup(self):
        """A restarted Brain must not re-condemn hosts from pods that
        failed long ago (kubelets keep Failed pods for days): the first
        tick is a baseline pass."""
        from dlrover_tpu.brain.ingestion import BrainNodeWatcher
        from dlrover_tpu.brain.service import BrainServicer
        from dlrover_tpu.k8s.client import FakeK8sApi

        api = FakeK8sApi()
        self._pod(api, "js-w0", "jobs1", 0, "host-s")
        api.set_pod_phase("js-w0", "Failed")  # failed BEFORE Brain start
        servicer = BrainServicer()
        watcher = BrainNodeWatcher(api, servicer)
        watcher._tick()
        assert servicer.node_events() == []
        # but a FRESH failure after startup is ingested
        self._pod(api, "js-w1", "jobs1", 1, "host-s")
        watcher._tick()
        api.set_pod_phase("js-w1", "Failed")
        watcher._tick()
        assert [e.event for e in servicer.node_events()] == ["failed"]

    def test_vanished_pod_is_not_an_incident(self):
        """Routine deletion (scale-down, job GC) must NOT condemn the
        host: with BAD_NODE_MIN_JOBS=2, two ordinary downscales would
        blacklist a healthy machine. Only explicit Failed phases count
        (preemptions surface as Failed with a reason)."""
        from dlrover_tpu.brain.ingestion import BrainNodeWatcher
        from dlrover_tpu.brain.service import BrainServicer
        from dlrover_tpu.k8s.client import FakeK8sApi

        api = FakeK8sApi()
        servicer = BrainServicer()
        watcher = BrainNodeWatcher(api, servicer)
        self._pod(api, "jv-w0", "jobv", 0, "host-p")
        watcher._tick()
        api.delete_pod("default", "jv-w0")  # deliberate scale-down
        watcher._tick()
        assert servicer.node_events() == []

    def test_cluster_config_overrides_exclusion_thresholds(self):
        from dlrover_tpu.brain.algorithms import bad_node_exclusion
        from dlrover_tpu.brain.ingestion import BrainNodeWatcher
        from dlrover_tpu.brain.service import BrainServicer
        from dlrover_tpu.k8s.client import FakeK8sApi

        api = FakeK8sApi()
        servicer = BrainServicer()
        watcher = BrainNodeWatcher(api, servicer)
        self._pod(api, "jc-w0", "job1", 0, "host-c")
        self._pod(api, "jc-w1", "job2", 0, "host-c")
        watcher._tick()
        api.set_pod_phase("jc-w0", "Failed")
        api.set_pod_phase("jc-w1", "Failed")
        watcher._tick()
        # defaults: 2 distinct jobs condemn the host
        assert bad_node_exclusion(servicer) == ("host-c",)
        # per-cluster override raises the bar
        servicer.set_cluster_config("default", "bad_node_min_jobs", "3")
        assert bad_node_exclusion(servicer) == ()

    def test_event_driven_ingestion(self):
        """With a watch-capable API, incidents land without waiting a
        poll interval (poll AND resync pushed beyond the test horizon,
        so only a watch wakeup can deliver)."""
        from dlrover_tpu.brain.ingestion import BrainNodeWatcher
        from dlrover_tpu.brain.service import BrainServicer
        from dlrover_tpu.k8s.client import FakeK8sApi

        api = FakeK8sApi()
        servicer = BrainServicer()
        watcher = BrainNodeWatcher(
            api, servicer, interval=3600.0, resync=3600.0
        )
        watcher.start()
        try:
            time.sleep(0.5)  # let the startup tick pass (empty cluster)
            deadline = time.time() + 5
            self._pod(api, "je-w0", "jobe", 0, "host-e")
            api.set_pod_phase("je-w0", "Failed")
            while not servicer.node_events() and time.time() < deadline:
                time.sleep(0.05)
            assert [e.event for e in servicer.node_events()] == ["failed"]
        finally:
            watcher.stop()

    def test_cluster_config_records(self):
        from dlrover_tpu.brain.service import BrainServicer

        s = BrainServicer()
        s.set_cluster_config("cl-a", "bad_node_min_jobs", "3")
        s.set_cluster_config("cl-a", "bad_node_min_jobs", "4")  # upsert
        s.set_cluster_config("cl-b", "hot_cpu_threshold", "85")
        assert s.cluster_config("cl-a") == {"bad_node_min_jobs": "4"}
        assert s.cluster_config("cl-b") == {"hot_cpu_threshold": "85"}
        assert s.cluster_config("cl-c") == {}
