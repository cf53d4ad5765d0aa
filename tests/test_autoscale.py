"""Auto-scaling + hang recovery: the elastic control loop closes.

Parity: the reference tests its auto-scaler against canned node tables
(test_job_auto_scaler.py) and treats hang as a relaunch trigger, not a
job failure.
"""

import threading
import time

import pytest

from dlrover_tpu.common.constants import (
    JobExitReason,
    NodeExitReason,
    NodeStatus,
)
from dlrover_tpu.common.global_context import Context
from dlrover_tpu.common.node import Node
from dlrover_tpu.master.job_manager import NodeEvent
from dlrover_tpu.master.local_master import LocalJobMaster
from dlrover_tpu.master.scaler import (
    CallbackScaler,
    LocalProcessScaler,
    ScalePlan,
)

_ctx = Context.singleton_instance()


@pytest.fixture()
def master3():
    scaler = CallbackScaler(lambda plan: None)
    m = LocalJobMaster(node_num=3, scaler=scaler)
    # no gRPC server needed: these tests drive the managers directly
    yield m, scaler
    m.auto_scaler.stop()


def _set_running(master, node_id):
    node = master.job_manager.get_node("worker", node_id)
    node.update_status(NodeStatus.RUNNING)
    node.heartbeat_time = time.time()
    master.speed_monitor.add_running_worker(node_id)
    return node


class TestAutoScaler:
    def test_replaces_dead_node(self, master3):
        """A preempted/released node is replaced to restore world size."""
        master, scaler = master3
        for i in range(3):
            _set_running(master, i)
        dead = master.job_manager.get_node("worker", 1)
        dead.is_released = True
        dead.update_status(NodeStatus.FAILED)

        plan = master.auto_scaler.check_and_scale()
        assert len(plan.launch_nodes) == 1
        new = plan.launch_nodes[0]
        assert new.rank_index == 1  # takes over the dead node's rank
        assert len(master.auto_scaler.alive_nodes()) == 3
        assert scaler.plans  # the plan reached the platform scaler

    def test_exhausted_budget_stops_churn(self, master3):
        """A rank whose relaunch budget is spent is NOT replaced forever
        (otherwise a crash-looping node would be respawned every pass)."""
        master, _ = master3
        for i in range(3):
            _set_running(master, i)
        dead = master.job_manager.get_node("worker", 1)
        dead.relaunchable = False  # e.g. fatal user error
        dead.is_released = True
        dead.update_status(NodeStatus.FAILED)

        plan = master.auto_scaler.check_and_scale()
        assert plan.launch_nodes == []
        assert len(master.auto_scaler.alive_nodes()) == 2

    def test_poisoned_rank_does_not_starve_others(self, master3):
        """Rank 1 out of budget, rank 2 entitled: rank 2 must still be
        replaced (a break on the first exhausted rank would starve it)."""
        master, _ = master3
        for i in range(3):
            _set_running(master, i)
        poisoned = master.job_manager.get_node("worker", 1)
        poisoned.relaunchable = False
        poisoned.is_released = True
        poisoned.update_status(NodeStatus.FAILED)
        entitled = master.job_manager.get_node("worker", 2)
        entitled.is_released = True
        entitled.update_status(NodeStatus.FAILED)

        plan = master.auto_scaler.check_and_scale()
        assert [n.rank_index for n in plan.launch_nodes] == [2]

    def test_replacement_inherits_oom_memory_bump(self, master3):
        master, _ = master3
        for i in range(3):
            _set_running(master, i)
        dead = master.job_manager.get_node("worker", 1)
        dead.config_resource.memory_mb = 4096  # post-OOM doubled resource
        dead.is_released = True
        dead.update_status(NodeStatus.FAILED)

        plan = master.auto_scaler.check_and_scale()
        assert plan.launch_nodes[0].config_resource.memory_mb == 4096

    def test_heartbeat_timeout_node_is_replaced(self, master3):
        master, scaler = master3
        for i in range(3):
            _set_running(master, i)
        stale = master.job_manager.get_node("worker", 2)
        stale.heartbeat_time = time.time() - 10_000

        plan = master.auto_scaler.check_and_scale()
        assert stale.is_released
        assert [n.id for n in plan.remove_nodes] == [2]
        assert len(plan.launch_nodes) == 1
        assert len(master.auto_scaler.alive_nodes()) == 3

    def test_scale_to_shrinks_and_grows(self, master3):
        master, scaler = master3
        for i in range(3):
            _set_running(master, i)
        plan = master.scale_to(1)
        assert len(plan.remove_nodes) == 2
        assert len(master.auto_scaler.alive_nodes()) == 1

        plan = master.scale_to(3)
        assert len(plan.launch_nodes) == 2
        assert len(master.auto_scaler.alive_nodes()) == 3

    def test_relaunch_goes_through_scaler(self, master3):
        """A recoverable failure relaunches via the Scaler seam."""
        master, scaler = master3
        node = _set_running(master, 0)
        failed = Node(node_type="worker", node_id=0)
        failed.exit_reason = NodeExitReason.HARDWARE_ERROR
        failed.status = NodeStatus.FAILED
        master.job_manager.process_event(NodeEvent("modified", failed))
        assert scaler.plans
        last = scaler.plans[-1]
        assert [n.id for n in last.remove_nodes] == [0]
        assert len(last.launch_nodes) == 1


class TestLocalProcessScaler:
    def test_spawn_and_remove(self):
        spawned = []
        s = LocalProcessScaler(
            "127.0.0.1:1", ["train.py"], spawn_fn=spawned.append
        )
        n = Node(node_type="worker", node_id=5, rank_index=2)
        s.scale(ScalePlan(launch_nodes=[n]))
        assert spawned == [n]
        cmd = s.command_for(n)
        assert "--node-rank=2" in cmd and "train.py" in cmd
        s.stop()


class TestHangRecovery:
    def test_hang_restarts_workers_then_survives(self, master3):
        """Hang → restart order via heartbeat channel; job keeps running
        (the reference's behavior; review weak #6: exiting is the
        anti-goodput outcome)."""
        master, _ = master3
        node = _set_running(master, 0)
        old_timeout = _ctx.hang_detection_secs
        _ctx.hang_detection_secs = 0.1
        try:
            master.speed_monitor.set_start_timestamp()
            master.speed_monitor._start_training_time = time.time() - 60
            assert master.speed_monitor.all_worker_hanged()

            box = {}
            t = threading.Thread(
                target=lambda: box.update(
                    reason=master.run(max_hang_recoveries=2)
                )
            )
            t.start()
            time.sleep(0.5)
            # first recovery must have fired: restart flag consumed via
            # the heartbeat channel, job still alive
            action = master.job_manager.collect_node_heartbeat("worker", 0)
            assert action == "restart"
            assert t.is_alive() or box.get("reason") != JobExitReason.SUCCEEDED
            # let recoveries exhaust -> HANG_ERROR exit (still no progress)
            t.join(timeout=30)
            assert not t.is_alive()
            assert box["reason"] == JobExitReason.HANG_ERROR
        finally:
            _ctx.hang_detection_secs = old_timeout
            master.stop()

    def test_progress_clears_hang_counter(self, master3):
        master, _ = master3
        _set_running(master, 0)
        old_timeout = _ctx.hang_detection_secs
        _ctx.hang_detection_secs = 30
        try:
            master.speed_monitor.collect_global_step(10)
            assert not master.speed_monitor.all_worker_hanged()
        finally:
            _ctx.hang_detection_secs = old_timeout
