"""Chaos soak: a 3-node elastic job survives repeated hard node kills.

Each SIGKILL exercises the full recovery chain end-to-end: worker-orphan
reaping (PR_SET_PDEATHSIG), heartbeat-based death detection on the
master, node relaunch, membership-change restarts on the survivors, and
flash-checkpoint resume from the shared shard-record tree. (This soak
found both the orphaned-worker collision and the LocalCluster shm
namespace collision — keep it in the suite.)
"""

import os
import random
import time

import pytest

from dlrover_tpu.testing.mock_cluster import LocalCluster

ASSETS = os.path.join(os.path.dirname(__file__), "assets")


@pytest.mark.slow
def test_chaos_soak(tmp_path):
    random.seed(7)
    with LocalCluster(
        3,
        os.path.join(ASSETS, "chaos_train.py"),
        # NOTE: worker stdout goes to files, not the inherited (possibly
        # pytest-captured) fd — inheriting a captured fd across the
        # launcher's subprocess tree has produced wedged bring-ups
        extra_args=["--max-restarts=20", "--rdzv-waiting-timeout=2",
                    f"--log-dir={tmp_path / 'logs'}"],
        env={
            "CHAOS_STEPS": "40",
            "CHAOS_STEP_SECS": "0.1",
            "CHAOS_CKPT_DIR": str(tmp_path / "ckpt"),
        },
    ) as c:
        for _ in range(2):
            time.sleep(random.uniform(4.0, 7.0))
            victim = random.randrange(3)
            c.kill_node(victim, sig=9)
            time.sleep(random.uniform(1.0, 2.0))
            c.start_node(victim)
        rcs = c.wait(timeout=480)
    assert all(rc == 0 for rc in rcs.values()), rcs


@pytest.mark.slow
def test_slice_unit_failover(tmp_path):
    """Slice-level elasticity (review r4 #4, SURVEY §5 "slice-level
    failure"): a 4-node job with node_unit=2 (two 2-host TPU slices)
    loses one WHOLE slice — both of its nodes SIGKILL'd — and must (a)
    re-freeze the surviving world at a node_unit multiple (2, never 3:
    a lone extra host cannot form a slice), then (b) re-admit the
    relaunched slice and finish at full size. Ref:
    dlrover rdzv_manager.py:129 node-unit semantics."""
    world_log = tmp_path / "worlds.log"
    with LocalCluster(
        4,
        os.path.join(ASSETS, "chaos_train.py"),
        extra_args=["--max-restarts=20", "--rdzv-waiting-timeout=2",
                    "--node-unit=2",
                    f"--log-dir={tmp_path / 'logs'}"],
        env={
            "CHAOS_STEPS": "40",
            "CHAOS_STEP_SECS": "0.1",
            "CHAOS_CKPT_DIR": str(tmp_path / "ckpt"),
            "CHAOS_WORLD_LOG": str(world_log),
        },
    ) as c:
        time.sleep(5.0)
        # one whole slice dies (nodes 2 and 3 form the second node-unit)
        c.kill_node(2, sig=9)
        c.kill_node(3, sig=9)
        time.sleep(2.0)
        c.start_node(2)
        c.start_node(3)
        rcs = c.wait(timeout=480)
    assert all(rc == 0 for rc in rcs.values()), rcs
    worlds = [
        int(line.split()[1])
        for line in world_log.read_text().splitlines()
        if line.strip()
    ]
    assert worlds, "no world observations recorded"
    # every frozen world is a whole number of slices
    assert all(w % 2 == 0 for w in worlds), worlds


@pytest.mark.slow
def test_chaos_node_and_master(tmp_path, monkeypatch):
    """Worst-case combination: a node is SIGKILL'd AND the master
    crashes (stale-autosave restore) in the same job — the job must
    still complete."""
    monkeypatch.setenv(
        "DLROVER_TPU_MASTER_STATE", str(tmp_path / "master_state.json")
    )
    with LocalCluster(
        2,
        os.path.join(ASSETS, "chaos_train.py"),
        extra_args=["--max-restarts=10", "--rdzv-waiting-timeout=2",
                    f"--log-dir={tmp_path / 'logs'}"],
        env={
            "CHAOS_STEPS": "40",
            "CHAOS_STEP_SECS": "0.15",
            "CHAOS_CKPT_DIR": str(tmp_path / "ckpt"),
        },
    ) as c:
        time.sleep(6.0)
        c.kill_node(1, sig=9)
        time.sleep(1.5)
        c.start_node(1)
        time.sleep(4.0)
        c.restart_master()  # crash-style: restores the last autosave
        rcs = c.wait(timeout=420)
    assert all(rc == 0 for rc in rcs.values()), rcs
