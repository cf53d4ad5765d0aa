"""Agent monitors + paral-config tuner: the master→agent→dataloader
retune loop closes end-to-end, and monitoring reaches the SpeedMonitor /
node table through a real served master.

Parity: the reference tests ParalConfigTuner and the monitors against
the in-process local master (test pattern from test_utils.py).
"""

import json
import os
import time

import pytest

from dlrover_tpu.agent.master_client import MasterClient
from dlrover_tpu.agent.monitor import (
    ParalConfigTuner,
    ResourceMonitor,
    TrainingMonitor,
    report_runtime_metrics,
)
from dlrover_tpu.common import comm
from dlrover_tpu.master.local_master import start_local_master
from dlrover_tpu.trainer.elastic.dataloader import ElasticDataLoader


@pytest.fixture()
def served_master():
    m = start_local_master(node_num=1)
    yield m
    m.stop()


@pytest.fixture()
def client(served_master):
    c = MasterClient(served_master.addr, node_id=0)
    yield c
    c.close()


def _wait_for(cond, timeout=10.0):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if cond():
            return True
        time.sleep(0.05)
    return False


class TestMonitors:
    def test_resource_monitor_reports_usage(self, served_master, client):
        node = served_master.job_manager.get_node("worker", 0)
        mon = ResourceMonitor(client, interval=0.1)
        mon.start()
        try:
            assert _wait_for(lambda: node.used_resource.memory_mb > 0)
        finally:
            mon.stop()

    def test_training_monitor_feeds_speed_monitor(
        self, served_master, client, tmp_path, monkeypatch
    ):
        metrics_file = str(tmp_path / "metrics.json")
        monkeypatch.setenv(
            "DLROVER_TPU_RUNTIME_METRICS_PATH", metrics_file
        )
        report_runtime_metrics(7, loss=1.5)
        assert json.load(open(metrics_file))["global_step"] == 7

        mon = TrainingMonitor(client, interval=0.1)
        mon.start()
        try:
            sm = served_master.speed_monitor
            assert _wait_for(lambda: sm.completed_global_step == 7)
            report_runtime_metrics(9)
            assert _wait_for(lambda: sm.completed_global_step == 9)
        finally:
            mon.stop()

    def test_paral_config_tuner_end_to_end(
        self, served_master, client, tmp_path
    ):
        """Master sets batch_size → tuner writes the file → a live
        ElasticDataLoader picks it up mid-run (review weak #5: this loop
        used to be two ends with no middle)."""
        cfg_file = str(tmp_path / "paral.json")
        loader = ElasticDataLoader(
            dataset=list(range(100)), batch_size=4, config_file=cfg_file
        )
        tuner = ParalConfigTuner(client, interval=0.1, path=cfg_file)
        tuner.start()
        try:
            config = comm.ParallelConfig()
            config.dataloader.batch_size = 16
            served_master.paral_config_service.set_global_config(config)
            assert _wait_for(lambda: os.path.exists(cfg_file))
            assert _wait_for(
                lambda: (loader.load_config() or loader.batch_size == 16)
            )
            batch = next(iter(loader))
            assert len(batch) == 16
        finally:
            tuner.stop()

    def test_tuner_rewrites_only_on_new_version(
        self, served_master, client, tmp_path
    ):
        cfg_file = str(tmp_path / "paral.json")
        tuner = ParalConfigTuner(client, interval=0.05, path=cfg_file)
        config = comm.ParallelConfig()
        config.dataloader.batch_size = 8
        served_master.paral_config_service.set_global_config(config)
        tuner.start()
        try:
            assert _wait_for(lambda: os.path.exists(cfg_file))
            mtime = os.path.getmtime(cfg_file)
            time.sleep(0.3)  # several polls, same version
            assert os.path.getmtime(cfg_file) == mtime
        finally:
            tuner.stop()
