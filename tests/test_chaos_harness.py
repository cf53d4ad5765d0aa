"""The deterministic chaos harness (tools/chaos.py): scripted kill /
evict / outage scenarios gated on the survival contract.

Tier-1 runs the fast control-plane scenarios (master restart with a
pending cluster-plan slice — the PR-9 robustness gap — and a Brain
outage mid-plan) plus the CLI surface; the trainer-bearing scenarios
(eviction drain, subprocess SIGKILL) are the ``slow`` matrix here.
"""

import json
import os
import subprocess
import sys
import importlib.util

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_CHAOS = os.path.join(_REPO, "tools", "chaos.py")


def _load_chaos():
    spec = importlib.util.spec_from_file_location("chaos_mod", _CHAOS)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


chaos = _load_chaos()


class TestControlPlaneScenarios:
    def test_master_restart_mid_plan_redelivers_to_acked(
        self, tmp_path
    ):
        """Satellite 3: the master dies holding a pending
        cluster_plans slice; the restarted PlanExecutor (fresh ack
        watermark) is redelivered the slice and the plan converges to
        acked — never silently dropped."""
        res = chaos.run_scenario(
            "master_restart_mid_plan", seed=3, workdir=str(tmp_path)
        )
        assert res["ok"], res
        assert res["plan_status"].get("pending", 0) == 0
        assert res["plan_status"].get("acked", 0) >= 1
        assert res["target_after"] == 4

    def test_brain_outage_mid_plan_degrades_then_executes(
        self, tmp_path
    ):
        res = chaos.run_scenario(
            "brain_outage_mid_plan", seed=3, workdir=str(tmp_path)
        )
        assert res["ok"], res
        # the outage poll degraded to None (no crash, no resize)
        assert res["poll_during_outage"] is None
        assert res["target_during_outage"] == 2

    def test_unknown_scenario_is_hard_error(self):
        with pytest.raises(ValueError):
            chaos.run_scenario("no_such_scenario")


class TestServingScenarios:
    def test_serving_crc_retry(self, tmp_path):
        """ISSUE 17 satellite: a seeded bit flip rots one published
        record; the subscriber must skip that generation naming the
        rotten record (no crash, exactly one crc retry) and recover on
        the next clean commit."""
        res = chaos.run_scenario(
            "serving_crc_retry", seed=3, workdir=str(tmp_path)
        )
        assert res["ok"], res
        assert res["crc_retries"] == 1
        assert res["rotten_record"] is not None
        assert res["recovered_step"] == 3


class TestCli:
    def test_list(self):
        out = subprocess.run(
            [sys.executable, _CHAOS, "--list"],
            capture_output=True,
            text=True,
            timeout=120,
        )
        assert out.returncode == 0
        names = out.stdout.split()
        assert "eviction_during_save" in names
        assert "sigkill_mid_step" in names

    def test_usage_without_scenario(self):
        out = subprocess.run(
            [sys.executable, _CHAOS],
            capture_output=True,
            text=True,
            timeout=120,
        )
        assert out.returncode == 2


@pytest.mark.slow
class TestTrainerScenarios:
    """The full matrix, replayed under pytest."""

    def test_eviction_during_save(self, tmp_path):
        res = chaos.run_scenario(
            "eviction_during_save", seed=11, workdir=str(tmp_path)
        )
        assert res["ok"], res
        assert res["loss_bitwise"] is True
        assert res["verified_step"] == chaos.EVICT_STEP
        assert res["goodput_eviction_s"] > 0
        assert res["wedged_threads"] == []

    def test_sigkill_mid_step(self, tmp_path):
        res = chaos.run_scenario(
            "sigkill_mid_step", seed=11, workdir=str(tmp_path)
        )
        assert res["ok"], res
        assert res["kill_rc"] == 137
        assert 0 <= res["lost_steps"] <= chaos.COMMIT_INTERVAL
        assert res["loss_bitwise"] is True

    def test_cli_scenario_replay_is_deterministic(self, tmp_path):
        """Same seed, same scenario, two runs: the scripted kill lands
        at the same step and the gates agree — the harness's whole
        reason to exist."""
        a = chaos.run_scenario(
            "sigkill_mid_step", seed=5,
            workdir=str(tmp_path / "a"),
        )
        b = chaos.run_scenario(
            "sigkill_mid_step", seed=5,
            workdir=str(tmp_path / "b"),
        )
        assert a["ok"] and b["ok"]
        assert a["killed_at_step"] == b["killed_at_step"]
        assert a["resumed_step"] == b["resumed_step"]
