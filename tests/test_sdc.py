"""Silent-data-corruption defense (parallel/sdc.py + trainer/master
wiring): the three-tier chain from ISSUE 20.

Tier-1 here: detector units (the satellite-3 false-positive gate — a
bad BATCH that moves every lane together must skip-and-log, never
escalate), the paired audit probe's rotated voting, the deterministic
injection plan, the master's permanent-quarantine wiring (including
quarantine surviving a relaunch — same rank after a relaunch means the
same convicted chip), the Brain's single-event condemnation, and ONE
full in-process detect->convict->rollback->halt trainer chain. The
multi-seed soak (full quarantine scenario + extra convict-only seeds)
is ``slow``.
"""

import importlib.util
import json
import os
import time
import types

import numpy as np
import pytest

from dlrover_tpu.common import faults
from dlrover_tpu.common.constants import NodeExitReason, NodeStatus
from dlrover_tpu.parallel import sdc

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_CHAOS = os.path.join(_REPO, "tools", "chaos.py")


def _load_chaos():
    spec = importlib.util.spec_from_file_location("chaos_sdc_mod", _CHAOS)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(autouse=True)
def _disarm():
    faults.reset()
    sdc.set_enabled(False)
    yield
    faults.reset()
    sdc.set_enabled(False)


# ---------------------------------------------------------------------------
# injection plan: the armed spec resolves to one deterministic lane
# ---------------------------------------------------------------------------
class TestInjectionPlan:
    def test_no_faults_means_no_plan(self):
        assert sdc.injection_plan(4) is None

    def test_nth_spec_sets_lane_and_onset(self):
        faults.configure("device.sdc:scale:@6:2")
        plan = sdc.injection_plan(4)
        assert plan is not None
        assert plan.device == 2  # seed % n_lanes
        assert plan.from_step == 6
        assert plan.factor == faults.SCALE_FACTOR

    def test_prob_spec_defaults_to_step_one(self):
        faults.configure("device.sdc:scale:1.0:9")
        plan = sdc.injection_plan(4)
        assert plan.device == 9 % 4
        assert plan.from_step == 1

    def test_other_sites_do_not_arm_a_plan(self):
        faults.configure("ckpt.shm_stage:bit_flip:1.0:3")
        assert sdc.injection_plan(4) is None

    def test_env_spec_is_visible_before_any_fault_point_fires(
        self, monkeypatch
    ):
        # a fresh process arms faults lazily from the env on first
        # injector touch; injection_plan runs at trace time, often
        # BEFORE any fire()/corrupt() call — it must trigger the env
        # read itself, not just mirror already-loaded state
        monkeypatch.setenv(faults.ENV_VAR, "device.sdc:scale:@4:6")
        monkeypatch.setattr(faults, "_env_loaded", False)
        faults._injector.clear()
        plan = sdc.injection_plan(4)
        assert plan is not None
        assert plan.device == 6 % 4
        assert plan.from_step == 4


# ---------------------------------------------------------------------------
# tier 1: the robust median+MAD detector
# ---------------------------------------------------------------------------
class TestSdcDetector:
    def _clean(self, det, n=10, lanes=4, start=1):
        rng = np.random.default_rng(0)
        for i in range(n):
            norms = 1.0 + 0.05 * rng.standard_normal(lanes)
            v = det.observe(start + i, 2.0 + 0.01 * i, norms)
            assert v.kind in ("ok", "warming"), v
        return det

    def test_clean_steps_stay_ok(self):
        det = self._clean(sdc.SdcDetector(4))
        assert len(det.history()["loss"]) >= 8

    def test_single_lane_outlier_is_device_suspect(self):
        det = self._clean(sdc.SdcDetector(4))
        v = det.observe(11, 2.0, [1.0, 1.02, 32.0, 0.98])
        assert v.kind == "device_suspect"
        assert v.suspects == (2,)

    def test_cross_lane_test_needs_no_history(self):
        # a chip bad from the very first step is still caught
        det = sdc.SdcDetector(4)
        v = det.observe(1, 2.0, [1.0, 1.02, 32.0, 0.98])
        assert v.kind == "device_suspect"
        assert v.suspects == (2,)

    def test_all_lanes_spiking_together_is_data_spike(self):
        # satellite 3's core property: a bad BATCH moves every lane
        # together — that must read as data, never as a device
        det = self._clean(sdc.SdcDetector(4))
        v = det.observe(11, 97.0, [50.0, 51.0, 49.5, 50.5])
        assert v.kind == "data_spike"
        assert v.suspects == ()

    def test_anomalies_never_poison_the_window(self):
        det = self._clean(sdc.SdcDetector(4))
        before = list(det.history()["lane_norm_median"])
        det.observe(11, 97.0, [50.0, 51.0, 49.5, 50.5])
        assert det.history()["lane_norm_median"] == before

    def test_nonfinite_lane_is_device_suspect(self):
        det = sdc.SdcDetector(4)
        v = det.observe(1, 2.0, [1.0, np.nan, 1.0, 1.0])
        assert v.kind == "device_suspect"
        assert v.suspects == (1,)

    def test_nonfinite_everywhere_is_data_spike(self):
        det = sdc.SdcDetector(4)
        v = det.observe(1, np.nan, [np.nan] * 4)
        assert v.kind == "data_spike"

    def test_warming_never_mints_a_spike(self):
        det = sdc.SdcDetector(4)
        det.observe(1, 2.0, [1.0, 1.0, 1.0, 1.0])
        # lanes agree, loss insane: with no baseline this must warm,
        # not alarm
        v = det.observe(2, 9e9, [1.0, 1.0, 1.0, 1.0])
        assert v.kind in ("warming", "ok")

    def test_reset_drops_history(self):
        det = self._clean(sdc.SdcDetector(4))
        det.reset()
        assert det.history()["loss"] == []

    def test_two_lanes_cannot_outvote_two(self):
        # half the lanes diverging is not a minority: ambiguous, so
        # the cross-lane test must not mint suspects
        det = sdc.SdcDetector(4)
        v = det.observe(1, 2.0, [1.0, 1.0, 64.0, 64.0])
        assert v.kind != "device_suspect" or len(v.suspects) <= 2


# ---------------------------------------------------------------------------
# tier 2: the paired audit probe
# ---------------------------------------------------------------------------
class TestAuditProbe:
    def test_healthy_devices_agree_bitwise(self):
        import jax

        probe = sdc.AuditProbe(devices=list(jax.devices())[:4])
        res = probe.run(step=5)
        assert res.convicted == ()
        assert res.inconclusive is False
        assert len(set(res.digests)) == 1  # identical bytes everywhere
        assert sorted(res.cleared) == [0, 1, 2, 3]

    def test_injected_lane_is_convicted_by_both_peers(self):
        import jax

        faults.configure("device.sdc:scale:@3:2")  # lane 2 % 4 = 2
        probe = sdc.AuditProbe(devices=list(jax.devices())[:4])
        res = probe.run(step=5)  # past the onset
        assert res.convicted == (2,)
        assert 2 not in res.cleared
        # the vote matrix shows both rotated peers disagreeing with
        # the convict while agreeing with each other
        assert [a for _, a in res.votes[2]] == [False, False]

    def test_before_onset_everyone_clears(self):
        import jax

        faults.configure("device.sdc:scale:@9:2")
        probe = sdc.AuditProbe(devices=list(jax.devices())[:4])
        res = probe.run(step=5)  # onset not reached
        assert res.convicted == ()

    def test_two_lanes_is_structurally_inconclusive(self):
        import jax

        probe = sdc.AuditProbe(devices=list(jax.devices())[:2])
        res = probe.run(step=1, suspects=(1,))
        assert res.inconclusive is True
        assert res.convicted == ()


# ---------------------------------------------------------------------------
# trainer routing: spike skips, suspect escalates (no trainer build)
# ---------------------------------------------------------------------------
class _Counter:
    def __init__(self):
        self.n = 0

    def inc(self, amount=1):
        self.n += amount

    def set(self, v):
        self.n = v


class _Registry:
    def __init__(self):
        self.counters = {}

    def counter(self, name, desc=""):
        return self.counters.setdefault(name, _Counter())

    gauge = counter


class _Flight:
    def __init__(self):
        self.events = []

    def note_event(self, kind, detail=""):
        self.events.append(kind)


class _NeverProbe:
    def __init__(self):
        self.runs = 0

    def run(self, step, suspects=()):
        self.runs += 1
        return sdc.AuditResult(
            convicted=(), cleared=tuple(suspects), inconclusive=False
        )


def _make_host(n_lanes=4):
    """A bare stand-in exposing exactly what ``SdcFence.after_step``
    touches — the routing logic is testable without compiling a
    trainer."""
    from dlrover_tpu.trainer.elastic.sdc_fence import SdcFence

    host = types.SimpleNamespace(
        detector=sdc.SdcDetector(n_lanes),
        probe=_NeverProbe(),
        _pending=None,
        halt=False,
        convicted=(),
        detect_step=None,
        _registry=_Registry(),
        _flight=_Flight(),
        sampler=types.SimpleNamespace(
            state_dict=lambda: {"completed_num": 123}
        ),
    )
    host.step = lambda s, m, d: SdcFence.after_step(host, s, m, d)
    return host


class TestTrainerRouting:
    def _warm(self, host, n=10):
        rng = np.random.default_rng(1)
        for i in range(1, n + 1):
            host.step(
                i, {"loss": 2.0}, 1.0 + 0.05 * rng.standard_normal(4)
            )

    def test_data_spike_skips_and_logs_without_escalating(self):
        """Satellite 3's regression gate at the routing layer: a bad
        batch (all lanes together + loss spike) must be counted and
        black-boxed but NEVER reach the audit probe."""
        host = _make_host()
        self._warm(host)
        host.step(11, {"loss": 97.0}, [50.0, 51.0, 49.5, 50.5])
        host.step(12, {"loss": 2.0}, [1.0, 1.0, 1.0, 1.0])  # flush
        reg = host._registry.counters
        assert reg["dlrover_sdc_data_spikes_total"].n == 1
        assert "dlrover_sdc_suspicions_total" not in reg
        assert "dlrover_sdc_audits_run_total" not in reg
        assert host.probe.runs == 0
        assert host.convicted == ()
        assert "sdc_data_spike" in host._flight.events
        assert not host.halt

    def test_device_suspect_escalates_to_audit(self):
        host = _make_host()
        self._warm(host)
        host.step(11, {"loss": 2.0}, [1.0, 1.0, 32.0, 1.0])
        host.step(12, {"loss": 2.0}, [1.0, 1.0, 1.0, 1.0])  # flush
        reg = host._registry.counters
        assert reg["dlrover_sdc_suspicions_total"].n == 1
        assert reg["dlrover_sdc_audits_run_total"].n == 1
        assert host.probe.runs == 1
        assert host.detect_step == 11

    def test_observation_is_one_step_delayed(self):
        host = _make_host()
        host.step(1, {"loss": 2.0}, [1.0, 1.0, 1.0, 1.0])
        assert host.detector._steps_seen == 0  # first call only enqueues
        host.step(2, {"loss": 2.0}, [1.0, 1.0, 1.0, 1.0])
        assert host.detector._steps_seen == 1


# ---------------------------------------------------------------------------
# master: conviction -> permanent quarantine (relaunch-proof)
# ---------------------------------------------------------------------------
class TestMasterQuarantine:
    def test_conviction_marks_node_and_fires_listeners(self):
        from dlrover_tpu.master.job_manager import JobManager

        jm = JobManager()
        jm.create_initial_nodes(4)
        seen = []
        jm.add_sdc_listener(lambda nt, nid, detail: seen.append(nid))
        jm.handle_sdc_conviction("worker", 2, detail="vote 2-0")
        node = jm.get_node("worker", 2)
        assert node.exit_reason == NodeExitReason.SDC_QUARANTINED
        assert seen == [2]
        assert jm.quarantined_nodes() == [("worker", 2)]
        events = jm.node_events("sdc_conviction")
        assert len(events) == 1

    def test_conviction_is_idempotent(self):
        from dlrover_tpu.master.job_manager import JobManager

        jm = JobManager()
        jm.create_initial_nodes(4)
        seen = []
        jm.add_sdc_listener(lambda nt, nid, detail: seen.append(nid))
        jm.handle_sdc_conviction("worker", 1)
        jm.handle_sdc_conviction("worker", 1)  # audit re-fires
        assert seen == [1]
        assert jm.quarantined_nodes() == [("worker", 1)]

    def test_rdzv_quarantine_is_permanent_and_parks_joins(self):
        from dlrover_tpu.master.rdzv_manager import (
            ElasticTrainingRendezvousManager,
        )

        mgr = ElasticTrainingRendezvousManager()
        mgr.update_rdzv_params(
            min_nodes=1, max_nodes=4, waiting_timeout=0.0
        )
        mgr.quarantine_node(3)
        for rank in range(4):
            mgr.join_rendezvous(rank, 1, addr=f"h{rank}")
        _, _, world, _ = mgr.get_comm_world(0)
        assert sorted(world) == [0, 1, 2]
        assert mgr.excluded_ranks() == [3]
        # hardware replacement is the only way back in
        mgr.clear_exclusion(3)
        assert mgr.excluded_ranks() == []

    def test_master_wiring_quarantines_and_opens_maintenance(self):
        from dlrover_tpu.master.local_master import LocalJobMaster

        class _Scaler:
            def __init__(self):
                self.hosts = ()

            def set_exclude_hosts(self, hosts):
                self.hosts = tuple(hosts)

        master = LocalJobMaster(node_num=4)  # never prepare()d
        master.auto_scaler._scaler = _Scaler()
        node = master.job_manager.get_node("worker", 2)
        node.hostname = "tpu-host-2"
        master.job_manager.handle_sdc_conviction(
            "worker", 2, detail="convicted"
        )
        for mgr in master.rdzv_managers.values():
            assert 2 in mgr.excluded_ranks()
        # PR-19 interop: the fleet replays deliberately — the
        # straggler/hang detectors must hold fire
        assert master.telemetry.in_maintenance()
        # scheduler anti-affinity: the host is absent capacity
        assert master.auto_scaler._scaler.hosts == ("tpu-host-2",)

    def test_quarantine_survives_relaunch(self):
        """The replacement process lands on the SAME silicon: the
        relaunch listener must not shed an SDC quarantine (unlike an
        eviction exclusion, which it must shed)."""
        from dlrover_tpu.master.local_master import LocalJobMaster

        master = LocalJobMaster(node_num=4)
        master.job_manager.handle_sdc_conviction("worker", 2)
        rdzv = list(master.rdzv_managers.values())[0]
        assert 2 in rdzv.excluded_ranks()
        node = master.job_manager.get_node("worker", 2)
        node.update_status(NodeStatus.FAILED)
        master.job_manager._handle_node_failure(node)
        # a replacement exists (new id, same rank) ...
        assert any(
            n.id != 2 and n.rank_index == 2
            for n in master.job_manager.get_nodes("worker")
        )
        # ... and the quarantine still holds
        assert 2 in rdzv.excluded_ranks()

    def test_eviction_exclusion_still_clears_on_relaunch(self):
        """Regression guard for the path the quarantine check rides:
        a plain eviction exclusion must still be shed."""
        from dlrover_tpu.master.local_master import LocalJobMaster

        master = LocalJobMaster(node_num=4)
        master.job_manager.handle_eviction_notice(
            "worker", 1, grace_s=30.0
        )
        rdzv = list(master.rdzv_managers.values())[0]
        assert 1 in rdzv.excluded_ranks()
        node = master.job_manager.get_node("worker", 1)
        node.update_status(NodeStatus.FAILED)
        master.job_manager._handle_node_failure(node)
        assert 1 not in rdzv.excluded_ranks()

    def test_brain_condemns_host_on_single_conviction(self):
        from dlrover_tpu.brain.algorithms import bad_node_exclusion
        from dlrover_tpu.brain.service import BrainServicer
        from dlrover_tpu.common import comm

        servicer = BrainServicer()
        servicer.record_node_event(
            comm.BrainNodeEventReport(
                job_name="job1",
                node_id=2,
                hostname="host-sdc",
                event="sdc_conviction",
                detail=json.dumps({"convicted": [2]}),
            )
        )
        # ONE event condemns: the conviction carries its own two-peer
        # audit-vote evidence (unlike oom, which needs 2 jobs)
        assert bad_node_exclusion(servicer) == ("host-sdc",)


# ---------------------------------------------------------------------------
# the full chain: detect -> audit -> convict -> rollback -> halt
# ---------------------------------------------------------------------------
class TestConvictionChain:
    def test_single_conviction_chain(self, tmp_path):
        """One in-process dp=4 trainer with ``device.sdc:scale:@6``
        armed: the fence flags the injected lane at onset, the audit
        convicts exactly that lane, the trainer rolls back to the
        verified checkpoint and halts the incarnation without
        committing a post-onset checkpoint."""
        chaos = _load_chaos()
        res = chaos.sdc_convict_only(13, str(tmp_path))  # lane 1
        assert res["ok"], res
        assert res["convicted"] == [1]
        assert res["innocent_convictions"] == 0
        assert res["detect_step"] == chaos.SDC_ONSET
        # halted ON the verified step: the corrupt steps are gone and
        # no checkpoint at/after the onset was ever committed
        assert res["halted_step"] < chaos.SDC_ONSET


@pytest.mark.slow
class TestSdcSoak:
    def test_full_quarantine_scenario(self, tmp_path):
        """The complete golden -> convict -> quarantine -> resume
        scenario with the bitwise loss-continuity gate."""
        chaos = _load_chaos()
        res = chaos.run_scenario(
            "sdc_quarantine", seed=7, workdir=str(tmp_path)
        )
        assert res["ok"], res
        assert res["loss_bitwise"] is True
        assert res["world_ranks"] == [0, 1, 2]

    @pytest.mark.parametrize("seed", [20, 22])
    def test_convict_only_other_lanes(self, seed, tmp_path):
        """Different seeds inject different lanes: conviction must
        track the injection, never a bystander."""
        chaos = _load_chaos()
        res = chaos.sdc_convict_only(seed, str(tmp_path))
        assert res["ok"], res
        assert res["convicted"] == [seed % 4]
