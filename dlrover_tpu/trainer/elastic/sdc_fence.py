"""The trainer's silent-data-corruption fence (ISSUE 20): the routing, on
the train loop, of ``parallel/sdc.py``'s tier-1 detector (per-lane grad
norms), its tier-2 paired audit probe and the tier-3 conviction."""

from __future__ import annotations

from typing import Callable, Dict, Optional

import numpy as np

from dlrover_tpu.common import faults
from dlrover_tpu.common.log import default_logger as logger
from dlrover_tpu.obs.flight_recorder import default_recorder
from dlrover_tpu.obs.metrics import default_registry
from dlrover_tpu.parallel import sdc as sdc_mod


class SdcFence:
    """``rollback(step, evidence) -> step rolled back to (-1: none)`` is
    the one thing a conviction asks of the trainer; ``sampler`` names
    the batch of a data spike."""

    def __init__(
        self,
        plan,
        mesh,
        sampler,
        rollback: Callable[[int, Dict], int],
        requested: bool = False,
    ):
        self.sampler = sampler
        self._rollback = rollback
        self._requested = requested
        self._registry = default_registry()
        self._flight = default_recorder()
        self.arm(plan, mesh)

    def arm(self, plan, mesh):
        """Build the tier-1 detector + tier-2 probe for the CURRENT
        world (lane count = the sync plan's device total). Re-run after
        a resize — the lane axis is per-world, and history from the old
        world describes different lanes. Detection needs the explicit
        dp-family sync path: that is where the per-lane norm vector
        falls out of the bucket walk for free."""
        self.detector: Optional[sdc_mod.SdcDetector] = None
        self.probe = None
        # 1-step-delayed (step, loss_ref, norms_ref): the freshly
        # dispatched step's outputs stay on device; the PREVIOUS
        # step's are already materialized by dispatch depth, so the
        # fetch adds no host sync to the critical path
        self._pending = None
        self.halt = False
        self.convicted: tuple = ()
        self.detect_step: Optional[int] = None
        if not (self._requested or sdc_mod.enabled()):
            return
        if (
            plan is None
            or getattr(plan, "three_d", False)
            or getattr(plan, "kind", "") == "ep"
        ):
            logger.warning(
                "sdc detection requested but this mesh has no per-lane"
                " norm path (needs the explicit dp/ZeRO/tp sync plan —"
                " comm_overlap or grad_compress); fences disabled"
            )
            return
        cfg = sdc_mod.SdcConfig()
        cfg.audit_steps = sdc_mod.audit_steps_from_env(cfg.audit_steps)
        self.detector = sdc_mod.SdcDetector(plan.total, cfg)
        # lane i of the norm vector is device i of the mesh's stacked
        # data axes — the probe must vote over the same ordering
        self.probe = sdc_mod.AuditProbe(
            devices=list(mesh.devices.flatten())
        )
        logger.info(
            f"sdc defense armed: {plan.total} lanes, window "
            f"{cfg.window}, suspect sigma {cfg.suspect_sigma}, audit "
            f"cadence {cfg.audit_steps or 'on-suspicion'}"
        )

    def after_step(self, step: int, metrics: Dict, dev_norms):
        """One detector observation per step (1-step delayed). Tier-1
        verdicts route: data spike → count + log + black-box event
        (never escalates — satellite 3's false-positive gate); device
        suspect → tier-2 paired audit; audit conviction → tier-3
        response (:meth:`_convict`)."""
        # graftlint fault-site coverage + control-kind composability:
        # device.sdc control kinds (delay — "the bad chip is also
        # slow") fire here; the scale kind itself is a data kind baked
        # into the step at trace time (models/train.py)
        faults.fire("device.sdc")
        pending, self._pending = self._pending, (
            (step, metrics.get("loss"), dev_norms)
            if dev_norms is not None
            else None
        )
        if pending is None:
            return
        p_step, p_loss, p_norms = pending
        try:
            loss = float(p_loss)
            norms = np.asarray(p_norms, dtype=np.float64).reshape(-1)
        except Exception as e:
            logger.warning(
                f"sdc: fetching step {p_step} telemetry failed: {e!r}"
            )
            return
        verdict = self.detector.observe(p_step, loss, norms)
        suspects: tuple = ()
        if verdict.kind == "data_spike":
            self._registry.counter(
                "dlrover_sdc_data_spikes_total",
                "steps classified as data spikes (skipped, not escalated)",
            ).inc()
            detail = (
                f"step {p_step} (batch at sampler position "
                f"{self.sampler.state_dict().get('completed_num', -1)})"
                f": {verdict.detail}"
            )
            self._flight.note_event("sdc_data_spike", detail)
            logger.warning(f"sdc data spike, skip-and-log: {detail}")
        elif verdict.kind == "device_suspect":
            self._registry.counter(
                "dlrover_sdc_suspicions_total",
                "tier-1 device-suspect verdicts (escalated to audit)",
            ).inc()
            if self.detect_step is None:
                self.detect_step = p_step
            logger.warning(
                f"sdc device suspect at step {p_step}: lanes "
                f"{list(verdict.suspects)} ({verdict.detail})"
            )
            suspects = verdict.suspects
        cadence = self.detector.cfg.audit_steps
        if suspects or (cadence and p_step % cadence == 0):
            self._registry.counter(
                "dlrover_sdc_audits_run_total",
                "tier-2 paired-device audit probes executed",
            ).inc()
            result = self.probe.run(p_step, suspects=suspects)
            if result.convicted:
                self._convict(p_step, result, verdict)
            elif suspects and not result.inconclusive:
                logger.info(
                    f"sdc audit cleared lanes {list(suspects)} at step "
                    f"{p_step} (bitwise agreement across rotated pairs)"
                )

    def _convict(self, step: int, result, verdict):
        """Tier-3 response: evidence bundle (norm history + vote
        matrix), ``sdc_conviction`` event to the master/Brain, verified
        rollback with the downtime booked to ``restart_replay``, then
        HALT this incarnation — the injected corruption is baked into
        the compiled step (exactly like a real bad chip is baked into
        the hardware), so the quarantine-drain model applies: the
        master excludes the convicted host and the next world
        re-assembles without it."""
        self.convicted = tuple(result.convicted)
        evidence = {
            "step": step,
            "convicted": list(result.convicted),
            "votes": {
                str(lane): [[p, bool(a)] for p, a in vv]
                for lane, vv in result.votes.items()
            },
            "digests": list(result.digests),
            "suspect_detail": verdict.detail if verdict else "",
            "norm_history": self.detector.history(),
        }
        self._registry.counter(
            "dlrover_sdc_convictions_total",
            "devices convicted by the paired audit vote",
        ).inc(len(result.convicted))
        self._flight.note_event(
            "sdc_conviction",
            f"lanes {list(result.convicted)} at step {step}",
        )
        self._flight.dump("sdc_conviction", extra=evidence, force=True)
        rolled_to = self._rollback(step, evidence)
        logger.error(
            f"sdc conviction at step {step}: lanes "
            f"{list(result.convicted)} convicted"
            + (
                f"; rolled back to verified step {rolled_to}"
                if rolled_to >= 0
                else ""
            )
            + "; halting for quarantine-drain"
        )
        # the detector's window described the corrupted trajectory
        self.detector.reset()
        self._pending = None
        self.halt = True
