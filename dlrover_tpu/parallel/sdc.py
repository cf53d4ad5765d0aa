"""Silent-data-corruption defense (ISSUE 20): the detection and
attribution layer in front of the repo's existing response primitives.

Every robustness path before this PR defends against failures that
*announce themselves* — crashes, torn bytes, dead heartbeats. A chip
that silently computes wrong-but-finite numbers corrupts weights for
thousands of steps before any of those fire; at fleet scale that is the
dominant undetected failure mode. Elastic-native systems treat
detect-plus-surgical-replacement as a first-class path (ElasWave
2510.00606; TorchTitan 2410.06511 couples loss-anomaly handling with
checkpoint rollback). This module supplies the three escalating tiers;
the trainer and master wire them to the response primitives that
already exist (``latest_verified_step`` rollback, rendezvous exclusion,
Brain ``node_events``, flight bundles):

- **Tier 1 — free fences** (:class:`SdcDetector`): the grad-sync
  bucket walk already computes per-bucket norms, so each device's
  LOCAL (pre-sync) grad norm rides the same shard_map out-spec at ~zero
  cost (``sync_grads(device_norms=True)``). A robust median+MAD window
  detector over the loss and the per-lane norm vector distinguishes a
  *data spike* (every lane moves together — skip-and-log, batch id
  recorded) from a *device suspect* (one lane diverges from its replica
  peers — escalate). NaN/Inf propagates into the lane norms, so the
  finite fence falls out of the same vector.
- **Tier 2 — paired audit probe** (:class:`AuditProbe`): on suspicion
  (or every ``DLROVER_TPU_SDC_AUDIT_STEPS`` steps) re-run a
  deterministic fixed-seed probe computation per device — the
  ``node_check`` matmul pattern lifted on-device — and vote with
  rotated pairings so each suspect is judged by two disjoint peers.
  Majority disagreement convicts a specific device; bitwise agreement
  clears it (a data spike that escalated by ambiguity is cleared here,
  never convicted).
- **Tier 3 — response** (trainer/master wiring, not this module):
  conviction rolls back to the latest verified checkpoint (replay
  booked to ``restart_replay``), quarantines the convicted host out of
  rendezvous, and ships a ``sdc_conviction`` node event with the vote
  matrix + norm history to the Brain.

Injection (``common/faults.py`` site ``device.sdc``, kind ``scale``)
makes the whole chain replayable: ``device.sdc:scale:@N:seed`` scales
ONE device's local gradient by a large *finite* factor from step ``N``
on (``seed % n_lanes`` picks the lane) — finite-but-wrong is the case
the detector must earn; a bit flip on f32 usually yields NaN, which the
cheap fence catches trivially. :func:`injection_plan` resolves the
armed spec once at step-build time; the probe applies the same plan to
the convicted lane's probe output, so the audit sees exactly what the
training step saw.
"""

from __future__ import annotations

import math
import os
import zlib
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from dlrover_tpu.common.log import default_logger as logger

ENV_ENABLED = "DLROVER_TPU_SDC"
ENV_AUDIT_STEPS = "DLROVER_TPU_SDC_AUDIT_STEPS"

_enabled_override: Optional[bool] = None


def set_enabled(on: bool):
    """Programmatic switch (the trainer's ``sdc_detect`` knob): wins
    over the env var. Must be set BEFORE the train step is built —
    ``build_train_step`` reads it at trace time to decide whether the
    per-lane norm vector rides the sync."""
    global _enabled_override
    _enabled_override = bool(on)


def enabled() -> bool:
    if _enabled_override is not None:
        return _enabled_override
    return os.getenv(ENV_ENABLED, "") not in ("", "0", "false")


def audit_steps_from_env(default: int = 0) -> int:
    raw = os.getenv(ENV_AUDIT_STEPS, "")
    if not raw:
        return default
    try:
        return max(0, int(raw))
    except ValueError:
        logger.warning(f"bad {ENV_AUDIT_STEPS}={raw!r}; keeping {default}")
        return default


# ---------------------------------------------------------------------------
# injection plan (site device.sdc, kind scale)
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class InjectionPlan:
    """The realized ``device.sdc:scale`` fault: lane ``device`` scales
    its local gradient by ``factor`` from 1-based step ``from_step``
    on (sticky — a chip that goes bad stays bad until excluded)."""

    device: int
    factor: float
    from_step: int


def injection_plan(n_lanes: int) -> Optional[InjectionPlan]:
    """Resolve the armed ``device.sdc`` scale spec into a concrete
    plan, or None. Fully derived from the spec fields (no RNG stream),
    so the step builder, the audit probe and the chaos harness all
    replay the SAME corruption: ``seed % n_lanes`` is the lane, ``@N`` is the
    onset step (default 1 = corrupt from the first step)."""
    from dlrover_tpu.common import faults

    if n_lanes <= 0:
        return None
    # touch the injector first: it performs the one-time env read, so a
    # DLROVER_TPU_FAULTS-armed spec is visible even when no other fault
    # point has fired yet in this process (faults.active() alone only
    # mirrors already-loaded state)
    inj = faults.injector()
    if not faults.active():
        return None
    for spec in inj.specs():
        if spec.site == "device.sdc" and spec.kind == "scale":
            return InjectionPlan(
                device=spec.seed % n_lanes,
                factor=faults.SCALE_FACTOR,
                from_step=spec.nth or 1,
            )
    return None


# ---------------------------------------------------------------------------
# tier 1: robust median+MAD window detector
# ---------------------------------------------------------------------------
@dataclass
class SdcConfig:
    # trailing window of CLEAN steps feeding the temporal baseline
    # (anomalous steps never enter it — a spike must not poison the
    # statistics that flagged it)
    window: int = 32
    # observations before the temporal (data-spike) test arms; the
    # cross-lane test needs no history and arms immediately
    min_history: int = 8
    # robust z (MAD-normalized) thresholds. 6 sigma on a MAD scale is
    # far outside healthy lane-to-lane spread (replica lanes see
    # different data shards, so their norms legitimately differ by
    # tens of percent — see rel_floor) but far below the injected
    # finite-corruption factor
    spike_sigma: float = 6.0
    suspect_sigma: float = 6.0
    # MAD floor as a fraction of the median: replica lanes computing
    # near-identical norms would otherwise make the z-score a
    # hair-trigger (MAD ~ 0 -> any jitter divides to infinity)
    rel_floor: float = 0.1
    # periodic tier-2 audit cadence in steps (0 = audit only on
    # suspicion); DLROVER_TPU_SDC_AUDIT_STEPS overrides
    audit_steps: int = 0


@dataclass
class SdcVerdict:
    kind: str  # "warming" | "ok" | "data_spike" | "device_suspect"
    step: int = 0
    suspects: Tuple[int, ...] = ()
    detail: str = ""
    zscores: Tuple[float, ...] = ()


def _median(xs: Sequence[float]) -> float:
    """Median of a small list. The detector runs EVERY step on a
    handful of floats — pure Python beats numpy by an order of
    magnitude at this size (no array boxing, no dispatch), which is
    what keeps the always-on fence under the tracer-overhead floor."""
    s = sorted(xs)
    n = len(s)
    m = n // 2
    return s[m] if n % 2 else 0.5 * (s[m - 1] + s[m])


def _robust_scale(
    dev: Sequence[float], center: float, rel_floor: float
) -> float:
    """1.4826*MAD with the relative + absolute floors applied."""
    mad = _median([abs(d) for d in dev])
    return max(1.4826 * mad, rel_floor * abs(center), 1e-12)


class SdcDetector:
    """The tier-1 fence: feed it one (loss, per-lane local grad norm)
    observation per step; it answers with a verdict. Host-side Python
    on a handful of floats (not measured on the chip: no benchmark cell
    turns it on)."""

    def __init__(self, n_lanes: int, cfg: Optional[SdcConfig] = None):
        self.cfg = cfg or SdcConfig()
        self.n_lanes = int(n_lanes)
        self._loss_hist: List[float] = []
        self._med_hist: List[float] = []
        # trailing raw lane vectors (evidence for the flight bundle)
        self._lane_hist: List[List[float]] = []
        self._steps_seen = 0

    def reset(self):
        """Drop all history (post-rollback: the window described the
        corrupted trajectory)."""
        self._loss_hist.clear()
        self._med_hist.clear()
        self._lane_hist.clear()
        self._steps_seen = 0

    def history(self, last: int = 16) -> Dict:
        """Evidence payload for the flight bundle / Brain event."""
        return {
            "loss": [round(v, 6) for v in self._loss_hist[-last:]],
            "lane_norm_median": [
                round(v, 6) for v in self._med_hist[-last:]
            ],
            "lane_norms": [
                [round(v, 6) for v in row]
                for row in self._lane_hist[-last:]
            ],
        }

    def observe(
        self, step: int, loss: float, lane_norms: Sequence[float]
    ) -> SdcVerdict:
        cfg = self.cfg
        # one numpy touch to normalize the input (the trainer hands us a
        # device-fetched array), then pure Python: at this size the
        # array path costs 3-5x more per step than list arithmetic
        norms = (
            np.asarray(lane_norms, dtype=np.float64).reshape(-1).tolist()
        )
        n = len(norms)
        if n != self.n_lanes:
            self.n_lanes = n
        loss = float(loss)
        self._steps_seen += 1

        # -- finite fence (free: NaN/Inf propagated into the norms) ----
        bad_lanes = [
            i for i, v in enumerate(norms) if not math.isfinite(v)
        ]
        if bad_lanes or not math.isfinite(loss):
            if bad_lanes and len(bad_lanes) <= n // 2:
                return SdcVerdict(
                    kind="device_suspect",
                    step=step,
                    suspects=tuple(bad_lanes),
                    detail="non-finite lane norm",
                )
            # every lane blew up together (or only the loss did): the
            # batch, not a chip
            return SdcVerdict(
                kind="data_spike", step=step, detail="non-finite step"
            )

        med = _median(norms)
        verdict = SdcVerdict(kind="ok", step=step)

        # -- cross-lane test (device suspect): one lane vs its replica
        # peers THIS step — needs no history, so a chip bad from step 1
        # is still caught. A minority of lanes diverging is a device
        # signal; a majority moving together is the data
        if n >= 3:
            dev = [v - med for v in norms]
            scale = _robust_scale(dev, med, cfg.rel_floor)
            z = [abs(d) / scale for d in dev]
            outliers = [
                i for i, v in enumerate(z) if v > cfg.suspect_sigma
            ]
            if 0 < len(outliers) <= n // 2:
                verdict = SdcVerdict(
                    kind="device_suspect",
                    step=step,
                    suspects=tuple(outliers),
                    detail=(
                        f"lane z={[round(z[i], 1) for i in outliers]}"
                        f" vs peers (median {med:.4g})"
                    ),
                    zscores=tuple(round(v, 2) for v in z),
                )

        # -- temporal test (data spike): the whole step vs the clean
        # window — loss or the lane-median jumping while the lanes
        # agree with each other is a batch problem, not a chip
        if (
            verdict.kind == "ok"
            and len(self._med_hist) >= cfg.min_history
        ):
            lh, mh = self._loss_hist, self._med_hist
            lc, mc = _median(lh), _median(mh)
            z_loss = abs(loss - lc) / _robust_scale(
                [v - lc for v in lh], lc, cfg.rel_floor
            )
            z_med = abs(med - mc) / _robust_scale(
                [v - mc for v in mh], mc, cfg.rel_floor
            )
            if z_loss > cfg.spike_sigma or z_med > cfg.spike_sigma:
                verdict = SdcVerdict(
                    kind="data_spike",
                    step=step,
                    detail=(
                        f"loss z={z_loss:.1f} lane-median z={z_med:.1f}"
                        f" vs {len(self._med_hist)}-step window"
                    ),
                )

        if verdict.kind == "ok":
            self._loss_hist.append(loss)
            self._med_hist.append(med)
            self._lane_hist.append(norms)
            if len(self._med_hist) > cfg.window:
                del self._loss_hist[0]
                del self._med_hist[0]
                del self._lane_hist[0]
        elif self._steps_seen <= 2 and verdict.kind == "data_spike":
            # the first couple of steps have no meaningful baseline;
            # never mint a spike off them (cross-lane suspects stand —
            # they compare lanes to each other, not to history)
            verdict = SdcVerdict(kind="warming", step=step)
        return verdict


# ---------------------------------------------------------------------------
# tier 2: paired-device audit probe
# ---------------------------------------------------------------------------
@dataclass
class AuditResult:
    convicted: Tuple[int, ...]
    cleared: Tuple[int, ...]
    inconclusive: bool
    # lane -> [(peer, agreed), (peer, agreed)] — the rotated-pair vote
    # matrix (evidence riding the flight bundle + Brain event)
    votes: Dict[int, List[Tuple[int, bool]]] = field(default_factory=dict)
    digests: Tuple[str, ...] = ()


class AuditProbe:
    """Tier 2: a deterministic fixed-seed probe computation replayed on
    every device, judged by rotated paired voting.

    The probe is the ``node_check`` pattern lifted on-device: a chained
    per-round-normalized matmul on a seeded matrix, placed and executed
    on each device in turn, digested bitwise (crc32 of the result
    bytes). Deterministic inputs + deterministic kernels mean every
    healthy device produces the SAME bytes; a chip computing wrong
    numbers cannot.

    Voting mirrors ``NetworkCheckRendezvousManager.check_fault_node``'s
    two-round rotated pairing: lane ``i`` is compared against peers
    ``i+1`` and ``i+2`` (mod n) — two DISJOINT judges per suspect.
    Conviction requires BOTH peers to disagree with the suspect while
    agreeing with each other; one disagreeing pair alone cannot say
    which side is wrong. Fewer than 3 lanes is structurally
    inconclusive (no majority exists) — log, never convict.
    """

    def __init__(
        self,
        devices: Optional[Sequence] = None,
        size: int = 64,
        rounds: int = 2,
        seed: int = 1234,
    ):
        self._devices = list(devices) if devices is not None else None
        self.size = int(size)
        self.rounds = int(rounds)
        self.seed = int(seed)
        self._base: Optional[np.ndarray] = None

    def _probe_input(self) -> np.ndarray:
        if self._base is None:
            rng = np.random.default_rng(self.seed)
            self._base = rng.standard_normal(
                (self.size, self.size)
            ).astype(np.float32)
        return self._base

    def _digest(self, lane: int, device, step: int) -> int:
        import jax
        import jax.numpy as jnp

        a = jax.device_put(self._probe_input(), device)
        inv = jnp.float32(1.0 / self.size)
        for _ in range(self.rounds):
            # per-round normalized so the chain stays O(1) magnitude
            a = (a @ a.T) * inv
        out = np.asarray(jax.device_get(a))
        plan = injection_plan(self.n_lanes)
        if (
            plan is not None
            and plan.device == lane
            and step >= plan.from_step
        ):
            # the injected chip computes wrong numbers EVERYWHERE —
            # the probe must see the same corruption the train step saw
            out = out * np.float32(plan.factor)
        return zlib.crc32(out.tobytes())

    @property
    def n_lanes(self) -> int:
        return len(self.devices)

    @property
    def devices(self) -> List:
        if self._devices is None:
            import jax

            self._devices = list(jax.devices())
        return self._devices

    def run(
        self, step: int, suspects: Sequence[int] = ()
    ) -> AuditResult:
        devs = self.devices
        n = len(devs)
        digests = [self._digest(i, d, step) for i, d in enumerate(devs)]
        hexes = tuple(f"{d:08x}" for d in digests)
        if n < 3:
            logger.warning(
                f"sdc audit inconclusive: {n} lane(s) cannot form a "
                f"majority (suspects={list(suspects)})"
            )
            return AuditResult(
                convicted=(),
                cleared=(),
                inconclusive=True,
                digests=hexes,
            )
        votes: Dict[int, List[Tuple[int, bool]]] = {}
        convicted: List[int] = []
        cleared: List[int] = []
        for i in range(n):
            p1, p2 = (i + 1) % n, (i + 2) % n
            a1 = digests[i] == digests[p1]
            a2 = digests[i] == digests[p2]
            votes[i] = [(p1, a1), (p2, a2)]
            if not a1 and not a2 and digests[p1] == digests[p2]:
                convicted.append(i)
            else:
                cleared.append(i)
        if convicted:
            logger.error(
                f"sdc audit convicted lane(s) {convicted} at step "
                f"{step}: digests {list(hexes)}"
            )
        return AuditResult(
            convicted=tuple(convicted),
            cleared=tuple(cleared),
            inconclusive=False,
            votes=votes,
            digests=hexes,
        )
