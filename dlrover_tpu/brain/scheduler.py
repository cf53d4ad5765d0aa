"""Brain cluster scheduler: closed-loop multi-job goodput allocation.

The L6 layer of the reference system (PAPER.md: the Brain
resource-optimization service + the ElasticJob/ScalePlan operator) as a
real decision maker: where ``optimize()`` answers one job's question
("what should *I* run at?"), the ``ClusterScheduler`` answers the
cluster's ("who should hold which chips *right now*?") and makes the
answer happen.

The loop, end to end:

1. **Telemetry in** — every job's master already streams
   ``job_metrics`` rows (steps/sec, alive_nodes, and the PR-7
   ``goodput_pct`` fleet number computed through the one shared
   ``obs.goodput.compute_goodput_pct`` formula) plus ``node_events``
   incidents into this datastore. The scheduler consumes those rows
   directly — no parallel bookkeeping.
2. **Scaling curves** — per job, the observed (worker_count →
   steps/sec) history is fitted to a power law ``speed = a·n^b`` with
   ``b`` clamped to [0, 1] (concave: diminishing returns). A job seen
   at a single size extrapolates with a conservative default exponent
   until the loop's own resizes produce a second point — the scheduler
   *learns* each job's curve by acting.
3. **Allocation** — greedy marginal allocation of node-unit chunks
   under the total chip budget, objective = goodput-weighted predicted
   throughput per chip (concave utilities make greedy exact). Every
   job keeps a starvation floor; chips whose best marginal gain is ≤ 0
   stay idle rather than burn power on a flat curve.
4. **Guard rails** — hysteresis (a new plan must beat the current
   allocation's predicted utility by ``hysteresis_frac``) and min-dwell
   (a job resized in the last ``min_dwell_s`` is pinned) keep the loop
   from thrashing: ElasWave's premise (arXiv 2510.00606) is that warm
   resize (~0.1–0.2 s, PR 2/8) makes *frequent* reallocation
   affordable, not *continuous* reallocation sensible.
5. **Plans out** — changed jobs get one versioned, crc-signed slice
   each in the ``cluster_plans`` table. Masters poll their slice over
   the existing ``BrainClient`` channel (redeliver-until-acked),
   execute it through ``JobAutoScaler.scale_to`` → warm resize
   (``brain/plan_exec.py``), and report the realized outcome
   (decision→resized latency, realized goodput) back — the feedback
   rows the next pass plans against. Unacked plans expire after
   ``plan_ttl_s``; nothing is ever silently dropped.

The ``run_algorithms`` verdict suite (brain/algorithms.py) is an input,
not a sibling: per-job hot-node verdicts raise that job's floor for the
pass, underperformance verdicts are persisted as ``node_events`` rows
(event ``"underperformance"``, once per episode window), and the
cluster bad-node exclusion list rides every emitted slice.

State is observable: ``dlrover_brain_*`` gauges (per-job allocation,
plan version, decision latency, plan status counts) through the obs/
registry, and ``tools/brain_ctl.py`` dumps jobs/curves/plans/outcomes
from the SQLite store.
"""

from __future__ import annotations

import math
import os
import time
import zlib
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

from dlrover_tpu.common.daemon import PollingDaemon
from dlrover_tpu.common.log import default_logger as logger

# a job observed at one size only: assume this scaling exponent until
# the loop's own resizes produce a second observed point (0.7 ≈ "scales
# well but not linearly" — conservative enough not to starve peers on
# one sample, optimistic enough to explore)
DEFAULT_EXPONENT = 0.7
# fitted exponents clamp here: b <= 1 keeps utilities concave (greedy
# marginal allocation is exact for concave curves), b >= 0 forbids
# "more chips make it slower" fits from noisy samples driving the
# allocator to zero
MIN_EXPONENT, MAX_EXPONENT = 0.0, 1.0

# jobs with a metrics sample younger than this (and no later job_end)
# participate in the pass
ACTIVE_WINDOW_S = 300.0
# a job whose allocation changed more recently than this is pinned —
# back-to-back resizes of the same job would replay drain/reshard
# before the previous resize's throughput is even observable
MIN_DWELL_S = 120.0
# pending plans a master never acked expire after this — the table
# must converge to acked-or-expired, never silently dropped rows
PLAN_TTL_S = 600.0
# a new plan must beat the standing allocation's predicted aggregate
# utility by this fraction, or it is not worth the resize downtime
HYSTERESIS_FRAC = 0.02
# an underperformance verdict re-fires into node_events at most once
# per this window (the check itself runs every pass)
UNDERPERF_REFIRE_S = 600.0

# -- preemption pricing (ROADMAP item-1 residue) -----------------------
# a job with an `eviction` node event inside this window is
# eviction-prone: its starvation floor rises one node_unit, so the
# allocator holds headroom where the platform keeps reclaiming chips
EVICTION_WINDOW_S = 3600.0
# dwell is priced from MEASURED downtime, not just the constant: a job
# pays (resize decision->resized latency + eviction drain latency) per
# reallocation, and must dwell at least this multiple of that price —
# a ~3.7 s cold tp resize is drained far less often than a 0.2 s warm
# dp one (`plan_outcomes` records the latencies; eviction events carry
# drain_ms in their detail)
DWELL_DOWNTIME_FACTOR = 30.0

ENV_TOTAL_CHIPS = "DLROVER_TPU_CLUSTER_CHIPS"
DEFAULT_TOTAL_CHIPS = 8

# curves fit over the newest N samples: old sizes a job has left must
# age out of its curve (and tools/brain_ctl.py `curves` shows the fit
# over the SAME window, so operators see the curve decisions were
# actually made from)
CURVE_FIT_LAST_N = 64


def parse_drain_ms(detail: str) -> float:
    """``drain_ms=412`` out of an eviction event's detail string; 0.0
    when absent/garbled (a notice-only event has no measurement yet)."""
    for tok in (detail or "").split():
        if tok.startswith("drain_ms="):
            try:
                return float(tok.split("=", 1)[1])
            except ValueError:
                return 0.0
    return 0.0


def observed_points(samples) -> Dict[int, float]:
    """(worker_count → best observed steps/sec) from a metric series —
    THE shared point-builder for `job_state` and brain_ctl."""
    points: Dict[int, float] = {}
    for s in samples:
        if s.alive_nodes > 0 and s.steps_per_sec > 0:
            points[s.alive_nodes] = max(
                points.get(s.alive_nodes, 0.0), s.steps_per_sec
            )
    return points


def plan_signature(
    version: int, job: str, worker_count: int, issued_ts: float
) -> int:
    """The scheduler's sign-off over one slice: executors recompute and
    compare before acting, so a torn row / spoofed response cannot
    resize a job (same integrity posture as the PR-5 checksummed
    checkpoint shards)."""
    payload = f"{version}:{job}:{worker_count}:{issued_ts:.6f}".encode()
    return zlib.crc32(payload)


@dataclass
class ScalingCurve:
    """Fitted ``speed(n) = a * n^b`` with the observed points kept for
    inspection (tools/brain_ctl.py ``curves``)."""

    a: float
    b: float
    points: Dict[int, float] = field(default_factory=dict)

    def predict(self, n: int) -> float:
        if n <= 0:
            return 0.0
        return self.a * float(n) ** self.b


def fit_scaling_curve(
    points: Dict[int, float]
) -> Optional[ScalingCurve]:
    """Least-squares power-law fit on log-log of (size → best observed
    steps/sec). One observed size falls back to ``DEFAULT_EXPONENT``;
    zero points means the job is unknowable (caller pins it)."""
    pts = {
        int(n): float(s)
        for n, s in points.items()
        if int(n) > 0 and float(s) > 0
    }
    if not pts:
        return None
    if len(pts) == 1:
        ((n0, s0),) = pts.items()
        b = DEFAULT_EXPONENT
        return ScalingCurve(a=s0 / float(n0) ** b, b=b, points=pts)
    xs = [math.log(n) for n in pts]
    ys = [math.log(s) for s in pts.values()]
    n = len(xs)
    mx, my = sum(xs) / n, sum(ys) / n
    var = sum((x - mx) ** 2 for x in xs)
    cov = sum((x - mx) * (y - my) for x, y in zip(xs, ys))
    b = cov / var if var > 0 else DEFAULT_EXPONENT
    b = min(MAX_EXPONENT, max(MIN_EXPONENT, b))
    # refit the scale with the clamped exponent (keeping the unclamped
    # intercept would bias predictions everywhere, not just at the clamp)
    a = math.exp(
        sum(y - b * x for x, y in zip(xs, ys)) / n
    )
    return ScalingCurve(a=a, b=b, points=pts)


@dataclass
class JobState:
    """One job's inputs to an allocation pass."""

    job: str
    curve: Optional[ScalingCurve]
    current: int
    goodput_pct: float = 0.0
    floor: int = 1
    frozen: bool = False
    verdicts: List[str] = field(default_factory=list)

    @property
    def weight(self) -> float:
        """Goodput weighting of the throughput utility: a chip on a
        job running at 50% goodput yields half the productive
        steps/sec its curve promises. 0.0 means "not reported" (the
        comm.JobMetricsSample contract) and weights as 1.0."""
        return self.goodput_pct / 100.0 if self.goodput_pct > 0 else 1.0

    def utility(self, n: int) -> float:
        if self.curve is None:
            return 0.0
        return self.weight * self.curve.predict(n)


def solve_allocation(
    jobs: List[JobState], total_chips: int, node_unit: int = 1
) -> Dict[str, int]:
    """Greedy marginal allocation of ``node_unit`` chunks under the
    budget: repeatedly hand the next chunk to the job with the best
    marginal goodput-per-chip gain. Exact for the concave clamped
    curves. Frozen / curve-less jobs are pinned at their current count
    (their chips are off the table); chips whose best marginal gain is
    ≤ 0 stay idle."""
    unit = max(1, node_unit)
    alloc: Dict[str, int] = {}
    budget = int(total_chips)
    free: List[JobState] = []
    for j in jobs:
        if j.frozen or j.curve is None:
            alloc[j.job] = j.current
            budget -= j.current
        else:
            free.append(j)
    for j in free:
        floor = max(unit, j.floor)
        if floor % unit:
            floor += unit - floor % unit  # whole slices only
        alloc[j.job] = floor
        budget -= floor
    if budget < 0:
        # oversubscribed (pins + floors exceed the budget): no safe
        # reallocation exists this pass — keep everyone where they are
        logger.warning(
            f"cluster scheduler: pinned+floor demand exceeds budget "
            f"{total_chips}; keeping current allocation"
        )
        return {j.job: j.current for j in jobs}
    while budget >= unit and free:
        best, best_gain = None, 0.0
        for j in free:
            cur = alloc[j.job]
            gain = j.utility(cur + unit) - j.utility(cur)
            if gain > best_gain:
                best, best_gain = j, gain
        if best is None:
            break  # every curve is flat: leave the chips idle
        alloc[best.job] += unit
        budget -= unit
    return alloc


class ClusterScheduler(PollingDaemon):
    """The Brain-side decision daemon. Runs over any object exposing
    the datastore protocol (``BrainServicer``): ``job_metrics`` /
    ``node_events`` / ``record_node_event`` / ``active_jobs`` and the
    ``cluster_plans`` table methods. Start it with ``.start()`` for
    the daemon loop or call ``run_pass()`` directly (tests)."""

    def __init__(
        self,
        servicer,
        total_chips: Optional[int] = None,
        node_unit: int = 1,
        interval: float = 15.0,
        min_dwell_s: float = MIN_DWELL_S,
        plan_ttl_s: float = PLAN_TTL_S,
        hysteresis_frac: float = HYSTERESIS_FRAC,
        active_window_s: float = ACTIVE_WINDOW_S,
        starvation_floor: Optional[int] = None,
        registry=None,
    ):
        super().__init__("brain-cluster-scheduler", interval)
        self._ds = servicer
        self.total_chips = int(
            total_chips
            if total_chips is not None
            else os.getenv(ENV_TOTAL_CHIPS, DEFAULT_TOTAL_CHIPS)
        )
        self.node_unit = max(1, node_unit)
        self.min_dwell_s = min_dwell_s
        self.plan_ttl_s = plan_ttl_s
        self.hysteresis_frac = hysteresis_frac
        self.active_window_s = active_window_s
        # every active job is guaranteed at least this many chips — a
        # cluster scheduler that starves a job to zero has turned a
        # resize into an eviction, which is the operator's call, not ours
        self.starvation_floor = max(
            self.node_unit, starvation_floor or self.node_unit
        )
        # job -> ts of its last emitted slice (min-dwell bookkeeping;
        # seeded from the plan table so a restarted Brain keeps dwell)
        self._last_change: Dict[str, float] = dict(
            getattr(servicer, "last_plan_ts_by_job", lambda: {})()
        )
        self._last_underperf: Dict[str, float] = {}
        if registry is None:
            from dlrover_tpu.obs.metrics import default_registry

            registry = default_registry()
        self._g_alloc = registry.gauge(
            "dlrover_brain_allocation",
            "cluster scheduler's target worker count per job",
            labelnames=("job",),
        )
        self._g_version = registry.gauge(
            "dlrover_brain_plan_version",
            "latest cluster plan version emitted",
        )
        self._g_latency = registry.gauge(
            "dlrover_brain_decision_to_resized_ms",
            "latest reported decision->resized latency per job",
            labelnames=("job",),
        )
        self._g_plans = registry.gauge(
            "dlrover_brain_plans",
            "cluster plan slices by status",
            labelnames=("status",),
        )
        self._g_emitted = registry.gauge(
            "dlrover_brain_plans_emitted",
            "total cluster plan slices ever emitted",
        )

    # -- preemption pricing --------------------------------------------
    def _recent_evictions(self, job: str, now: float) -> List:
        """This job's `eviction` node events inside the pricing window
        (empty when the datastore predates the event feed)."""
        try:
            return list(
                self._ds.node_events(
                    job=job,
                    event="eviction",
                    since_ts=now - EVICTION_WINDOW_S,
                )
            )
        except Exception:
            return []

    def dwell_for(
        self,
        job: str,
        now: float,
        evictions: Optional[List] = None,
        latencies: Optional[Dict[str, float]] = None,
    ) -> float:
        """Per-job min-dwell, priced from MEASURED downtime: the
        configured floor, raised to ``DWELL_DOWNTIME_FACTOR`` × (the
        job's latest decision→resized latency + its worst recent
        eviction drain). A job that pays 4 s per reallocation earns a
        2-minute-plus dwell; a 0.2 s warm-dp job keeps the floor.
        ``evictions``/``latencies`` let a pass reuse already-fetched
        rows instead of re-querying per job."""
        if latencies is None:
            try:
                latencies = self._ds.latest_outcome_latencies()
            except Exception:
                latencies = {}
        downtime_s = latencies.get(job, 0.0) / 1e3
        if evictions is None:
            evictions = self._recent_evictions(job, now)
        drains = [
            parse_drain_ms(getattr(e, "detail", "")) for e in evictions
        ]
        if drains:
            downtime_s += max(drains) / 1e3
        return max(self.min_dwell_s, DWELL_DOWNTIME_FACTOR * downtime_s)

    # -- inputs --------------------------------------------------------
    def job_state(
        self,
        job: str,
        now: float,
        exclude: Tuple[str, ...] = (),
        latencies: Optional[Dict[str, float]] = None,
    ) -> JobState:
        """Everything the allocator needs to know about one job,
        including the unified algorithm verdicts (satellite: hot-node /
        underperformance / bad-node live INSIDE the scheduler pass,
        not beside it)."""
        from dlrover_tpu.brain.algorithms import job_verdicts

        samples = self._ds.job_metrics(job, last_n=CURVE_FIT_LAST_N)
        curve = fit_scaling_curve(observed_points(samples))
        live = [s for s in samples if s.alive_nodes > 0]
        current = self._ds.last_planned_count(job) or (
            live[-1].alive_nodes if live else 0
        )
        goodput = 0.0
        for s in reversed(samples):
            if s.goodput_pct > 0:
                goodput = s.goodput_pct
                break
        evictions = self._recent_evictions(job, now)
        floor = self.starvation_floor
        if evictions:
            # eviction-prone: the platform keeps reclaiming this job's
            # chips — hold one extra unit of headroom so each reclaim
            # degrades it toward the floor instead of through it
            floor += self.node_unit
        state = JobState(
            job=job,
            curve=curve,
            current=current,
            goodput_pct=goodput,
            floor=floor,
            frozen=(
                now - self._last_change.get(job, -math.inf)
                < self.dwell_for(
                    job, now, evictions=evictions, latencies=latencies
                )
            ),
        )
        if evictions:
            state.verdicts.append("eviction_prone")
        v = job_verdicts(
            self._ds,
            job,
            samples=samples,
            node_unit=self.node_unit,
            now=now,
            exclude=exclude,
        )
        if v.hot is not None and not state.frozen:
            # pressure-driven scale-out: the hot verdict raises this
            # job's floor one unit above its current size for the pass
            state.floor = max(state.floor, current + self.node_unit)
            state.verdicts.append("hot")
        if v.underperformance:
            state.verdicts.append("underperformance")
            last = self._last_underperf.get(job, -math.inf)
            if now - last >= UNDERPERF_REFIRE_S:
                self._last_underperf[job] = now
                from dlrover_tpu.common import comm

                self._ds.record_node_event(
                    comm.BrainNodeEventReport(
                        job_name=job, event="underperformance"
                    )
                )
                logger.warning(
                    f"cluster scheduler: {job} {v.underperformance}"
                )
        return state

    # -- the pass ------------------------------------------------------
    def _tick(self):
        self.run_pass()

    def run_pass(self, now: Optional[float] = None) -> Optional[int]:
        """One closed-loop pass: expire stale plans, rebuild job
        states, solve the allocation, emit a plan when it clears the
        hysteresis gate. Returns the emitted plan version or None."""
        now = time.time() if now is None else now
        self._ds.expire_stale_plans(now - self.plan_ttl_s)
        from dlrover_tpu.brain.algorithms import bad_node_exclusion

        exclude = bad_node_exclusion(
            self._ds, now=now,
            cluster=getattr(self._ds, "cluster", "default"),
        )
        try:
            # one fetch per pass: dwell pricing reads the same map for
            # every job (hundreds of jobs = hundreds of redundant
            # plan_outcomes scans otherwise)
            latencies = self._ds.latest_outcome_latencies()
        except Exception:
            latencies = {}
        jobs = [
            self.job_state(j, now, exclude=exclude, latencies=latencies)
            for j in self._ds.active_jobs(now - self.active_window_s)
        ]
        version: Optional[int] = None
        if jobs:
            alloc = solve_allocation(
                jobs, self.total_chips, self.node_unit
            )
            changes = {
                j.job: alloc[j.job]
                for j in jobs
                if not j.frozen
                and j.curve is not None
                and alloc[j.job] != j.current
                and alloc[j.job] > 0
            }
            if changes and self._clears_hysteresis(jobs, alloc):
                version = self._emit(jobs, changes, exclude, now)
        self._export(jobs, now)
        return version

    def _clears_hysteresis(
        self, jobs: List[JobState], alloc: Dict[str, int]
    ) -> bool:
        """A reallocation pays ~0.1–0.2 s of warm-resize downtime per
        touched job; demand at least ``hysteresis_frac`` of predicted
        aggregate utility in return. A job below its floor (starved or
        hot-boosted) always justifies the plan — floors are contracts,
        not optimizations."""
        if any(
            not j.frozen and j.curve is not None and j.current < j.floor
            for j in jobs
        ):
            return True
        cur_u = sum(j.utility(j.current) for j in jobs)
        new_u = sum(j.utility(alloc[j.job]) for j in jobs)
        if new_u > cur_u * (1.0 + self.hysteresis_frac):
            return True
        logger.info(
            f"cluster scheduler: predicted gain "
            f"{new_u - cur_u:+.3f} under hysteresis "
            f"({self.hysteresis_frac:.0%} of {cur_u:.3f}); holding"
        )
        return False

    def _emit(
        self,
        jobs: List[JobState],
        changes: Dict[str, int],
        exclude: Tuple[str, ...],
        now: float,
    ) -> int:
        states = {j.job: j for j in jobs}
        version = self._ds.next_plan_version()
        slices = []
        for job, count in sorted(changes.items()):
            st = states[job]
            reason = (
                f"goodput-per-chip rebalance {st.current}->{count} "
                f"(curve b={st.curve.b:.2f}, weight {st.weight:.2f}"
                + (
                    f", verdicts: {','.join(st.verdicts)}"
                    if st.verdicts
                    else ""
                )
                + ")"
            )
            slices.append(
                {
                    "job": job,
                    "worker_count": count,
                    "prev_count": st.current,
                    "reason": reason,
                    "exclude_hosts": list(exclude),
                }
            )
            self._last_change[job] = now
        self._ds.record_cluster_plan(version, slices, now)
        logger.info(
            f"cluster plan v{version}: "
            + ", ".join(
                f"{s['job']} {s['prev_count']}->{s['worker_count']}"
                for s in slices
            )
            + (f" (exclude {list(exclude)})" if exclude else "")
        )
        return version

    # -- observability -------------------------------------------------
    def _export(self, jobs: List[JobState], now: float):
        live = set()
        for j in jobs:
            self._g_alloc.labels(j.job).set(
                float(self._ds.last_planned_count(j.job) or j.current)
            )
            live.add((j.job,))
        # departed jobs must not keep exposing a frozen allocation
        with self._g_alloc._lock:
            for key in [
                k for k in self._g_alloc._children if k not in live
            ]:
                del self._g_alloc._children[key]
        counts = self._ds.plan_status_counts()
        for status in ("pending", "acked", "expired", "superseded"):
            self._g_plans.labels(status).set(
                float(counts.get(status, 0))
            )
        self._g_emitted.set(float(sum(counts.values())))
        self._g_version.set(float(self._ds.latest_plan_version()))
        for job, latency in self._ds.latest_outcome_latencies().items():
            self._g_latency.labels(job).set(latency)
