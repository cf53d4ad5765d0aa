"""Window and percentile arithmetic on step records (no JAX, no program).

A step record is ``{"step": int, "t": seconds on CLOCK_MONOTONIC, ...}``,
one per call of the trainer's ``metrics_hook``. Every step ends in a device
read before the hook, so the time between two hooks is one real step.
"""

import json
import math
from typing import Dict, List, Optional, Sequence


def read_step_records(path: str) -> List[Dict]:
    """The step records a worker wrote, one JSON object per line; a line
    the kill cut short is left out, a missing file is no records."""
    rows = []
    try:
        with open(path) as f:
            for line in f:
                try:
                    rows.append(json.loads(line))
                except ValueError:
                    pass
    except OSError:
        pass
    return rows


def percentile(values: Sequence[float], q: float) -> float:
    """Linear-interpolated percentile, ``q`` in [0, 100] (numpy's
    default method), on plain floats."""
    if not values:
        raise ValueError("percentile of nothing")
    s = sorted(values)
    if len(s) == 1:
        return float(s[0])
    pos = (len(s) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(s) - 1)
    return float(s[lo] + (s[hi] - s[lo]) * (pos - lo))


def window_records(records: List[Dict], t_open: float, t_close: float):
    """Records whose hook fell in [t_open, t_close], in step order."""
    return [r for r in records if t_open <= r["t"] <= t_close]


def window_summary(rows: List[Dict], tokens_per_step: int) -> Dict:
    """Throughput over all the work and all the time of the window, and
    the step-time samples inside it, from the window's records
    (``window_records``). The window opens and closes at a hook, so the
    first record is the opening boundary and does not count as a step of
    the window."""
    if len(rows) < 2:
        raise ValueError(f"window holds {len(rows)} hooks, need two")
    steps = rows[-1]["step"] - rows[0]["step"]
    seconds = rows[-1]["t"] - rows[0]["t"]
    if steps != len(rows) - 1:
        raise ValueError(
            f"window lost hooks: {len(rows)} records span {steps} steps"
        )
    deltas = [1e3 * (b["t"] - a["t"]) for a, b in zip(rows, rows[1:])]
    return {
        "steps": steps,
        "seconds": seconds,
        "tokens_per_s": steps * tokens_per_step / seconds,
        "step_ms": deltas,
        "step_p50_ms": percentile(deltas, 50),
        "step_p95_ms": percentile(deltas, 95),
        "samples": len(deltas),
        "first_step": rows[0]["step"],
        "last_step": rows[-1]["step"],
    }


def time_of_step(records: List[Dict], step: int) -> Optional[float]:
    for r in records:
        if r["step"] == step:
            return r["t"]
    return None


def saves_begun(records: List[Dict]) -> List[int]:
    """Steps after whose hook a flash save really began: the next hook
    sees staging, more chunks written, or (a one-chunk state) a commit.
    A save that falls due while the agent's saver still persists the
    previous one is skipped by the program and does not count."""
    begun = []
    for r, nxt in zip(records, records[1:]):
        if not r["staging"] and (
            nxt["staging"]
            or nxt["stage_chunks"] > r["stage_chunks"]
        ):
            begun.append(r["step"])
    return begun


def commit_lags(records: List[Dict]) -> List[int]:
    """Steps from a save's start to the hook that first sees
    ``stage_commits`` rise, for the saves that began and committed
    inside ``records``."""
    lags = []
    starts = saves_begun(records)
    for r, nxt in zip(records, records[1:]):
        if nxt["commits"] > r["commits"]:
            earlier = [s for s in starts if s < nxt["step"]]
            if earlier:
                lags.append(nxt["step"] - earlier[-1])
                starts = [s for s in starts if s >= nxt["step"]]
    return lags
