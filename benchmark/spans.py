"""Arithmetic on the window's host spans (``run.spans``: ``[name,
start_ns, dur_ns, depth, tid]`` records of the program's ``SpanTracer``,
less the steps that hold the profiler's start and stop), shared by the
per-layer readers that take a step apart.

The train thread is the thread of the ``step`` spans. A span lies in a
step when it begins and ends inside it, on that thread. A *chunk step*
is a step that holds a ``ckpt_stage`` span: the trainer staged a chunk
of a flash save after it. Every function returns ``None`` where the
spans it needs are not there (a program that does not write them)."""

from statistics import median
from typing import Dict, Iterable, List, Optional, Tuple

Span = List  # [name, start_ns, dur_ns, depth, tid]
Step = Tuple[Span, List[Span]]  # the step span, the spans inside it


def steps_with_children(spans: List[Span]) -> List[Step]:
    """Every ``step`` span of the train thread with the spans that lie
    inside it, in time order."""
    steps = sorted((s for s in spans if s[0] == "step"), key=lambda s: s[1])
    if not steps:
        return []
    tid = steps[0][4]
    rest = sorted(
        (s for s in spans if s[4] == tid and s[0] != "step"),
        key=lambda s: s[1],
    )
    out, i = [], 0
    for step in steps:
        if step[4] != tid:
            continue
        start, end = step[1], step[1] + step[2]
        while i < len(rest) and rest[i][1] < start:
            i += 1
        inside = []
        while i < len(rest) and rest[i][1] < end:
            if rest[i][1] + rest[i][2] <= end:
                inside.append(rest[i])
            i += 1
        out.append((step, inside))
    return out


def mean_ms_per_step(spans: List[Span], names: Iterable[str]) -> Optional[float]:
    """Summed duration of the named spans inside each step, mean over
    the steps, in ms. None where no step holds one."""
    names = tuple(names)
    steps = steps_with_children(spans)
    sums = [
        sum(c[2] for c in inside if c[0] in names) for _s, inside in steps
    ]
    if not any(sums):
        return None
    return sum(sums) / (1e6 * len(steps))


def unattributed_ms_per_step(spans: List[Span]) -> Optional[float]:
    """Each step less its direct children (one level deeper), mean, ms."""
    steps = steps_with_children(spans)
    if not steps:
        return None
    left = sum(
        step[2] - sum(c[2] for c in inside if c[3] == step[3] + 1)
        for step, inside in steps
    )
    return left / (1e6 * len(steps))


def chunk_steps(spans: List[Span]) -> Dict[str, List[Step]]:
    """The steps split into ``staging`` (hold a ``ckpt_stage`` span) and
    ``plain`` (hold none)."""
    out: Dict[str, List[Step]] = {"staging": [], "plain": []}
    for step, inside in steps_with_children(spans):
        kind = (
            "staging" if any(c[0] == "ckpt_stage" for c in inside)
            else "plain"
        )
        out[kind].append((step, inside))
    return out


def mean_ms_per_chunk_step(
    spans: List[Span], names: Iterable[str]
) -> Optional[float]:
    """Summed duration of the named spans inside each chunk step, mean
    over the chunk steps, in ms."""
    names = tuple(names)
    staging = chunk_steps(spans)["staging"]
    sums = [
        sum(c[2] for c in inside if c[0] in names)
        for _s, inside in staging
    ]
    if not any(sums):
        return None
    return sum(sums) / (1e6 * len(staging))


def chunk_step_extra_ms(spans: List[Span]) -> Optional[float]:
    """Median of (step less the ``device_wait`` inside it) over the
    chunk steps, less the same over the plain steps, in ms: what
    staging a chunk adds to a step beside the device's own time. The
    median, because the plain steps hold the steps in which a save
    falls due while the agent still persists the last one (the lock
    call in their ``ckpt_save`` span takes up to a second to say so),
    and a mean would charge that to the steps that stage nothing."""
    kinds = chunk_steps(spans)

    def host_side(rows: List[Step]) -> Optional[float]:
        waits = [
            sum(c[2] for c in inside if c[0] == "device_wait")
            for _s, inside in rows
        ]
        if not rows or not any(waits):
            return None
        return median(s[2] - w for (s, _i), w in zip(rows, waits))

    a, b = host_side(kinds["staging"]), host_side(kinds["plain"])
    if a is None or b is None:
        return None
    return (a - b) / 1e6
