"""Seconds of ``agent.detect_respawn_s`` (last hook of the killed worker →
``up`` report of the new one, host clock) that the program's own timeline
does not cover: less the agent's tick, persist and respawn legs and the new
worker's imports and ``backend_up`` span, all from the second incarnation's
record. What ``loop.unattributed_ms_per_step`` is for the step. The tick is
an upper bound on the detection, so down to minus one tick is sound; above
about a second, a leg of the recovery has no span (on the chip: the dead
worker's exit, until the kernel has released the chip and ``poll()`` can see
the death; PERF.md, 7). Nothing where the run did not come back from a kill,
or on a program without the fields."""

LAYER = "launcher + agent"
UNIT = "s"
MOVES = "setup_s"

COVERED = (
    "recover_detect_tick_s", "recover_persist_s", "recover_respawn_s",
    "startup_import_s", "startup_backend_s",
)


def CELLS(cell):
    return bool(cell["kill"])


def read(run):
    if not run.recovery:
        return None
    pipeline = run.reports[1].get("pipeline") or {}
    if any(field not in pipeline for field in COVERED):
        return None
    return run.recovery["detect_respawn_s"] - sum(
        pipeline[field] for field in COVERED
    )
