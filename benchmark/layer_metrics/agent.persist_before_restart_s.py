"""Seconds of the agent's persist-before-restart: the save-at-breakpoint hook
that writes the newest flash save to storage before the workers are stopped
(the ``persist_before_restart`` leg of its ``recover`` span).
``PipelineStats.recover_persist_s``, read from the second incarnation's final
report (``worker_r1.json``). Nothing where the run did not come back from a
kill, or on a program without the field."""

LAYER = "launcher + agent"
UNIT = "s"
MOVES = "setup_s"


def CELLS(cell):
    return bool(cell["kill"])


def read(run):
    if not run.recovery:
        return None
    return (run.reports[1].get("pipeline") or {}).get("recover_persist_s")
