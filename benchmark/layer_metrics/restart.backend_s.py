"""Seconds of the new worker's ``backend_up`` span: the backend's start and
the chip's opening after a death, as ``startup.backend_s`` is for the first.
``PipelineStats.startup_backend_s``, read from the second incarnation's final
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
    return (run.reports[1].get("pipeline") or {}).get("startup_backend_s")
