"""Seconds of the new worker's first ``build:step_donating`` /
``build:step_safe`` span: compile or cache load plus one step, after the
restore. ``PipelineStats.startup_first_step_s``, read from the second
incarnation's final report (``worker_r1.json``). Nothing where the run did not
come back from a kill, or on a program without the field."""

LAYER = "strategy + build"
UNIT = "s"
MOVES = "setup_s"


def CELLS(cell):
    return bool(cell["kill"])


def read(run):
    if not run.recovery:
        return None
    return (run.reports[1].get("pipeline") or {}).get("startup_first_step_s")
