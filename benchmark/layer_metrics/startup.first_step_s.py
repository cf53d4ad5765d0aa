"""Seconds of the first worker's first ``build:step_donating`` /
``build:step_safe`` span: the step program's compile, or its load from the
persistent cache, plus the call of one step.
``PipelineStats.startup_first_step_s``, read from the first incarnation's
record at the window's close (``window_r0.json``). A program without the field
gives nothing."""

LAYER = "strategy + build"
UNIT = "s"
MOVES = "setup_s"


def CELLS(cell):
    return True


def read(run):
    return (run.window.get("pipeline") or {}).get("startup_first_step_s")
