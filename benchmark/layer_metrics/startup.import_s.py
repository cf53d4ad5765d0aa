"""Seconds from the agent's ``Popen`` of the first worker to its
``init_elastic()``: the interpreter's start and every import up to there
(``import jax`` among them), on ``time.monotonic()``, one clock for the host.
``PipelineStats.startup_import_s``, read from the first incarnation's record
at the window's close (``window_r0.json``). A program without the field gives
nothing."""

LAYER = "launcher + agent"
UNIT = "s"
MOVES = "setup_s"


def CELLS(cell):
    return True


def read(run):
    return (run.window.get("pipeline") or {}).get("startup_import_s")
