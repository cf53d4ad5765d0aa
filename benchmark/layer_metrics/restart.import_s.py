"""Seconds from the agent's ``Popen`` of the new worker to its
``init_elastic()``: interpreter and imports, as ``startup.import_s`` is for
the first. ``PipelineStats.startup_import_s``, read from the second
incarnation's final report (``worker_r1.json``). Nothing where the run did not
come back from a kill, or on a program without the field."""

LAYER = "launcher + agent"
UNIT = "s"
MOVES = "setup_s"


def CELLS(cell):
    return bool(cell["kill"])


def read(run):
    if not run.recovery:
        return None
    return (run.reports[1].get("pipeline") or {}).get("startup_import_s")
