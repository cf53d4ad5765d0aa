"""Programs the persistent compile cache did not hold on the new worker's way
to its first step (its ``build:<what>`` rows up to and including the first
step's): whether a recovery compiled what the first incarnation had cached.
``PipelineStats.startup_cache_misses``, read from the second incarnation's
final report (``worker_r1.json``). Nothing where the run did not come back
from a kill, or on a program without the field."""

LAYER = "strategy + build"
UNIT = "programs"
MOVES = "setup_s"


def CELLS(cell):
    return bool(cell["kill"])


def read(run):
    if not run.recovery:
        return None
    return (run.reports[1].get("pipeline") or {}).get("startup_cache_misses")
