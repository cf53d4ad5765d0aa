"""Programs the persistent compile cache did not hold on the first worker's
way to its first step: the ``cache_misses`` of its ``build:<what>`` rows up to
and including the first step's. 0 says the run loaded what it ran; anything
else, that its set-up holds a compile. ``PipelineStats.startup_cache_misses``,
read from the first incarnation's record at the window's close
(``window_r0.json``). A program without the field gives nothing."""

LAYER = "strategy + build"
UNIT = "programs"
MOVES = "setup_s"


def CELLS(cell):
    return True


def read(run):
    return (run.window.get("pipeline") or {}).get("startup_cache_misses")
