"""Seconds of the agent's monitor tick in which it found the death: an upper
bound on the detection's own share of a recovery (the death fell somewhere
inside it). The agent's ``recovery timeline`` hands it to the worker it
starts; ``PipelineStats.recover_detect_tick_s``, read from the second
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
    return (run.reports[1].get("pipeline") or {}).get("recover_detect_tick_s")
