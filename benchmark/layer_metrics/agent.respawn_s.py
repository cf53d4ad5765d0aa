"""Seconds of the agent's ``stop_workers`` + ``shm_lock_reset`` +
``rendezvous`` legs: what lies between its persist and the new worker's
``Popen``. ``PipelineStats.recover_respawn_s``, read from the second
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
    return (run.reports[1].get("pipeline") or {}).get("recover_respawn_s")
