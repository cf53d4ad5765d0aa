"""Seconds in ``ElasticTrainer(...)`` in the second incarnation, the
restore from agent shm included."""

LAYER = "strategy + build"
UNIT = "s"
MOVES = "setup_s"


def CELLS(cell):
    return bool(cell["kill"])


def read(run):
    return run.recovery and run.recovery["build_restart_s"]
