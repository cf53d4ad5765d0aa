"""Steps from a save's start to the hook that sees ``stage_commits`` rise,
median over the saves of the window: how stale the newest committed state
is when the worker dies."""

import statistics

LAYER = "flash checkpoint"
UNIT = "steps"
MOVES = "setup_s"


def CELLS(cell):
    return bool(cell["kill"])


def read(run):
    import arith

    lags = arith.commit_lags(run.in_window)
    return statistics.median(lags) if lags else None
