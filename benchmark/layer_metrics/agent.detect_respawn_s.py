"""Seconds from the last step hook of the worker that was killed to the
``up`` report of the new one (it holds the chip again): the agent's 3 s
monitor tick, its persist-before-restart, the respawn, the worker's start
and the opening of the chip."""

LAYER = "launcher + agent"
UNIT = "s"
MOVES = "setup_s"


def CELLS(cell):
    return bool(cell["kill"])


def read(run):
    return run.recovery and run.recovery["detect_respawn_s"]
