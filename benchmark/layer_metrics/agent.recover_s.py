"""Seconds from the last step hook of the worker that was killed to the
hook of the new one that reports the same step again: detect + persist +
respawn + open chip + build + restore + compile-or-load + replay. What a
death costs. It was the end-to-end ``recover_s``; its runs spread by 6-8 %
of the median (the agent's 3 s tick, its persist to disk, the worker's
start), more than half of the widest bound, so it is read here, unbounded.
The recovery runs the set-up's code a second time (worker start, chip open,
build, compile-or-load), so ``setup_s`` is the bounded metric it moves with."""

LAYER = "launcher + agent"
UNIT = "s"
MOVES = "setup_s"


def CELLS(cell):
    return bool(cell["kill"])


def read(run):
    return run.recovery and run.recovery["recover_s"]
