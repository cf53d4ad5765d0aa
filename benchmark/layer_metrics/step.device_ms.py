"""Milliseconds the device was busy per step: the busy time of the traced
stretch divided by the whole step executions it holds, both from the
device trace (``xplane.step_stretch``), never from the host's hooks."""

LAYER = "step program"
UNIT = "ms"
MOVES = "tokens_per_s"


def CELLS(cell):
    return True


def read(run):
    if not run.trace or not run.trace.get("devices"):
        return None
    return 1e3 * run.trace["busy_s"] / run.trace["steps"]
