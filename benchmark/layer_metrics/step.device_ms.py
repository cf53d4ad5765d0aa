"""Milliseconds the device was busy per step: the union of the device
plane's operation events over the traced stretch, divided by the steps
traced."""

LAYER = "step program"
UNIT = "ms"
MOVES = "tokens_per_s"


def CELLS(cell):
    return True


def read(run):
    if not run.trace or not run.trace.get("devices"):
        return None
    t = run.window["trace"]
    steps = t["step_end"] - t["step_begin"]
    return 1e3 * run.trace["busy_s"] / steps if steps else None
