"""Median time between two step hooks in the window: the steadier
companion of ``step_p95_ms``."""

LAYER = "trainer loop"
UNIT = "ms"
MOVES = "tokens_per_s"


def CELLS(cell):
    return True


def read(run):
    return run.summary["step_p50_ms"]
