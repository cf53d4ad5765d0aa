"""Seconds in ``ElasticTrainer(...)`` in the first incarnation: strategy,
dry run, both step twins, state."""

LAYER = "strategy + build"
UNIT = "s"
MOVES = "setup_s"


def CELLS(cell):
    return True


def read(run):
    return run.reports[0].get("build_seconds")
