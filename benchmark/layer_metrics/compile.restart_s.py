"""Seconds the second incarnation spent in XLA's backend compile and in
retrieval from the persistent cache (``jax.monitoring`` events). Hits and
misses are on an earlier line of the run's output."""

LAYER = "strategy + build"
UNIT = "s"
MOVES = "setup_s"


def CELLS(cell):
    return bool(cell["kill"])


def read(run):
    if not run.recovery or not run.recovery.get("compile_restart"):
        return None
    t = run.recovery["compile_restart"]
    return t["backend_compile_s"] + t["cache_retrieval_s"]
