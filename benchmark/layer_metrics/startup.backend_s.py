"""Seconds of the first worker's ``backend_up`` span (``init_elastic``: device
spec, compile cache, ``jax.distributed.initialize`` where the world has
several processes, the backend's start and the chip's opening).
``PipelineStats.startup_backend_s``, read from the first incarnation's record
at the window's close (``window_r0.json``). A program without the field gives
nothing."""

LAYER = "launcher + agent"
UNIT = "s"
MOVES = "setup_s"


def CELLS(cell):
    return True


def read(run):
    return (run.window.get("pipeline") or {}).get("startup_backend_s")
