"""Seconds the second incarnation's restore spent moving the records to
the device (``sharding.restore_state`` to ``block_until_ready`` of the
restored state), timed by ``CheckpointEngine.load`` where it happens and
folded into ``PipelineStats.restore_h2d_s``. A program without the
field gives nothing."""

LAYER = "flash checkpoint"
UNIT = "s"
MOVES = "setup_s"


def CELLS(cell):
    return bool(cell["kill"])


def read(run):
    pipeline = (run.reports.get(1) or {}).get("pipeline") or {}
    if not pipeline.get("restore_source"):
        return None
    return pipeline.get("restore_h2d_s")
