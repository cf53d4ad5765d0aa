"""Seconds the second incarnation's restore spent on the crc pass over
the agent's shm records (``ShmHandler.load_records(verify=True)``),
timed by ``CheckpointEngine.load`` where it happens and folded into
``PipelineStats.restore_shm_verify_s`` (the second incarnation's report
carries the whole record). A program without the field gives nothing."""

LAYER = "flash checkpoint"
UNIT = "s"
MOVES = "setup_s"


def CELLS(cell):
    return bool(cell["kill"])


def read(run):
    pipeline = (run.reports.get(1) or {}).get("pipeline") or {}
    if not pipeline.get("restore_source"):
        return None
    return pipeline.get("restore_shm_verify_s")
