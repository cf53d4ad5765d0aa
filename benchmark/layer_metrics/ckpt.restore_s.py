"""Seconds in ``FlashCheckpointer.load_checkpoint`` in the second
incarnation, to ``block_until_ready`` of the restored state."""

LAYER = "flash checkpoint"
UNIT = "s"
MOVES = "setup_s"


def CELLS(cell):
    return bool(cell["kill"])


def read(run):
    return run.recovery and run.recovery["restore_s"]
