"""Milliseconds on the critical path per flash save: the rise of
``PipelineStats.stage_block_s`` over the window divided by the saves that
began in it."""

LAYER = "flash checkpoint"
UNIT = "ms"
MOVES = "tokens_per_s"


def CELLS(cell):
    return cell["save_memory_interval"] < cell["max_steps"]


def read(run):
    import arith

    rows = run.in_window
    begun = len(arith.saves_begun(rows))
    if not begun:
        return None
    return 1e3 * (rows[-1]["stage_block_s"] - rows[0]["stage_block_s"]) / begun
