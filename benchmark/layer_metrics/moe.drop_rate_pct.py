"""Share of the (token, expert) assignments the sparse layers dropped,
mean over the steps the trainer reported inside the window: the rise of
``PipelineStats.moe_drop_rate_sum`` over the rise of ``moe_reports`` (the
trainer adds the reported step's ``moe_drop_rate`` at every
``log_interval``-th step). The dropless dispatch must read 0. Nothing to
read where the cell has no experts, the program has no such counter, or
no report fell inside the window."""

LAYER = "step program"
UNIT = "%"
MOVES = "tokens_per_s"


def CELLS(cell):
    return bool(cell.get("moe"))


def read(run):
    opened = run.window.get("pipeline_open") or {}
    closed = run.window.get("pipeline") or {}

    def rise(field):
        return closed.get(field, 0) - opened.get(field, 0)

    reports = rise("moe_reports")
    if not reports:
        return None
    return 100.0 * rise("moe_drop_rate_sum") / reports
