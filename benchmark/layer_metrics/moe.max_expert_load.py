"""How uneven the routing is: the busiest expert's share of the (token,
expert) assignments (in the mean over the sparse layers) times the number
of experts, mean over the steps the trainer reported inside the window
(1 = every expert gets the same, ``num_experts`` = one expert gets all).
The rise of ``PipelineStats.moe_max_load_sum`` over the rise of
``moe_reports``. Nothing to read where the cell has no experts, the
program has no such counter, or no report fell inside the window."""

LAYER = "step program"
UNIT = "x"
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
    return rise("moe_max_load_sum") / reports
