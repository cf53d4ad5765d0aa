"""Share, in percent, of the (token, expert) assignments that fell on the
experts this chip holds, mean over the sparse layers and over the steps the
trainer reported inside the window: the rise of
``PipelineStats.moe_held_share_sum`` over the rise of ``moe_reports`` (the
trainer adds, at every ``log_interval``-th step, the reported step's
``moe_expert_load`` summed over the held experts). Balanced routing reads
``100 * experts_held / num_experts`` (6.25 for 8 of 128); what the grouped
matmuls of a step really get is this share of the k * T assignments, where
the family module can only count the balanced expectation, so read
``kernel.moe_gmm_roofline`` beside it. Nothing to read where the
configuration holds every expert it routes over, the program has no such
counter, or no report fell inside the window."""

import json
import os

LAYER = "step program"
UNIT = "%"
MOVES = "tokens_per_s"

CONFIGS = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "configs"
)


def _holds_a_share(model) -> bool:
    held = model.get("experts_held") or 0
    return 0 < held < (model.get("num_experts") or 0)


def CELLS(cell):
    """The cells whose configuration holds fewer experts than it routes
    over. A cell of another data directory (a rehearsal's) is left to
    ``read``."""
    try:
        with open(os.path.join(CONFIGS, f"{cell.get('config')}.json")) as f:
            model = json.load(f)["model"]
    except (OSError, ValueError, KeyError, TypeError):
        return bool(cell.get("moe"))
    return _holds_a_share(model)


def read(run):
    if not _holds_a_share(run.config.get("model") or {}):
        return None
    opened = run.window.get("pipeline_open") or {}
    closed = run.window.get("pipeline") or {}
    if "moe_held_share_sum" not in closed:
        return None

    def rise(field):
        return closed.get(field, 0) - opened.get(field, 0)

    reports = rise("moe_reports")
    if not reports:
        return None
    return 100.0 * rise("moe_held_share_sum") / reports
