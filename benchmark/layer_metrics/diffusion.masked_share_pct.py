"""The share of a step's data tokens that the noise of diffusion over
blocks masked (``cfg.objective`` "block_diffusion": ``x_t`` reads the mask
id there, and only there does a position's cross-entropy enter the loss,
weighted ``1 / t``), in percent, mean over the steps the trainer reported
inside the window: the rise of ``PipelineStats.diffusion_masked_sum`` over
the rise of ``diffusion_reports``, as ``ut.exit_entropy_nats`` reads the
exits'. A block's ``t`` is uniform in ``[t_min, 1)``, so it reads ``100 (1
+ t_min) / 2``, 50.05 at ``t_min`` 1e-3, give or take the draw (8,192
positions in 2,048 blocks a row: a standard deviation near 0.7); 0 or 100
says the noise is dead and the cell measures something else (every
position's loss weight 0, or every input the mask id). Nothing to read
where the configuration's model states no such objective, the program has
no such counter, or no report fell inside the window."""

import json
import os

LAYER = "step program"
UNIT = "%"
MOVES = "tokens_per_s"

CONFIGS = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "configs"
)


def _states_the_objective(model) -> bool:
    return model.get("objective") == "block_diffusion"


def CELLS(cell):
    """The cells whose configuration's model states the objective, as
    ``attn.bd_blocks_walked_pct`` has it."""
    try:
        with open(os.path.join(CONFIGS, f"{cell.get('config')}.json")) as f:
            model = json.load(f)["model"]
    except (OSError, ValueError, KeyError, TypeError):
        return True
    return _states_the_objective(model)


def read(run):
    if not _states_the_objective(run.config.get("model") or {}):
        return None
    opened = run.window.get("pipeline_open") or {}
    closed = run.window.get("pipeline") or {}

    def rise(field):
        return closed.get(field, 0) - opened.get(field, 0)

    reports = rise("diffusion_reports")
    if not reports:
        return None
    return 100.0 * rise("diffusion_masked_sum") / reports
