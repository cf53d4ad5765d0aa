"""Share, in percent, of a step's query rows whose position scale is not 1
(``cfg.attn_pos_scale_beta`` > 0: a query is multiplied by ``1 + beta
ln(1 + floor(pos / rope_original_len))`` after its rotation):
``PipelineStats.attn_pos_scaled_rows`` over ``attn_pos_rows`` as the
program traced last (``common/trace_counts``: the rows at positions from
``rope_original_len`` on, and all rows, each summed over the attention
sites, from the static row length). 50 in the Mistral-Small-4 cell (rows of
16,384 over a table made for 8,192), 0 on rows of 8,192 or fewer. It says
whether the cell's rows reach the positions where the source's scale is
live, which a later change of the cell's row length must not lose
silently. Nothing to read where the configuration states no such scale, or
the program has no such counter."""

import json
import os

LAYER = "step program"
UNIT = "%"
MOVES = "tokens_per_s"

CONFIGS = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "configs"
)


def _pos_scaled(model) -> bool:
    return (model.get("attn_pos_scale_beta") or 0) > 0


def CELLS(cell):
    """The cells whose configuration scales a query by its position. A
    cell of another data directory (a rehearsal's) is left to ``read``."""
    try:
        with open(os.path.join(CONFIGS, f"{cell.get('config')}.json")) as f:
            model = json.load(f)["model"]
    except (OSError, ValueError, KeyError, TypeError):
        return True
    return _pos_scaled(model)


def read(run):
    if not _pos_scaled(run.config.get("model") or {}):
        return None
    pipeline = run.window.get("pipeline") or {}
    rows = pipeline.get("attn_pos_rows")
    if not rows or "attn_pos_scaled_rows" not in pipeline:
        return None
    return 100.0 * pipeline["attn_pos_scaled_rows"] / rows
