"""Share, in percent, of the lanes of a head's query and key that the
attention sites of the train step were CALLED with which the model states
(``PipelineStats.attn_score_lanes_used`` over ``attn_score_lanes``: the
trainer sets both from what the train step's build traced,
``models/transformer.ScoreLanes``, each summed over the sites). The
attention kernels take one width of whole lane tiles for q, k and v, so a
latent attention's scores of 128 + 64 run through a call of 256 with zeros
on the rest, its values of 128 likewise: 75 says so, and 100 says the
kernels took the stated width. It is what ``kernel.attn_roofline`` loses
to the padding, by name: the family module counts the stated widths, the
kernels' seconds hold the called ones. Nothing to read where the
configuration's attention states one width for scores and values, or the
program has no such counter."""

import json
import os

LAYER = "kernels"
UNIT = "%"
MOVES = "tokens_per_s"

CONFIGS = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "configs"
)


def _widths_differ(model) -> bool:
    """The attention states a score width apart from its value width."""
    scores = (model.get("qk_nope_dim") or 0) + (model.get("qk_rope_dim") or 0)
    return bool(scores) and scores != (model.get("v_head_dim") or 0)


def CELLS(cell):
    """The cells whose configuration's attention states a score width
    apart from its value width. A cell of another data directory (a
    rehearsal's) is left to ``read``."""
    try:
        with open(os.path.join(CONFIGS, f"{cell.get('config')}.json")) as f:
            model = json.load(f)["model"]
    except (OSError, ValueError, KeyError, TypeError):
        return True
    return _widths_differ(model)


def read(run):
    if not _widths_differ(run.config.get("model") or {}):
        return None
    pipeline = run.window.get("pipeline") or {}
    called = pipeline.get("attn_score_lanes")
    if not called or "attn_score_lanes_used" not in pipeline:
        return None
    return 100.0 * pipeline["attn_score_lanes_used"] / called
