"""How often a differential attention layer computes a head pair's scores:
``PipelineStats.attn_diff_score_calls`` over ``attn_diff_pairs`` (the
trainer sets both from what the train step's build traced,
``models/transformer._diff_attention``, each summed over the sites: the
pairs, and the attention calls' score heads over two). A pair has two score
maps and one value twice a head wide. 1 says each map is computed once: one
attention call a layer whose query and key heads are the pairs' halves and
whose values are the pairs' (q and k padded to the values' width, which
``kernel.attn_roofline`` pays for in its seconds); 2 says the call runs at
a head's width and each map is computed once a value half. Lower is
better. Nothing to read where the configuration's attention is not
differential or the program has no such counter."""

import json
import os

LAYER = "kernels"
UNIT = "passes"
MOVES = "tokens_per_s"

CONFIGS = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "configs"
)


def _differential(model) -> bool:
    return model.get("attn_kind") == "diff"


def CELLS(cell):
    """The cells whose configuration's attention is differential. A cell
    of another data directory (a rehearsal's) is left to ``read``."""
    try:
        with open(os.path.join(CONFIGS, f"{cell.get('config')}.json")) as f:
            model = json.load(f)["model"]
    except (OSError, ValueError, KeyError, TypeError):
        return True
    return _differential(model)


def read(run):
    if not _differential(run.config.get("model") or {}):
        return None
    pipeline = run.window.get("pipeline") or {}
    pairs = pipeline.get("attn_diff_pairs")
    if not pairs or "attn_diff_score_calls" not in pipeline:
        return None
    return pipeline["attn_diff_score_calls"] / pairs
