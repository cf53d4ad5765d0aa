"""How many layer bodies one forward pass of the train step runs in a
looped model (``cfg.ut_steps`` > 1: the whole stack applied several times
over the same weights), in ONE-MIXER LAYERS, the entries of the
``layer_pattern``, summed over the passes: ``PipelineStats.ut_layer_passes``
as the program traced last (``common/trace_counts``).
12 one-mixer layers (6 blocks of attention then feed-forward) x 4 passes =
48 in the Ouro cell. A record that the looped program is the configured
one, beside the device's ``attn.fwd_kernel_runs_per_step`` (attention
layers x passes); not expected to move. Nothing to read where the
configuration runs its layers once, or the program has no such counter."""

import json
import os

LAYER = "step program"
UNIT = "passes"
MOVES = "tokens_per_s"

CONFIGS = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "configs"
)


def _loops(model) -> bool:
    return (model.get("ut_steps") or 1) > 1


def CELLS(cell):
    """The cells whose configuration's model loops (``ut_steps`` > 1). A
    cell of another data directory (a rehearsal's) is left to ``read``."""
    try:
        with open(os.path.join(CONFIGS, f"{cell.get('config')}.json")) as f:
            model = json.load(f)["model"]
    except (OSError, ValueError, KeyError, TypeError):
        return True
    return _loops(model)


def read(run):
    if not _loops(run.config.get("model") or {}):
        return None
    passes = (run.window.get("pipeline") or {}).get("ut_layer_passes")
    return float(passes) if passes else None
