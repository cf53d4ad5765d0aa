"""Sequential chunk-state steps one training step runs through its Gated
DeltaNet mixers, forward and backward
(``PipelineStats.gdn_chunk_steps``: the trainer sets it, with
``gdn_sites``, from what the train step's build traced; sites x ``seq /
gdn_chunk`` x 2). The delta rule's transition is a matrix, so the pass
over chunk states is a loop and not one matmul: this is the step's serial
depth, the part no wider matmul shortens. A change of the chunk size or of
the pass's form moves it and ``tokens_per_s`` together. Nothing to read
where the configuration has no such layer or the program no such
counter."""

import json
import os

LAYER = "step program"
UNIT = "steps"
MOVES = "tokens_per_s"

CONFIGS = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "configs"
)


def _has_the_kind(model) -> bool:
    return "G" in (model.get("layer_pattern") or "")


def CELLS(cell):
    """The cells whose configuration names a Gated DeltaNet layer in its
    ``layer_pattern``. A cell of another data directory (a rehearsal's)
    is left to ``read``."""
    try:
        with open(os.path.join(CONFIGS, f"{cell.get('config')}.json")) as f:
            model = json.load(f)["model"]
    except (OSError, ValueError, KeyError, TypeError):
        return True
    return _has_the_kind(model)


def read(run):
    if not _has_the_kind(run.config.get("model") or {}):
        return None
    steps = (run.window.get("pipeline") or {}).get("gdn_chunk_steps")
    return float(steps) if steps else None
