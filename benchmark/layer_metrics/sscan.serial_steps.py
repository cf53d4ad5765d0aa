"""Steps one training step's selective scans walk in order, forward and
backward (``PipelineStats.sscan_serial_steps``: the trainer sets it, with
``sscan_sites``, from what the train step's build traced; a forward pass
walks the row's T steps, a backward pass 2 T: a block's states made again
from the state that entered it, then walked from the end). The decay of a
Mamba-1 state is a matrix times a step of its own a channel, so no matmul
over a chunk stands for its steps: this is the step's serial depth in that
layer kind, the part no wider operation shortens. A layer the backward
pass makes again (``remat``) walks its forward twice, and shows here.
Nothing to read where the configuration has no such layer or the program
no such counter."""

import json
import os

LAYER = "step program"
UNIT = "steps"
MOVES = "tokens_per_s"

CONFIGS = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "configs"
)


def _has_the_kind(model) -> bool:
    return "S" in (model.get("layer_pattern") or "")


def CELLS(cell):
    """The cells whose configuration names a selective-scan layer in its
    ``layer_pattern``. A cell of another data directory (a rehearsal's) is
    left to ``read``."""
    try:
        with open(os.path.join(CONFIGS, f"{cell.get('config')}.json")) as f:
            model = json.load(f)["model"]
    except (OSError, ValueError, KeyError, TypeError):
        return True
    return _has_the_kind(model)


def read(run):
    if not _has_the_kind(run.config.get("model") or {}):
        return None
    steps = (run.window.get("pipeline") or {}).get("sscan_serial_steps")
    return float(steps) if steps else None
