"""Share, in percent, of the optimizer's int8 moment elements whose
128-element blocks lie in their leaf's own tile order
(``PipelineStats.opt_q8_tiles_elems``), so that the update reads the
gradient and the parameter where they lie; the rest
(``opt_q8_blocks_elems``) lie in ``[nblocks, 128]`` rows and cost two
relayouts of the leaf a step. The trainer sets both once, from the
optimizer state it built. A program without the counters, or an
optimizer with fp32 moments, gives nothing."""

import json
import os

LAYER = "step program"
UNIT = "%"
MOVES = "tokens_per_s"

CONFIGS = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "configs"
)


def CELLS(cell):
    """The cells whose configuration trains with int8 moments. A cell
    of another data directory (a rehearsal's) is left to ``read``."""
    try:
        with open(os.path.join(CONFIGS, f"{cell.get('config')}.json")) as f:
            optimizer = json.load(f)["optimizer"]["name"]
    except (OSError, ValueError, KeyError, TypeError):
        return True
    return optimizer.startswith("adamw_8bit")


def read(run):
    closed = run.window.get("pipeline") or {}
    tiles = closed.get("opt_q8_tiles_elems") or 0
    blocks = closed.get("opt_q8_blocks_elems") or 0
    if tiles + blocks <= 0:
        return None
    return 100.0 * tiles / (tiles + blocks)
