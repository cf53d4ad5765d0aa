"""Share, in percent, of the optimizer's int8 moment elements whose
leaf's step the train step that was built runs as the one-pass kernel
(``PipelineStats.opt_q8_kernel_elems``, counted where the leaf's call of
``_q8_adam_step`` is traced: the Pallas call ``q8_adam_step``, which reads
gradient, parameter, codes and scales once where they lie and writes the
parameter and the moments in place), over all the int8 moments' elements (``opt_q8_tiles_elems`` +
``opt_q8_blocks_elems``, which the trainer sets from the state it built).
What the built program does, where ``opt.q8_tiles_share`` says what the
state would allow: the two agree where every whole-tile leaf took the
kernel. A program without the counter, or an optimizer with fp32
moments, gives nothing."""

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
    if "opt_q8_kernel_elems" not in closed:
        return None
    tiles = closed.get("opt_q8_tiles_elems") or 0
    blocks = closed.get("opt_q8_blocks_elems") or 0
    if tiles + blocks <= 0:
        return None
    return 100.0 * (closed["opt_q8_kernel_elems"] or 0) / (tiles + blocks)
