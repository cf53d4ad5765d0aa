"""Share, in percent, of the lanes of a key and a value head in the blocks
of the delta rule's chunk kernels that the model states
(``PipelineStats.gdn_head_lanes_used`` over ``gdn_head_lanes``: the trainer
sets both from what the train step's build traced,
``ops/gated_delta.gated_delta_chunked``, each summed over the sites whose
chunk-local work is in the kernels). A block's minor dimension is whole
128-lane tiles in VMEM whatever the array's width, so a head of 96 / 192
is held as 128 / 256 with the rest padding: 75 says so, and 100 says the
kernels took the stated widths in whole tiles (heads grouped, or widths
that are tiles). It is what ``kernel.gdn_roofline`` loses to the padding,
by name: the family module counts the stated widths, the kernels' seconds
hold the held ones. Nothing to read where the configuration's delta-rule
heads are whole tiles, or the program has no such counter."""

import json
import os

LAYER = "kernels"
UNIT = "%"
MOVES = "tokens_per_s"

LANES = 128
CONFIGS = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "configs"
)


def _no_whole_tiles(model) -> bool:
    """A ``G`` layer whose key or value head is no multiple of 128."""
    if "G" not in (model.get("layer_pattern") or ""):
        return False
    widths = (model.get("gdn_key_dim") or 0, model.get("gdn_value_dim") or 0)
    return any(w % LANES for w in widths)


def CELLS(cell):
    """The cells whose configuration names a Gated DeltaNet layer with a
    head that is no whole lane tiles. A cell of another data directory (a
    rehearsal's) is left to ``read``."""
    try:
        with open(os.path.join(CONFIGS, f"{cell.get('config')}.json")) as f:
            model = json.load(f)["model"]
    except (OSError, ValueError, KeyError, TypeError):
        return True
    return _no_whole_tiles(model)


def read(run):
    if not _no_whole_tiles(run.config.get("model") or {}):
        return None
    pipeline = run.window.get("pipeline") or {}
    called = pipeline.get("gdn_head_lanes")
    if not called or "gdn_head_lanes_used" not in pipeline:
        return None
    return 100.0 * pipeline["gdn_head_lanes_used"] / called
