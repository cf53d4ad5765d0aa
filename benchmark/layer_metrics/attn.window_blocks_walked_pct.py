"""Blocks a head walks at the attention kernels that were given a window,
in percent of the blocks at or under the diagonal at those kernels, at the
block size they run (``PipelineStats.attn_window_blocks_walked`` over
``attn_window_blocks_causal``: the trainer sets both from what the train
step's build traced, ``ops/flash_attention._count_window_site``, each
summed over the kernels, forward and backward). Where the streaming
kernels walk the band ``i - wb <= j <= i`` a window can see, the share is
the band's: Trinity-Mini's window of 2048 at T = 16384 reads 33.1 in
blocks of 1024 (45 of 136) and 28.4 in blocks of 512 (150 of 528); 100
says the window was only a mask over everything a causal call walks.
Lower is better: it is what ``kernel.attn_window_roofline`` is paid for
(the window's own pairs are 23.4 % of the causal ones there; the rest of
the share is the half-masked blocks on the band's two edges). Nothing to
read where the configuration's model states no window or the program has
no such counter."""

import json
import os

LAYER = "kernels"
UNIT = "%"
MOVES = "tokens_per_s"

CONFIGS = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "configs"
)


def _states_a_window(model) -> bool:
    return bool(model.get("attn_window"))


def CELLS(cell):
    """The cells whose configuration's model states a window. A cell of
    another data directory (a rehearsal's) is left to ``read``."""
    try:
        with open(os.path.join(CONFIGS, f"{cell.get('config')}.json")) as f:
            model = json.load(f)["model"]
    except (OSError, ValueError, KeyError, TypeError):
        return True
    return _states_a_window(model)


def read(run):
    if not _states_a_window(run.config.get("model") or {}):
        return None
    pipeline = run.window.get("pipeline") or {}
    under = pipeline.get("attn_window_blocks_causal")
    if not under or "attn_window_blocks_walked" not in pipeline:
        return None
    return 100.0 * pipeline["attn_window_blocks_walked"] / under
