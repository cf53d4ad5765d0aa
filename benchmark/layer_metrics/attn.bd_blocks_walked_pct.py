"""Blocks a head walks at the attention kernels under the block-diffusion
rule, in percent of the blocks of the whole ``2L x 2L`` grid of a doubled
row at the block size they run (``PipelineStats.attn_bd_blocks_walked``
over ``attn_bd_blocks_square``: the trainer sets both from what the train
step's build traced, ``ops/flash_attention._count_bd_site``, each summed
over the kernels, forward and backward). Where the ``flash_attn_bd_*``
kernels walk only the blocks that hold a visible pair the share is theirs:
SDAR's blocks of 4 at L = 8192 read 31.25 in blocks of 1024 (80 of 256: 36
clean x clean, 36 noised x clean, the 8 noised x noised blocks on the
diagonal); 100 says the rule ran as a mask over everything (the rectangular
grid), and 25.0 is the visible pairs' own share of the square (``L^2 + L
B`` of ``4 L^2``). Lower is better: it is what ``kernel.attn_bd_roofline``
is paid for. Nothing to read where the configuration's model states no
such objective or the program has no such counter (the jnp path counts no
blocks)."""

import json
import os

LAYER = "kernels"
UNIT = "%"
MOVES = "tokens_per_s"

CONFIGS = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "configs"
)


def _states_the_objective(model) -> bool:
    return model.get("objective") == "block_diffusion"


def CELLS(cell):
    """The cells whose configuration's model states the objective. A cell
    of another data directory (a rehearsal's) is left to ``read``."""
    try:
        with open(os.path.join(CONFIGS, f"{cell.get('config')}.json")) as f:
            model = json.load(f)["model"]
    except (OSError, ValueError, KeyError, TypeError):
        return True
    return _states_the_objective(model)


def read(run):
    if not _states_the_objective(run.config.get("model") or {}):
        return None
    pipeline = run.window.get("pipeline") or {}
    square = pipeline.get("attn_bd_blocks_square")
    if not square or "attn_bd_blocks_walked" not in pipeline:
        return None
    return 100.0 * pipeline["attn_bd_blocks_walked"] / square
