"""Share of the Gated DeltaNet mixers of the train step whose chunk-local
work (a chunk's [C, C] float32 squares: decays, masked scores, the unit
triangle's inverse, forward and backward) was traced into the
``gdn_chunk_*`` kernels and not into XLA operations over arrays of those
squares (``PipelineStats.gdn_kernel_sites`` over ``gdn_sites``: the
trainer sets both from what the train step's build traced). Which way a
site goes is read from its shapes (``ops/gated_delta_kernels.fits``), so
100 says the configuration's widths fit the kernels and anything less
names how many mixers still pay for the squares in HBM. Nothing to read
where the configuration has no such layer or the program no such
counter."""

import json
import os

LAYER = "kernels"
UNIT = "%"
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
    pipeline = run.window.get("pipeline") or {}
    sites = pipeline.get("gdn_sites")
    if not sites or "gdn_kernel_sites" not in pipeline:
        return None
    return 100.0 * pipeline["gdn_kernel_sites"] / sites
