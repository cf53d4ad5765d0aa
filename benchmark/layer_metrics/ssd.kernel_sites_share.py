"""Share of the Mamba-2 chunked scans of the train step (``ssd_chunked``
with the ``D`` skip after it, forward and backward, one a Mamba-2 mixer)
that were traced into the ``ssd_scan_*`` kernels and not into XLA operations
over ``[Q, Q]`` float32 decay squares and head-major copies of the tokens
(``PipelineStats.ssd_kernel_sites`` over ``ssd_sites``: the trainer sets
both from what the train step's build traced, both counted at one place).
Which way a site goes is read from its operands (``ops/ssd_kernels.fits``),
so 100 says the configuration's widths fit the kernels and anything less
names how many mixers still pay for the squares in HBM. Nothing to read
where the configuration has no such layer or the program no such counter."""

import json
import os

LAYER = "kernels"
UNIT = "%"
MOVES = "tokens_per_s"

CONFIGS = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "configs"
)


def _has_the_kind(model) -> bool:
    return "M" in (model.get("layer_pattern") or "")


def CELLS(cell):
    """The cells whose configuration names a Mamba-2 layer in its
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
    pipeline = run.window.get("pipeline") or {}
    sites = pipeline.get("ssd_sites")
    if not sites or "ssd_kernel_sites" not in pipeline:
        return None
    return 100.0 * pipeline["ssd_kernel_sites"] / sites
