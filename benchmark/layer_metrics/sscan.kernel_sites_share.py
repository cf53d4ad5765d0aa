"""Share of the selective scans of the train step (one a Mamba-1 mixer,
forward and backward) that were traced into the ``sscan_*`` kernels and not
into a ``lax.scan`` over the steps (``PipelineStats.sscan_kernel_sites``
over ``sscan_sites``: the trainer sets both from what the train step's
build traced, both counted at one place, ``ops/selective_scan.
selective_scan``). Which way a site goes is read from its input
(``ops/selective_scan.fits``), so 100 says the configuration's widths fit
the kernels and anything less names how many mixers walk their steps as
XLA loops with the state in HBM. Nothing to read where the configuration
has no such layer or the program no such counter."""

import json
import os

LAYER = "kernels"
UNIT = "%"
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
    pipeline = run.window.get("pipeline") or {}
    sites = pipeline.get("sscan_sites")
    if not sites or "sscan_kernel_sites" not in pipeline:
        return None
    return 100.0 * pipeline["sscan_kernel_sites"] / sites
