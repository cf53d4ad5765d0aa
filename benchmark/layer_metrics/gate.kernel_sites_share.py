"""Share of the gated norms after a scan of the train step (``weight *
RMSNorm(o) * gate(z)`` after a Mamba-2 or Gated DeltaNet mixer's scan,
forward and backward with the weight's gradient reduce) that were traced
into the ``gated_norm_*`` kernels and not into XLA operations over float32
token-shaped arrays (``PipelineStats.gate_kernel_sites`` over ``gate_sites``:
the trainer sets both from what the train step's build traced, both counted
at one place). Which way a site goes is read from its input
(``ops/gated_norm_kernels.fits``), so 100 says the configuration's widths
fit the kernels and anything less names how many mixers still pay for the
float32 stretch in HBM. Nothing to read where the configuration has no such
layer or the program no such counter."""

import json
import os

LAYER = "kernels"
UNIT = "%"
MOVES = "tokens_per_s"

CONFIGS = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "configs"
)


def _has_the_kind(model) -> bool:
    pattern = model.get("layer_pattern") or ""
    return "M" in pattern or "G" in pattern


def CELLS(cell):
    """The cells whose configuration names a Mamba-2 or a Gated DeltaNet
    layer in its ``layer_pattern``. A cell of another data directory (a
    rehearsal's) is left to ``read``."""
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
    sites = pipeline.get("gate_sites")
    if not sites or "gate_kernel_sites" not in pipeline:
        return None
    return 100.0 * pipeline["gate_kernel_sites"] / sites
