"""Share of the Gated DeltaNet mixers of the train step whose serial pass
over the chunk states (``V' = U - W S``, ``S <- a S + K^T (delta V')`` a
chunk, in order, and its reversal with the cotangents of ``U``, ``W``,
``K`` and the decays) was traced into the ``delta_state_pass`` kernels,
which keep a head's float32 state in VMEM across a layer's chunks, and not
into XLA's loop over the chunks, which reads and writes the state in HBM
every step and leaves the reversal's cotangents to einsums over all chunks
after it (``PipelineStats.gdn_pass_kernel_sites`` over ``gdn_sites``: the
trainer sets both from what the train step's build traced, both counted at
one place). Which way a site goes is read from its shapes
(``ops/gated_delta_kernels.fits``, the rule of the chunk-local work around
the pass), so 100 says the configuration's widths fit the kernels and
anything less names how many mixers still walk their chunk states in HBM.
The steps walked are the same either way (``gdn.serial_chunk_steps``).
Nothing to read where the configuration has no such layer or the program
no such counter."""

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
    if not sites or "gdn_pass_kernel_sites" not in pipeline:
        return None
    return 100.0 * pipeline["gdn_pass_kernel_sites"] / sites
