"""Of the exits of a looped model (``cfg.ut_steps`` > 1: the one head read
after every pass), the share whose gradients the head's own forward rule
makes beside their losses (``models/transformer.exits_nll``: softmax minus
one-hot in the activation dtype from the one float32 logits array, read by
both gradient products), in percent: ``PipelineStats.ut_exit_fused_heads``
over ``ut_exit_heads``, both as the program traced last
(``common/trace_counts``). 100 (4 of 4 in the Ouro cell) says no exit's
logits are made a second time in the backward pass and no float32
[B, T, V] cotangent is written; 0 would say the step was built from the
exits' plain form. A record of which program ran, beside the device's
``step.device_ms``. Nothing to read where the configuration runs its
layers once, or the program has no such counter (the parent of the PR
that brought it)."""

import json
import os

LAYER = "step program"
UNIT = "%"
MOVES = "tokens_per_s"

CONFIGS = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "configs"
)


def _loops(model) -> bool:
    return (model.get("ut_steps") or 1) > 1


def CELLS(cell):
    """The cells whose configuration's model loops (``ut_steps`` > 1), as
    ``ut.layer_passes_per_step`` has it."""
    try:
        with open(os.path.join(CONFIGS, f"{cell.get('config')}.json")) as f:
            model = json.load(f)["model"]
    except (OSError, ValueError, KeyError, TypeError):
        return True
    return _loops(model)


def read(run):
    if not _loops(run.config.get("model") or {}):
        return None
    pipeline = run.window.get("pipeline") or {}
    exits = pipeline.get("ut_exit_heads")
    if not exits or "ut_exit_fused_heads" not in pipeline:
        return None
    return 100.0 * pipeline["ut_exit_fused_heads"] / exits
