"""How spread a token's probability of stopping is over the passes of a
looped model (``cfg.ut_steps`` > 1): the entropy of the stopping
distribution that the exit gate gives, in nats, mean over tokens and over
the steps the trainer reported inside the window: the rise of
``PipelineStats.ut_entropy_sum`` over the rise of ``ut_reports``, as
``moe.max_expert_load`` reads the routers'. Between 0 (the gate has
collapsed onto one pass) and ``ln ut_steps`` (even: 1.386 at four passes);
the loss's entropy term is there to keep it up, and a change to the exits'
arithmetic must keep it. Nothing to read where the configuration runs its
layers once, the program has no such counter, or no report fell inside the
window."""

import json
import os

LAYER = "step program"
UNIT = "nats"
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
    opened = run.window.get("pipeline_open") or {}
    closed = run.window.get("pipeline") or {}

    def rise(field):
        return closed.get(field, 0) - opened.get(field, 0)

    reports = rise("ut_reports")
    if not reports:
        return None
    return rise("ut_entropy_sum") / reports
