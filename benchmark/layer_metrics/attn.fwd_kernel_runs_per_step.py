"""How often a forward attention kernel runs in one train step: the count
of the traced stretch's device events (``xplane.step_stretch``) whose own
name holds ``flash_attn`` and ``fwd`` (``%flash_attn_fwd.N``,
``%flash_attn_window_fwd.N``, ``%flash_attn_fused_fwd.N``), over the
stretch's whole steps. A configuration that recomputes every layer in the
backward pass (``remat``) has one such run a layer for the forward pass and
had a second for the backward pass, which made ``o`` and the logsumexp
again before the backward kernel could read them; a recomputed layer that
keeps them (``models/transformer.recomputed``) runs the kernel once.
So the number is the attention layers where the outputs are kept and twice
that where they are made again: lower is better, the floor is the layers.
Read from the device trace alone, so it reads the same way on a program
that knows nothing of what is kept. Nothing to read where the configuration
does not recompute or the trace holds no such kernel."""

import json
import os

LAYER = "kernels"
UNIT = "runs"
MOVES = "tokens_per_s"

# as kernel.attn_roofline recognises the attention kernels, and of those
# the forward ones
TARGET = "tpu_custom_call"
NAME = "flash_attn"
FORWARD = "fwd"

CONFIGS = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "configs"
)


def _recomputes(model) -> bool:
    return bool(model.get("remat"))


def CELLS(cell):
    """The cells whose configuration's model recomputes its layers
    (``remat``). A cell of another data directory (a rehearsal's) is left
    to ``read``."""
    try:
        with open(os.path.join(CONFIGS, f"{cell.get('config')}.json")) as f:
            model = json.load(f)["model"]
    except (OSError, ValueError, KeyError, TypeError):
        return True
    return _recomputes(model)


def read(run):
    import xplane

    if not _recomputes(run.config.get("model") or {}):
        return None
    if not run.trace or not run.trace.get("devices"):
        return None
    device = run.trace["devices"][0]
    steps = device["steps"]
    named = [
        r for r in device["ops"]
        if NAME in r["name"].lower() and FORWARD in r["name"].lower()
    ]
    found = xplane.kernel_seconds({"ops": named}, (TARGET,))
    if not found["count"] or not steps:
        return None
    print(json.dumps({
        "forward_attention_kernels": found, "steps_traced": steps,
    }), flush=True)
    return found["count"] / steps
