"""How often a forward kernel of the gated delta rule runs in one train
step: the count of the traced stretch's device events
(``xplane.step_stretch``) whose own name holds ``gdn_`` and ``_fwd``
(``%gdn_chunk_wy_fwd.N``, ``%gdn_chunk_read_fwd.N``, and for a decay that
is a vector over the key's channels ``%gdn_channel_wy_fwd.N``,
``%gdn_channel_read_fwd.N``), over the stretch's whole steps. A delta-rule
mixer runs two of them in its forward pass, one before the serial pass over
the chunk states and one after it. A configuration that recomputes every
layer in the backward pass (``remat``) ran both a second time there, and
the serial pass between them, to make again what the backward rules read;
a recomputed layer that keeps what the pass read and returned
(``models/transformer.recomputed``) runs them once. So the number is twice
the delta-rule layers where nothing is made again and four times where it
is: lower is better, the floor is twice the layers. Read from the device
trace alone, so it reads the same way on a program that knows nothing of
what is kept. Nothing to read where the configuration has no such layer or
the trace holds no such kernel."""

import json
import os

LAYER = "kernels"
UNIT = "runs"
MOVES = "tokens_per_s"

TARGET = "tpu_custom_call"
NAME = "gdn_"
FORWARD = "_fwd"

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
    import xplane

    if not _has_the_kind(run.config.get("model") or {}):
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
        "forward_delta_rule_kernels": found, "steps_traced": steps,
    }), flush=True)
    return found["count"] / steps
