"""The window attention kernels' share of their roofline: the least time
the chip could take for the window layers' attention of the traced
stretch's whole steps (``xplane.step_stretch``), which is the larger of
operations over the bf16 peak and bytes over HBM bandwidth, over the summed
device time of the band kernels' events, forward and backward. The
operations and bytes of one step are the ``attention_window`` of
``step_work`` in the configuration's family module (``run.hook``: the
window layers alone, over the pairs a window lets a query see); the band
kernels are the attention kernels whose name holds ``flash_attn_window``
(``kernel.attn_roofline`` sums them with every other attention kernel).
The half-masked blocks on the band's two edges are the kernels' cost and
not the algorithm's, so they show here and in
``attn.window_blocks_walked_pct``. Under ``remat`` the seconds hold the
forward kernels twice (each layer's forward runs again in the backward
pass) and the work counts them once (``step_work``: what recomputation
runs again is the program's choice): the kernels then do 4/3 of the
counted operations, the forward a third of forward + backward, and a
share of 36 says they run at 48 % of the roofline; so does
``kernel.attn_roofline`` in every recomputing cell (the Ling cell's too).
Nothing to read where the family module counts no such work or the trace
holds no such kernel (a program that runs the window as a mask, or
none)."""

import json
import os

LAYER = "kernels"
UNIT = "%"
MOVES = "tokens_per_s"

# as kernel.attn_roofline recognises the attention kernels, by the part of
# the name the band's kernels alone carry
TARGET = "tpu_custom_call"
NAME = "flash_attn_window"

# a share of a roofline cannot pass 100 %: above it the family module counts
# work the program does not run, and run.py refuses the run with the numbers
CEILING = 100.0

CONFIGS = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "configs"
)


def CELLS(cell):
    """The cells whose configuration's model states a window (as
    ``attn.window_blocks_walked_pct``). A cell of another data directory
    (a rehearsal's) is left to ``read``."""
    try:
        with open(os.path.join(CONFIGS, f"{cell.get('config')}.json")) as f:
            model = json.load(f)["model"]
    except (OSError, ValueError, KeyError, TypeError):
        return True
    return bool(model.get("attn_window"))


def read(run):
    import flops
    import xplane

    if not run.trace or not run.trace.get("devices") or not run.peak:
        return None
    work = run.hook.step_work(
        run.config["model"], run.cell["batch"], run.cell["seq"]
    ).get("attention_window")
    if work is None:
        return None
    device = run.trace["devices"][0]
    steps = device["steps"]
    named = [r for r in device["ops"] if NAME in r["name"].lower()]
    found = xplane.kernel_seconds({"ops": named}, (TARGET,))
    if not found["seconds"]:
        return None
    work = {k: v * steps for k, v in work.items()}
    roof = flops.roofline_seconds(work, run.peak)
    print(json.dumps({
        "window_attention_kernels": found, "roofline": roof,
        "steps_traced": steps,
    }), flush=True)
    return 100.0 * roof["seconds"] / found["seconds"]
