"""The block-diffusion attention kernels' share of their roofline: the
least time the chip could take for the attention of the traced stretch's
whole steps (``xplane.step_stretch``) under the block-diffusion rule, which
is the larger of operations over the bf16 peak and bytes over HBM
bandwidth, over the summed device time of the walk's kernels' events,
forward and backward. The operations and bytes of one step are the
``attention_block_diffusion`` of ``step_work`` in the configuration's
family module (``run.hook``: every attention layer over the ``L^2 + L B``
pairs a head that the rule lets a doubled row's queries see); the walk's
kernels are the attention kernels whose name holds ``flash_attn_bd``
(``kernel.attn_roofline`` sums them with every other attention kernel).
The half-masked blocks on the two clean diagonals and the nearly empty
noised x noised blocks are the kernels' cost and not the algorithm's, so
they show here and in ``attn.bd_blocks_walked_pct``. Under ``remat`` the
work counts a forward kernel once and the seconds hold what the program
runs (a recomputed layer that keeps the kernel's outputs runs it once: see
``attn.fwd_kernel_runs_per_step``). Nothing to read where the family module
counts no such work or the trace holds no such kernel (a program that runs
the rule as a mask over the rectangular grid, or knows none)."""

import json
import os

LAYER = "kernels"
UNIT = "%"
MOVES = "tokens_per_s"

# as kernel.attn_roofline recognises the attention kernels, by the part of
# the name the walk's kernels alone carry
TARGET = "tpu_custom_call"
NAME = "flash_attn_bd"

# a share of a roofline cannot pass 100 %: above it the family module counts
# work the program does not run, and run.py refuses the run with the numbers
CEILING = 100.0

CONFIGS = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "configs"
)


def _states_the_objective(model) -> bool:
    return model.get("objective") == "block_diffusion"


def CELLS(cell):
    """The cells whose configuration's model states the objective (as
    ``attn.bd_blocks_walked_pct``). A cell of another data directory (a
    rehearsal's) is left to ``read``."""
    try:
        with open(os.path.join(CONFIGS, f"{cell.get('config')}.json")) as f:
            model = json.load(f)["model"]
    except (OSError, ValueError, KeyError, TypeError):
        return True
    return _states_the_objective(model)


def read(run):
    import flops
    import xplane

    if not run.trace or not run.trace.get("devices") or not run.peak:
        return None
    work = run.hook.step_work(
        run.config["model"], run.cell["batch"], run.cell["seq"]
    ).get("attention_block_diffusion")
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
        "block_diffusion_attention_kernels": found, "roofline": roof,
        "steps_traced": steps,
    }), flush=True)
    return 100.0 * roof["seconds"] / found["seconds"]
