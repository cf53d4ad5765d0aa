"""The Pallas attention kernels' share of their roofline: the least time
the chip could take for the attention of the traced stretch's whole steps
(``xplane.step_stretch``), which is the larger of operations over the bf16
peak and bytes over HBM bandwidth, over the summed device time of the
kernels' events, forward and backward. The operations and bytes of one
step are the ``attention`` of ``step_work`` in the configuration's family
module (``run.hook``: which layers run attention, with how many heads of
what width, is that module's knowledge and not this reader's); a model
that runs none has nothing to read. Which bound holds is printed on an
earlier line, with the seconds of the other ``tpu_custom_call`` events."""

import json

LAYER = "kernels"
UNIT = "%"
MOVES = "tokens_per_s"

# how the kernels' events are recognised in the trace: a Pallas kernel is a
# custom-call whose target is tpu_custom_call, named by the kernel's own
# ``name=`` (``%flash_attn_fused_fwd.N``, ``%flash_attn_bwd_dkv.N``, ...).
# The target alone is not enough: XLA lowers ``lax.ragged_dot`` to
# tpu_custom_calls of its own. The name is matched in the event's name
# alone: a fusion that takes a kernel's result holds its name in its HLO.
TARGET = "tpu_custom_call"
NAME = "flash_attn"

# a share of a roofline cannot pass 100 %: above it the family module counts
# work the program does not run, and run.py refuses the run with the numbers
CEILING = 100.0


def CELLS(cell):
    return True


def read(run):
    import flops
    import xplane

    if not run.trace or not run.trace.get("devices") or not run.peak:
        return None
    work = run.hook.step_work(
        run.config["model"], run.cell["batch"], run.cell["seq"]
    ).get("attention")
    if work is None:
        return None
    device = run.trace["devices"][0]
    steps = device["steps"]
    calls = xplane.kernel_seconds(device, (TARGET,))
    named = [r for r in device["ops"] if NAME in r["name"].lower()]
    found = xplane.kernel_seconds({"ops": named}, (TARGET,))
    if not found["seconds"]:
        return None
    work = {k: v * steps for k, v in work.items()}
    roof = flops.roofline_seconds(work, run.peak)
    print(json.dumps({
        "attention_kernels": found, "roofline": roof,
        "steps_traced": steps,
        "other_custom_call_seconds": calls["seconds"] - found["seconds"],
    }), flush=True)
    return 100.0 * roof["seconds"] / found["seconds"]
