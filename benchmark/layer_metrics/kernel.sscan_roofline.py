"""The selective-scan kernels' share of their roofline: the least time the
chip could take for the scan layers' recurrences of the traced stretch's
whole steps (``xplane.step_stretch``), which is the larger of operations
over the bf16 peak and bytes over HBM bandwidth, over the summed device
time of the ``sscan`` kernels' events, forward and backward. The operations
and bytes of one step are the ``selective_scan`` of ``step_work`` in the
configuration's family module (``run.hook``: the recurrence's least
element-wise arithmetic, and x, dt, B, C in and y out with their gradients;
``flops_phi4flash.py``). By the published peaks the bound that holds is HBM
(22 bytes a token and channel against 21 x 16 operations), and the peak the
operations are set against is the MXU's, which the recurrence cannot use:
its work is element-wise, one ``exp`` and a dozen multiply-adds for every
(step, channel, state) on the vector unit. **So a low share reads "bound by
the vector unit"**, not "waiting for memory": the share rises with fewer
vector operations a step (and with a forward that is not run a second time
where a layer is recomputed: the seconds then hold it twice and the work
counts it once, as ``kernel.attn_window_roofline`` says of its own).
Nothing to read where the family module counts no such work or the trace
holds no such kernel (a program that runs the scan plainly, or none)."""

import json
import os

LAYER = "kernels"
UNIT = "%"
MOVES = "tokens_per_s"

# as kernel.attn_roofline recognises the attention kernels: a Pallas kernel
# is a custom-call whose target is tpu_custom_call, named by the kernel's
# own ``name=`` (``%sscan_fwd.N``, ``%sscan_bwd.N``)
TARGET = "tpu_custom_call"
NAME = "sscan"

# a share of a roofline cannot pass 100 %: above it the family module counts
# work the program does not run, and run.py refuses the run with the numbers
CEILING = 100.0

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
    import flops
    import xplane

    if not run.trace or not run.trace.get("devices") or not run.peak:
        return None
    work = run.hook.step_work(
        run.config["model"], run.cell["batch"], run.cell["seq"]
    ).get("selective_scan")
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
        "selective_scan_kernels": found, "roofline": roof,
        "steps_traced": steps,
    }), flush=True)
    return 100.0 * roof["seconds"] / found["seconds"]
