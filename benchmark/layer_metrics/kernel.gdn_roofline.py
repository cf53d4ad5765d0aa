"""The delta-rule kernels' share of their roofline: the least time the chip
could take for the chunk-local work of the Gated DeltaNet layers of the
traced stretch's whole steps (``xplane.step_stretch``), which is the larger
of operations over the bf16 peak and bytes over HBM bandwidth, over the
summed device time of the ``gdn_chunk`` kernels' events, forward and
backward (``gdn_chunk_wy_*`` before the serial pass, ``gdn_chunk_read_*``
after it). The operations and bytes of one step are the ``gated_delta`` of
``step_work`` in the configuration's family module (``run.hook``: the
causal halves of the chunk's squares, the triangle's inverse by
substitution, its products and the read-out AT THE STATED HEAD WIDTHS, and
what the four kernels must read and write once; ``flops_olmo_hybrid.py``).
**The serial pass between the two stretches is XLA's and is in neither
side.** By the published peaks the bound that holds is HBM. What the share
loses, by design: lanes a kernel's blocks pad a head to (96 / 192 held as
128 / 256: ``gdn.head_lanes_used_pct``), the whole squares where the least
is their causal half, an MXU tile half filled by a ``[64, 64]`` square, the
inverse as ten ``highest`` products where substitution is the least, and a
forward kernel that a recomputed layer would run again. Nothing to read
where the family module counts no such work or the trace holds no such
kernel (a program that runs the chunk-local work plainly, or none)."""

import json
import os

LAYER = "kernels"
UNIT = "%"
MOVES = "tokens_per_s"

# as kernel.sscan_roofline recognises its kernels: a Pallas kernel is a
# custom-call whose target is tpu_custom_call, named by the kernel's own
# ``name=`` (``%gdn_chunk_wy_fwd.N``, ``%gdn_chunk_read_bwd.N``)
TARGET = "tpu_custom_call"
NAME = "gdn_chunk"

# a share of a roofline cannot pass 100 %: above it the family module counts
# work the program does not run, and run.py refuses the run with the numbers
CEILING = 100.0

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _counted_work(config, cell):
    """What the configuration's family module counts for the chunk
    kernels of one step (``step_work``'s ``gated_delta``), or None: a
    model without the kind, or a module that counts no such work."""
    import importlib
    import sys

    model = config.get("model") or {}
    if "G" not in (model.get("layer_pattern") or ""):
        return None
    if BENCH not in sys.path:  # where the family modules live
        sys.path.insert(0, BENCH)
    module = importlib.import_module(config.get("flops", "flops"))
    return module.step_work(
        model, int(cell["batch"]), int(cell["seq"])
    ).get("gated_delta")


def CELLS(cell):
    """The cells whose configuration names a Gated DeltaNet layer and whose
    family module counts the chunk kernels' work. A cell of another data
    directory (a rehearsal's) is left to ``read``."""
    path = os.path.join(BENCH, "configs", f"{cell.get('config')}.json")
    try:
        with open(path) as f:
            config = json.load(f)
    except (OSError, ValueError, TypeError):
        return True
    try:
        return _counted_work(config, cell) is not None
    except (ImportError, KeyError, ValueError, TypeError, AttributeError):
        return False


def read(run):
    import flops
    import xplane

    if not run.trace or not run.trace.get("devices") or not run.peak:
        return None
    work = run.hook.step_work(
        run.config["model"], run.cell["batch"], run.cell["seq"]
    ).get("gated_delta")
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
        "gated_delta_kernels": found, "roofline": roof,
        "steps_traced": steps,
    }), flush=True)
    return 100.0 * roof["seconds"] / found["seconds"]
