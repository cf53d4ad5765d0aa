"""The grouped expert matmuls' share of their roofline: the least time the
chip could take for the expert projections of the traced stretch's whole
steps (``xplane.step_stretch``), which is the larger of operations over the
bf16 peak and bytes over HBM bandwidth, over the summed device time
of the grouped-matmul events, forward, recomputed forward and backward.
The operations and bytes of one step are the ``grouped_matmul`` of
``step_work`` in the configuration's family module (``run.hook``: which
layers are sparse, how many experts are held here and how many projections
an expert has is that module's knowledge and not this reader's).
Which bound holds is printed on an earlier line, with the seconds of the
other ``tpu_custom_call`` events (flash attention's) beside it.

How the events are recognised (looked at by hand in the compiled step
and in a trace, my chip run, PR 27): on the TPU, XLA lowers
``lax.ragged_dot`` to custom-calls of its own whose instruction names
start ``%ragged-dot`` (``%ragged-dot-none.N`` the matmul,
``%ragged-dot-metadata.N`` the tiling of the groups), with
``custom_call_target="tpu_custom_call"`` like a Pallas kernel's. They are
found by the start of that name, and not with ``xplane.kernel_seconds``,
which also searches an event's HLO text: a fusion that takes a grouped
matmul's result as an operand holds its name there. Nothing to read where
the model runs no grouped matmul or the trace holds no such event."""

import json

LAYER = "kernels"
UNIT = "%"
MOVES = "tokens_per_s"

PREFIX = "%ragged-dot"

# as kernel.attn_roofline's: above it run.py refuses the run
CEILING = 100.0


def CELLS(cell):
    return bool(cell.get("moe"))


def read(run):
    import flops
    import xplane

    if not run.trace or not run.trace.get("devices") or not run.peak:
        return None
    work = run.hook.step_work(
        run.config["model"], run.cell["batch"], run.cell["seq"]
    ).get("grouped_matmul")
    if work is None:
        return None
    device = run.trace["devices"][0]
    steps = device["steps"]
    rows = [r for r in device["ops"] if r["name"].startswith(PREFIX)]
    found = {
        "seconds": sum(r["total_s"] for r in rows),
        "count": sum(r["count"] for r in rows),
        "names": [r["name"] for r in rows],
    }
    if not found["seconds"]:
        return None
    work = {k: v * steps for k, v in work.items()}
    roof = flops.roofline_seconds(work, run.peak)
    every = xplane.kernel_seconds(device, ("tpu_custom_call",))
    print(json.dumps({
        "grouped_matmul_kernels": found, "roofline": roof,
        "steps_traced": steps,
        "other_custom_call_seconds": every["seconds"] - found["seconds"],
    }), flush=True)
    return 100.0 * roof["seconds"] / found["seconds"]
