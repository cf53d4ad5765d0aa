"""The Pallas attention kernels' share of their roofline: the least time
the chip could take for the attention of the traced steps (the larger of
operations over the bf16 peak and bytes over HBM bandwidth, both from
shapes by ``flops.attention_kernel_work``) over the summed device time of
the kernels' events, forward and backward. Which bound holds is printed
on an earlier line."""

import json

LAYER = "kernels"
UNIT = "%"
MOVES = "tokens_per_s"

# how the kernels' events are recognised in the trace
# (a Pallas kernel is a custom-call whose target is tpu_custom_call; the
# step program holds no other Pallas kernel than flash attention's)
PATTERNS = ("tpu_custom_call",)


def CELLS(cell):
    return True


def read(run):
    import flops
    import xplane

    if not run.trace or not run.trace.get("devices") or not run.peak:
        return None
    t = run.window["trace"]
    steps = t["step_end"] - t["step_begin"]
    found = xplane.kernel_seconds(run.trace["devices"][0], PATTERNS)
    if not steps or not found["seconds"]:
        return None
    m = run.config["model"]
    work = flops.attention_kernel_work(
        run.cell["batch"], m["num_heads"], run.cell["seq"],
        m["model_dim"] // m["num_heads"],
    )
    layers = m["num_layers"] * steps
    work = {k: v * layers for k, v in work.items()}
    roof = flops.roofline_seconds(work, run.peak)
    print(json.dumps({
        "attention_kernels": found, "roofline": roof,
        "steps_traced": steps,
    }), flush=True)
    return 100.0 * roof["seconds"] / found["seconds"]
