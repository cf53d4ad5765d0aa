"""How often a grouped expert matmul runs in one train step: the count of
the traced stretch's device events (``xplane.step_stretch``) whose name
starts ``%ragged-dot-none`` (the grouped matmuls as
``kernel.moe_gmm_roofline`` finds them, without the ``%ragged-dot-metadata``
events that tile the groups beside them), over the stretch's whole steps.
An expert layer of two projections needs 6 a step (2 forward, 4 backward:
a data and a weight gradient each) and a gated one of three needs 9. A
chip's share of the experts (``parallel/moe._moe_share``) that makes a
round again in its hand-written backward pass ran the forward ones a second
time there (8 and 12 a layer), and a third time where a recomputed layer
(``remat``) made the round again before that; a share whose first round
keeps what its backward pass reads runs them once. So the number is 6 or 9
times the expert layers at its floor: lower is better. Read from the
device trace alone, so it reads the same way on a program that knows
nothing of what is kept. Nothing to read where the trace holds no grouped
matmul."""

import json

LAYER = "kernels"
UNIT = "runs"
MOVES = "tokens_per_s"

# as kernel.moe_gmm_roofline recognises the grouped matmuls, and of those
# the matmuls themselves
PREFIX = "%ragged-dot-none"


def CELLS(cell):
    return bool(cell.get("moe"))


def read(run):
    if not run.trace or not run.trace.get("devices"):
        return None
    device = run.trace["devices"][0]
    steps = device["steps"]
    rows = [r for r in device["ops"] if r["name"].startswith(PREFIX)]
    count = sum(r["count"] for r in rows)
    if not count or not steps:
        return None
    print(json.dumps({
        "grouped_matmul_runs": {
            "count": count, "seconds": sum(r["total_s"] for r in rows),
            "names": len(rows),
        },
        "steps_traced": steps,
    }), flush=True)
    return count / steps
